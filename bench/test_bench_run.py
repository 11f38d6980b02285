"""The harness end to end on the CPU at smoke size: a configuration, a
mix and the metrics found by name in a directory of their own, a run
that comes out correct, and runs with the timed path broken underneath
that come out not correct.  The look for a TPU is skipped
(``require_tpu=False``); everything after it is the run as on the chip.
"""
from __future__ import annotations

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench.families import dense_gqa_ref as ref

BENCH = Path(__file__).resolve().parent

SMOKE_CONF = {
    "name": "smoke", "source": "test", "family": "dense_gqa",
    "model_type": "qwen3", "head_dim": 16, "hidden_size": 64,
    "intermediate_size": 128, "num_attention_heads": 4,
    "num_hidden_layers": 2, "num_key_value_heads": 2,
    "rms_norm_eps": 1e-6, "rope_theta": 1000000,
    "tie_word_embeddings": True, "vocab_size": 2048, "dtype": "bfloat16",
    "serve": {"max_batch": 4, "max_seq": 256, "page_size": 16,
              "num_pages": 96},
    # bf16 program vs float32 reference read 0 to 0.011 sd on seeds 1-3
    # at this size, the fp8 control 0.23 to 0.36 sd
    "check": {"served_gap_sd": 0.05},
}
SMOKE_MIX = {
    "kind": "sessions", "rate_per_s": 4.0, "domains": 2,
    "system_prompt_tokens": 32, "shared_system_prompt": True,
    "body_tokens": [8, 40], "rounds": [1, 3], "feedback_tokens": [4, 12],
    "output_tokens": [16, 40], "think_s": 0.0, "ramp_s": 0.5, "backlog": 6,
    "check_requests": 6,
}
SECONDS = 1.5


def smoke_root(tmp: Path, conf=None, mix=None, metric_src=None,
               per_layer=()) -> Path:
    """A checkout-like directory holding one smoke cell."""
    (tmp / "bench" / "configs").mkdir(parents=True)
    (tmp / "bench" / "traffic").mkdir()
    (tmp / "bench" / "metrics").mkdir()
    (tmp / "bench" / "configs" / "smoke.json").write_text(
        json.dumps(conf or SMOKE_CONF))
    (tmp / "bench" / "traffic" / "chat.json").write_text(
        json.dumps(mix or SMOKE_MIX))
    per_layer = list(per_layer)
    for m in per_layer:
        (tmp / "bench" / "metrics" / f"{m['name']}.py").write_text(
            (BENCH / "metrics" / f"{m['name']}.py").read_text())
    for name, src in (metric_src or {}).items():
        (tmp / "bench" / "metrics" / f"{name}.py").write_text(src)
        per_layer.append({"name": name, "unit": "%", "better": "higher",
                          "source": "program_counter", "layer": "test",
                          "moves": "itl_p50_ms"})
    bench = {
        "workloads": [{"name": "smoke.chat", "config": "smoke",
                       "traffic": "chat", "chips": 1, "why": "test"}],
        "end_to_end": [
            {"name": n, "unit": u, "better": "lower", "bound": 0.25,
             "source": "host_clock"}
            for n, u in (("ttft_p90_ms", "ms"), ("itl_p50_ms", "ms"),
                         ("itl_p95_ms", "ms"), ("output_tok_s", "tokens/s"),
                         ("setup_s", "s"))],
        "per_layer": per_layer,
    }
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp


def run(root: Path, seed: int = 2**31 + 7, trace: bool = False, **kw):
    return harness.run_cell("smoke.chat", seed, SECONDS, trace, root=root,
                            require_tpu=False, log=lambda m: None, **kw)


def test_discovery_by_name(tmp_path):
    """A configuration, a mix and a metric reader dropped into a new
    directory are found from BENCHMARK.json by name alone."""
    root = smoke_root(tmp_path, metric_src={
        "requests_seen":
            "def read(run):\n    return float(len(run.records))\n"})
    spec = harness.load_cell("smoke.chat", root)
    assert spec["conf"]["name"] == "smoke"
    assert spec["mix"]["rate_per_s"] == SMOKE_MIX["rate_per_s"]
    assert [m["name"] for m in spec["per_layer"]] == ["requests_seen"]
    reader = harness.metric_reader("requests_seen", root)
    assert reader(type("R", (), {"records": [1, 2]})()) == 2.0


@pytest.fixture(scope="module")
def good(tmp_path_factory):
    return run(smoke_root(tmp_path_factory.mktemp("good")))


def test_run_is_correct_and_reports_every_metric(good):
    assert good["correct"] is True, good["checks"]
    assert good["attempted"] > 0 and good["failed"] == 0
    assert set(good["metrics"]) == {"ttft_p90_ms", "itl_p50_ms",
                                    "itl_p95_ms", "output_tok_s", "setup_s"}
    assert all(m["value"] > 0 for m in good["metrics"].values())
    assert good["device"]["platform"] == "cpu"
    assert list(good)[-1] == "checks"
    assert good["checks"]["served_gap_sd"]["value"] <= \
        SMOKE_CONF["check"]["served_gap_sd"]
    assert good["checks"]["later_rounds_checked"]["value"] >= 1


class _Broken:
    """Engine subclasses that break the timed path underneath."""

    @staticmethod
    def token_altered():
        from repro.serving.engine import Engine

        class E(Engine):
            def _sample_rows(self, logits):
                out = np.array(super()._sample_rows(logits))
                return (out + 1) % self.cfg.vocab_size
        return E

    @staticmethod
    def state_unchanged():
        """Every model step hands back the KV pool it was given."""
        from repro.serving.engine import Engine

        class Stale:
            def __init__(self, fn):
                self.fn = fn

            def __getattr__(self, name):
                return getattr(self.fn, name)

            def __call__(self, params, cache, *args):
                fresh = jax.tree_util.tree_map(jnp.copy, cache)
                logits, _ = self.fn(params, fresh, *args)
                return logits, cache

        class E(Engine):
            def __init__(self, *a, **k):
                super().__init__(*a, **k)
                self._decode = Stale(self._decode)
                self._mixed = Stale(self._mixed)
        return E


    @staticmethod
    def half_batch():
        """Every model step computes only the first half of its rows; the
        rest take the logits of a row from that half."""
        from repro.serving.engine import Engine

        class Half:
            def __init__(self, fn):
                self.fn = fn

            def __getattr__(self, name):
                return getattr(self.fn, name)

            def __call__(self, params, cache, *args):
                logits, cache = self.fn(params, cache, *args)
                h = logits.shape[0] // 2
                return jnp.concatenate([logits[:h], logits[:h]]), cache

        class E(Engine):
            def __init__(self, *a, **k):
                super().__init__(*a, **k)
                self._decode = Half(self._decode)
                self._mixed = Half(self._mixed)
        return E


@pytest.mark.parametrize("fault", ["token_altered", "state_unchanged",
                                   "half_batch"])
def test_broken_timed_path_is_not_correct(tmp_path, fault):
    res = run(smoke_root(tmp_path), engine_cls=getattr(_Broken, fault)())
    assert res["correct"] is False
    assert res["checks"]["served_gap_sd"]["value"] > \
        SMOKE_CONF["check"]["served_gap_sd"]


def test_control_precision_fails_the_limit(tmp_path):
    """The reference computed one step below the stated precision, put
    in the program's place, reads above the limit on the same tokens."""
    seed = 2**31 + 7
    b = harness.build(harness.load_cell("smoke.chat", smoke_root(tmp_path)),
                      seed, SECONDS, require_tpu=False, log=lambda m: None)
    win = b.window()
    win.run()
    correct, checks, sample = harness.check(b, win)
    assert correct is True
    conf = SMOKE_CONF
    ctl = harness.check_outputs(conf, seed, sample,
                                control=ref.CONTROL[conf["dtype"]])
    assert max(ctl) > conf["check"]["served_gap_sd"]


def test_program_state_freed_before_reference(tmp_path, monkeypatch):
    """The reference runs only once nothing holds the engine any more, so
    that its memory is free on the chip."""
    import weakref
    b = harness.build(harness.load_cell("smoke.chat", smoke_root(tmp_path)),
                      3, SECONDS, require_tpu=False, log=lambda m: None)
    win = b.window()
    win.run()
    alive = weakref.ref(b.eng)
    seen = []
    real = harness.check_outputs

    def spy(*a, **k):
        seen.append(alive())
        return real(*a, **k)
    monkeypatch.setattr(harness, "check_outputs", spy)
    harness.check(b, win)
    assert seen == [None]


def test_no_later_round_checked_is_not_correct(tmp_path, monkeypatch):
    """In a mix of multi-round sessions a run whose check holds no later
    round (here none is due before the run ends) is not correct, even
    with every sampled round 1 served right."""
    monkeypatch.setattr(harness, "DRAIN_MAX_S", 0.0)
    res = run(smoke_root(tmp_path, mix=dict(SMOKE_MIX, think_s=1e3)))
    assert res["correct"] is False
    assert res["checks"]["later_rounds_checked"] == {"value": 0,
                                                     "at_least": 1}
    assert res["checks"]["served_gap_sd"]["value"] <= \
        SMOKE_CONF["check"]["served_gap_sd"]


def test_traced_run_reports_per_layer_metrics(tmp_path):
    """The --trace 1 path on the CPU: the committed per-layer readers run
    on a real profiler trace.  The paged kernel does not run off the TPU,
    so its reader finds nothing and the metric is left out."""
    with open(BENCH.parent / "BENCHMARK.json") as f:
        per_layer = [dict(m, workloads=["smoke.chat"])
                     for m in json.load(f)["per_layer"]]
    res = run(smoke_root(tmp_path, per_layer=per_layer), trace=True)
    got = set(res["metrics"])
    assert got == {m["name"] for m in per_layer} - {
        "paged_attention_roofline"}, got
    assert 0 < res["device"]["busy_s"] <= res["device"]["window_s"]
    assert res["breakdown"]["device_ops"] and res["breakdown"]["idle_gaps"]
    assert res["correct"] is True


def test_single_shot_mix_without_ttft(tmp_path):
    """A ``requests`` mix with a backlog, in a cell that reports no time
    to first token: the run stops at the window's end (no drain) and a
    request still queued there is not counted as failed."""
    mix = {"kind": "requests", "rate_per_s": 3.0, "backlog": 4,
           "prompt_tokens": [16, 48], "output_tokens": [20, 60],
           "ramp_s": 0.5, "check_requests": 3}
    root = smoke_root(tmp_path, mix=mix)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["end_to_end"] = [m for m in bench["end_to_end"]
                           if not m["name"].startswith("ttft")]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    res = run(root)
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"itl_p50_ms", "itl_p95_ms",
                                   "output_tok_s", "setup_s"}
