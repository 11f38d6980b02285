"""The plain reference against the engine's paged path on the CPU at
smoke size: prefill through chunked mixed steps, a second round that
extends from prefix-cache pages, and decode through the page pool.

The engine in float32 agrees with the float32 reference to
``F32_TOL``: both compute the same sums in float32 and differ only in
their order.  The engine in bfloat16 (8 mantissa bits, ~0.4% per
rounding) misses that tolerance by orders of magnitude, so the
comparison can tell the precision the configuration states from the
step below it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.families import dense_gqa, dense_gqa_ref as ref

CONF = {
    "name": "smoke", "source": "test", "family": "dense_gqa",
    "model_type": "qwen3", "head_dim": 16, "hidden_size": 64,
    "intermediate_size": 128, "num_attention_heads": 4,
    "num_hidden_layers": 2, "num_key_value_heads": 2,
    "rms_norm_eps": 1e-6, "rope_theta": 1000000,
    "tie_word_embeddings": False, "vocab_size": 512,
}
SERVE = {"max_batch": 2, "max_seq": 256, "page_size": 16, "num_pages": 40}
F32_TOL = 1e-4      # max |logit difference| in units of the row's sd


def recording_engine():
    from repro.serving.engine import Engine

    class Recording(Engine):
        def _sample_rows(self, logits):
            rows = np.asarray(jnp.asarray(logits, jnp.float32))
            for slot, req in enumerate(self.slots):
                if req is not None:
                    self.rows[(req.uid, len(req.output))] = rows[slot]
            return super()._sample_rows(logits)
    return Recording


def served_rows(dtype: str):
    """Two rounds of two conversations; returns [(prompt, output, rows)]
    with the logits row behind each served token."""
    from repro.serving.request import Request
    conf = dict(CONF, dtype=dtype)
    key = jax.random.PRNGKey(3)
    eng, _ = dense_gqa.build(conf, key, SERVE, engine_cls=recording_engine())
    eng.rows = {}
    rng = np.random.default_rng(0)
    convos = [rng.integers(3, 512, n).tolist() for n in (45, 70)]
    out = []
    for _ in range(2):
        reqs = [Request(prompt=list(c), max_new_tokens=9, eos_id=None)
                for c in convos]
        for r in reqs:
            eng.submit(r)
        eng.run()
        for c, r in zip(convos, reqs):
            out.append((r.prompt, list(r.output),
                        [eng.rows[(r.uid, i)] for i in range(len(r.output))]))
            c += r.output + rng.integers(3, 512, 11).tolist()
    assert eng.stats()["prefix_cache"]["hits"] >= 2
    return conf, key, out


def worst_diff(dtype: str) -> float:
    conf, key, served = served_rows(dtype)
    w = jax.jit(lambda k: ref.draw_weights(conf, k))(key)
    worst = 0.0
    for prompt, output, rows in served:
        seq = prompt + output[:-1]
        want = ref.next_token_logits(conf, w, seq,
                                     list(range(len(prompt) - 1, len(seq))))
        got = np.stack(rows)
        worst = max(worst, float(np.max(np.abs(got - want)
                                        / want.std(-1, keepdims=True))))
    return worst


def test_float32_engine_matches_reference():
    assert worst_diff("float32") < F32_TOL


def test_bfloat16_engine_misses_float32_tolerance():
    assert worst_diff("bfloat16") > 10 * F32_TOL


def test_served_gaps_zero_on_own_argmax():
    """A sequence whose tokens are the reference's own greedy choices
    reads a gap of 0 at every position; the fp8 control does not."""
    conf = dict(CONF, dtype="bfloat16", vocab_size=2048)
    w = jax.jit(lambda k: ref.draw_weights(conf, k))(jax.random.PRNGKey(1))
    seq = np.random.default_rng(1).integers(3, 2048, 30).tolist()
    out = []
    for _ in range(40):
        nxt = ref.next_token_logits(conf, w, seq + out, [len(seq + out) - 1])
        out.append(int(nxt[0].argmax()))
    assert max(ref.served_gaps(conf, w, seq, out)) == 0.0
    assert max(ref.served_gaps(conf, w, seq, out, control="fp8")) > 0.0


@pytest.mark.parametrize("n", [1, 1024, 1025, 4096])
def test_bucket_holds_the_sequence(n):
    assert ref.bucket(n) >= n and ref.bucket(n) % ref.Q_BLOCK == 0
