"""Bring-up check on a TPU: Qwen3-0.6B at its published widths (bf16,
28 layers, weights drawn from ``--seed``), served through the normal
entry points — repro.launch.serve's helpers -> Engine -> paged KV pool
-> Pallas paged kernels — with what comes out checked on the chip.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # 1x4 serve mesh vs one chip, and
                                        # four replicas on four devices

Each phase prints what it found.  The last line of standard output is
one JSON object, ``{"ok": true, "device": {"platform", "kind",
"count"}}``.  Where JAX finds no TPU, or any phase fails or misses its
bound, the script exits non-zero and prints no such line.  The XLA
compilation cache lives where ``JAX_COMPILATION_CACHE_DIR`` says, else
in the checkout's ``.jax_cache/`` (launch/compile_cache.py).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

ARCH = "qwen3_0_6b"
SERVE = dict(max_batch=8, max_seq=2048, page_size=16)
# main serve: reflection rounds over ~1,000-token prompts
REQUESTS, ROUNDS, PROMPT_LEN, MAX_NEW = 8, 2, 1000, 64
# path comparisons (pallas vs xla, mesh vs one chip): a short decode
CMP_PROMPT_LEN, CMP_NEW = 200, 8
CHECK_PROMPT_LEN = 300          # logits-vs-forward prompt

# bf16 tolerances on logits, as max |difference| over the vocabulary in
# units of the reference row's standard deviation.  bf16 keeps 8
# mantissa bits, so each of the 28 layers' matmuls rounds at ~0.4%;
# a wrong page, mask, position or shard moves logits by O(1) of it.
FORWARD_TOL = 0.25      # engine (bf16) vs the model's forward in float32
PATH_TOL = 0.25         # two bf16 engine paths on identical context


def fail(msg: str):
    raise SystemExit(f"chip_smoke: FAIL: {msg}")


def check(cond: bool, msg: str) -> None:
    if not cond:
        fail(msg)


def make_recording_engine(model, params, scfg, mesh=None):
    """An Engine that, while ``record`` is set, keeps every sampled
    logits row on the host as float32, keyed (request uid, output
    index) — the row that chose that output token."""
    from repro.serving.engine import Engine

    class RecordingEngine(Engine):
        record = False

        def _sample_rows(self, logits):
            if self.record:
                rows = np.asarray(jnp.asarray(logits, jnp.float32))
                for slot, req in enumerate(self.slots):
                    if req is not None:
                        self.rows[(req.uid, len(req.output))] = rows[slot]
            return super()._sample_rows(logits)

    eng = RecordingEngine(model, params, scfg, mesh=mesh)
    eng.rows = {}
    return eng


def rel_diff(got: np.ndarray, ref: np.ndarray) -> float:
    return float(np.max(np.abs(got - ref)) / np.std(ref))


def serve(eng, prompts, max_new: int, record: bool = False):
    """Serve ``prompts`` to completion; every request must end with
    exactly ``max_new`` tokens."""
    from repro.serving.request import Request, Status
    eng.record = record
    reqs = [Request(prompt=list(p), max_new_tokens=max_new, eos_id=None)
            for p in prompts]
    for r in reqs:
        eng.submit(r)
    eng.run()
    eng.record = False
    for r in reqs:
        check(r.status is Status.DONE and r.stop_reason == "max_tokens"
              and len(r.output) == max_new,
              f"request {r.uid} ended {r.status} / {r.stop_reason} with "
              f"{len(r.output)} of {max_new} tokens")
    return reqs


def compare_paths(a, b, prompts, label: str) -> None:
    """Greedy decode of the same prompts on engines ``a`` and ``b``.
    Before any two outputs differ their contexts are identical, so the
    logits rows of every step up to and including the first split must
    agree within PATH_TOL; a split can then only fall on a near-tie
    (top-2 margin below twice the measured difference)."""
    ra = serve(a, prompts, CMP_NEW, record=True)
    rb = serve(b, prompts, CMP_NEW, record=True)
    worst, first_step, same, splits = 0.0, 0.0, 0, []
    for x, y in zip(ra, rb):
        split = next((i for i, (s, t) in enumerate(zip(x.output, y.output))
                      if s != t), None)
        for k in range(CMP_NEW if split is None else split + 1):
            d = rel_diff(b.rows[(y.uid, k)], a.rows[(x.uid, k)])
            worst = max(worst, d)
            if k == 0:
                first_step = max(first_step, d)
        if split is None:
            same += 1
        else:
            row = a.rows[(x.uid, split)]
            margin = float(row[x.output[split]] - row[y.output[split]]) \
                / np.std(row)
            splits.append(f"req{len(splits)}@{split} margin={margin:.4f}sd")
            # b picked y over x although no logit moved by more than d
            check(margin <= 2 * d, f"{label}: request {x.uid} split at "
                  f"step {split} on a margin of {margin:.4f} sd, more than "
                  f"twice the logit difference {d:.4f} sd")
    print(f"{label}: first-step logits max|diff| = {first_step:.4f} sd, "
          f"over all compared steps {worst:.4f} sd (bound {PATH_TOL} sd)")
    print(f"{label}: greedy tokens identical for {same}/{len(prompts)} "
          f"requests over {CMP_NEW} tokens; near-tie splits: "
          f"{', '.join(splits) or 'none'}")
    check(worst <= PATH_TOL, f"{label} logits differ by {worst:.4f} sd")


def forward_logits(cfg, params, tokens) -> np.ndarray:
    """Last-position logits of the model's own forward pass over
    ``tokens``, in float32 with full-precision matmuls."""
    from repro.models.registry import build_model
    cfg32 = cfg.replace(dtype="float32", param_dtype="float32")
    m32 = build_model(cfg32)
    p32 = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        logits, _ = jax.jit(m32.forward)(
            p32, {"tokens": jnp.asarray([tokens], jnp.int32)})
    return np.asarray(logits[0, -1], np.float32)


def print_compile(eng, label: str) -> None:
    cs = eng.compile_stats
    shapes = {n: f.warmed for n, f in eng._step_fns().items()}
    per_fn = {n: [round(s, 2) for s in v]
              for n, v in cs["per_fn_compile_s"].items()}
    print(f"{label}: AOT compile {cs['startup_compile_s']:.2f}s; shapes "
          f"{shapes} (decode [{eng.scfg.max_batch},1], mixed "
          f"[{eng.scfg.max_batch},w] w in {eng._mixed_buckets}); per-fn "
          f"seconds {per_fn}")


def one_chip(cfg, model, params, seed: int) -> None:
    from repro.configs.base import ServeConfig
    from repro.launch.serve import reflection_prompts, serve_rounds

    V = cfg.vocab_size
    scfg = ServeConfig(**SERVE, aot_warmup=True)
    eng = make_recording_engine(model, params, scfg)
    print(f"engine: attn_impl {eng.attn_impl}, kv_dtype {eng.kv_dtype}, "
          f"{eng.pool.num_pages} pages of {scfg.page_size} tokens")
    check(eng.attn_impl == "pallas",
          f"one chip should serve with the Pallas kernels, got "
          f"{eng.attn_impl}")
    print_compile(eng, "engine")

    convos = reflection_prompts(REQUESTS, PROMPT_LEN, V, seed)
    t0 = time.perf_counter()
    served = serve_rounds(eng, convos, ROUNDS, MAX_NEW, V, seed)
    dt = time.perf_counter() - t0
    for r in (r for rnd in served for r in rnd):
        check(r.stop_reason == "max_tokens" and len(r.output) == MAX_NEW,
              f"request {r.uid}: {r.stop_reason}, {len(r.output)} tokens")
    st = eng.stats()
    print(f"served {REQUESTS} requests x {ROUNDS} rounds (last-round "
          f"prompts {min(len(r.prompt) for r in served[-1])}-"
          f"{max(len(r.prompt) for r in served[-1])} tokens) in {dt:.2f}s: "
          f"prefill {st['prefill_tokens']} "
          f"+ extend {st['extend_tokens']} tokens, {st['decode_steps']} "
          f"decoded tokens, {st['mixed_steps']} mixed + "
          f"{st['decode_batch_steps']} decode steps; prefix cache "
          f"{st['prefix_cache']['hits']} hits, "
          f"{st['prefix_cache']['tokens_saved']} tokens saved")
    print(f"mid-serve recompiles: {st['step_compiles']}")
    check(st["step_compiles"] == 0, "the serve loop recompiled")

    # logits vs the model's own forward over the same tokens
    prompt = reflection_prompts(1, CHECK_PROMPT_LEN, V, seed + 7)[0]
    (req,) = serve(eng, [prompt], 1, record=True)
    got = eng.rows[(req.uid, 0)]
    ref = forward_logits(cfg, params, prompt)
    check(got.shape == ref.shape == (V,) and np.isfinite(got).all(),
          f"engine logits shape {got.shape} / finite "
          f"{bool(np.isfinite(got).all())}")
    err = rel_diff(got, ref)
    print(f"prefill logits vs float32 forward ({CHECK_PROMPT_LEN} tokens): "
          f"max|diff| = {err:.4f} sd (bound {FORWARD_TOL} sd), argmax "
          f"{int(got.argmax())} vs {int(ref.argmax())}")
    check(err <= FORWARD_TOL, f"engine logits off the forward by {err:.4f} sd")

    xla = make_recording_engine(model, params,
                                ServeConfig(**SERVE, aot_warmup=True,
                                            attn_impl="xla"))
    print_compile(xla, "xla engine")
    prompts = reflection_prompts(REQUESTS, CMP_PROMPT_LEN, V, seed + 11)
    compare_paths(eng, xla, prompts, "pallas vs xla")
    check(eng.stats()["step_compiles"] == 0
          and xla.stats()["step_compiles"] == 0, "an engine recompiled")


def four_chips(cfg, model, params, seed: int) -> None:
    from repro.configs.base import ServeConfig
    from repro.launch.mesh import make_device_mesh
    from repro.launch.serve import reflection_prompts
    from repro.serving.engine import Engine
    from repro.serving.fleet import EngineReplica, Router, RouterConfig
    from repro.serving.trace import TraceConfig, generate_trace

    V = cfg.vocab_size
    devices = jax.devices()[:4]
    # (a) a 1x4 serve mesh against the same requests on one chip; both
    # engines compile at once
    scfg = ServeConfig(**SERVE)
    one = make_recording_engine(model, params, scfg)
    mesh = make_recording_engine(model, params,
                                 ServeConfig(**SERVE, mesh="1x4"))
    t0 = time.perf_counter()
    with ThreadPoolExecutor(2) as ex:
        list(ex.map(lambda e: e.aot_compile(), (one, mesh)))
    print(f"1x4 mesh: attn_impl {mesh.attn_impl} (one chip: "
          f"{one.attn_impl}); both engines compiled in "
          f"{time.perf_counter() - t0:.2f}s")
    print_compile(mesh, "1x4 mesh engine")
    print_compile(one, "one-chip engine")
    check(mesh.n_devices == 4, f"mesh engine spans {mesh.n_devices} devices")
    prompts = reflection_prompts(REQUESTS, CMP_PROMPT_LEN, V, seed + 11)
    compare_paths(one, mesh, prompts, "1x4 mesh vs one chip")
    st = mesh.stats()
    print(f"1x4 mesh: resident KV {st['resident_kv_bytes_per_device']} "
          f"B/device of {st['resident_kv_bytes']} B; mid-serve recompiles "
          f"{st['step_compiles']} / {one.stats()['step_compiles']}")
    check(st["step_compiles"] == 0 and one.stats()["step_compiles"] == 0,
          "an engine recompiled")
    del one, mesh

    # (b) four replicas behind the router, one per device
    engines = [Engine(model, params, scfg, mesh=make_device_mesh(d))
               for d in devices]
    homes = [{d for x in jax.tree_util.tree_leaves(e.params)
              for d in x.devices()} for e in engines]
    print(f"replicas: params on {[sorted(str(d) for d in h) for h in homes]}")
    check(all(len(h) == 1 for h in homes)
          and len(set().union(*homes)) == 4,
          "the four replicas' params do not sit on four distinct devices")
    t0 = time.perf_counter()
    with ThreadPoolExecutor(4) as ex:
        list(ex.map(lambda e: e.aot_compile(), engines))
    print(f"replicas: attn_impl {[e.attn_impl for e in engines]}; compiled "
          f"in {time.perf_counter() - t0:.2f}s")
    replicas = [EngineReplica(i, e) for i, e in enumerate(engines)]
    trace = generate_trace(TraceConfig(n_requests=32, seed=seed,
                                       vocab=V, out_tokens=(4, 16)))
    router = Router(replicas, RouterConfig(policy="affinity"))
    t0 = time.perf_counter()
    report = router.run_trace(trace)
    s = report.summary()
    served = [sum(1 for _, rid in report.assignments if rid == i)
              for i in range(4)]
    print(f"replicas: {s['requests']} trace requests in "
          f"{time.perf_counter() - t0:.2f}s, per replica {served}, "
          f"prefix hit rate {s['prefix_hit_rate']}, recompiles "
          f"{[e.stats()['step_compiles'] for e in engines]}")
    check(s["requests"] == len(trace)
          and all(c["reason"] == "max_tokens" for c in report.completions),
          "a trace request did not complete")
    check(all(n > 0 for n in served), f"a replica served nothing: {served}")
    check(all(e.stats()["step_compiles"] == 0 for e in engines),
          "a replica recompiled")
    check(router.shutdown_check() == 0, "pages leaked")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip paths (needs 4 TPUs)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (platform "
                         f"{dev.platform}); nothing was run")
    need = 4 if args.four_chips else 1
    check(len(devices) >= need, f"needs {need} TPU chips, found "
          f"{len(devices)}")
    print(f"device: {dev.device_kind} x {len(devices)}")

    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.serve import load_model
    events = {"hits": 0, "misses": 0}

    def on_event(event, **_):
        for k in events:
            if event == f"/jax/compilation_cache/cache_{k}":
                events[k] += 1
    jax.monitoring.register_event_listener(on_event)
    print(f"compile cache: {enable_compile_cache()}")

    t0 = time.perf_counter()
    cfg, model, params = load_model(ARCH, smoke=False, seed=args.seed)
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    print(f"model {cfg.name}: {cfg.num_layers} layers, d_model "
          f"{cfg.d_model}, {cfg.num_heads}/{cfg.num_kv_heads} heads of "
          f"{cfg.head_dim}, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, "
          f"{n_params} params in {cfg.param_dtype}, {cfg.dtype} "
          f"activations (built in {time.perf_counter() - t0:.2f}s)")

    (four_chips if args.four_chips else one_chip)(cfg, model, params,
                                                   args.seed)

    mem = [d.memory_stats() or {} for d in devices[:need]]
    print(f"peak device memory: "
          f"{[m.get('peak_bytes_in_use') for m in mem]} B of "
          f"{[m.get('bytes_limit') for m in mem]} B")
    print(f"compile cache: {events['hits']} hits, {events['misses']} "
          f"misses")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
