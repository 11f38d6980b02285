"""Serving launcher: ``python -m repro.launch.serve --arch <id> [--smoke]``.

Runs reflection-style conversations through the engine — every round
resubmits each conversation, which then grows by the model's draft and
a feedback turn — and prints token counts, compile and cache statistics.

Without ``--smoke`` the model is the architecture's published ``CONFIG``
in bfloat16 (params and activations), with weights drawn from
``--seed``: the path ``chip_smoke.py`` drives on a TPU.  ``--smoke``
serves the reduced ``smoke_config()`` in float32, the CPU path (run it
under ``JAX_PLATFORMS=cpu``; Pallas kernels then run in interpret mode).

``--mesh DxM`` serves mesh-sharded (docs/SERVING.md#sharded-serving):
params tensor-parallel along 'model', the paged KV pool sharded by
physical page.  Under ``JAX_PLATFORMS=cpu`` the devices come from
``xla_force_host_platform_device_count``, which must be set BEFORE the
first jax import — which is why jax is imported inside main(), after
argparse.  ``--aot`` pre-compiles every step shape at startup and prints
the compile time; the serve loop then reports the recompile tripwire.

``--replicas N`` serves a FLEET instead of one engine: N engines behind
the prefix-affinity router (docs/SERVING.md#fleet-routing), each pinned
to its own device when there are several, replaying a seeded trace
(``--trace-requests`` arrivals) and printing the fleet report —
per-replica assignment counts, p50/p99 TTFT, goodput, fleet prefix-hit
rate, spillovers/steals.  ``--policy round_robin`` swaps in the
baseline for an A/B.
"""
import argparse
import os
import time

# Per-mode defaults for the options left unset on the command line.
SMOKE_DEFAULTS = dict(requests=6, rounds=2, max_new=12, max_batch=4,
                      max_seq=512, prompt_len=24)
FULL_DEFAULTS = dict(requests=8, rounds=2, max_new=64, max_batch=8,
                     max_seq=2048, prompt_len=1000)
FEEDBACK_TOKENS = 16        # feedback turn appended after each round
PAGE_SIZE = 16


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="reflect_demo_100m")
    ap.add_argument("--smoke", action="store_true",
                    help="serve smoke_config() in float32 (the CPU path)")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the weights and the prompts")
    ap.add_argument("--requests", type=int)
    ap.add_argument("--rounds", type=int)
    ap.add_argument("--max-new", type=int)
    ap.add_argument("--max-batch", type=int)
    ap.add_argument("--max-seq", type=int)
    ap.add_argument("--prompt-len", type=int,
                    help="first-round prompt length in tokens")
    ap.add_argument("--no-prefix-cache", action="store_true")
    ap.add_argument("--mesh", default=None, metavar="DxM",
                    help="serve mesh, e.g. 1x2 (data x model)")
    ap.add_argument("--aot", action="store_true",
                    help="AOT-compile every step shape at startup")
    ap.add_argument("--replicas", type=int, default=0, metavar="N",
                    help="serve a fleet of N engines behind the router")
    ap.add_argument("--policy", default="affinity",
                    choices=("affinity", "round_robin"),
                    help="fleet routing policy (with --replicas)")
    ap.add_argument("--trace-requests", type=int, default=24,
                    help="trace arrivals to replay (with --replicas)")
    ap.add_argument("--trace-seed", type=int, default=0)
    args = ap.parse_args(argv)
    for k, v in (SMOKE_DEFAULTS if args.smoke else FULL_DEFAULTS).items():
        if getattr(args, k) is None:
            setattr(args, k, v)
    return args


def cpu_platform() -> bool:
    """True when ``JAX_PLATFORMS`` selects the CPU — the only platform
    on which host devices can be forced."""
    return os.environ.get("JAX_PLATFORMS", "").split(",")[0] == "cpu"


def load_model(arch: str, smoke: bool, seed: int):
    """(cfg, model, params): the published config in bfloat16, or the
    smoke config in float32, with weights drawn from ``seed``."""
    import jax

    from repro.models.registry import build_model, get_config, get_smoke_config

    if smoke:
        cfg = get_smoke_config(arch).replace(dtype="float32")
    else:
        cfg = get_config(arch).replace(dtype="bfloat16",
                                       param_dtype="bfloat16")
    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    return cfg, model, params


def reflection_prompts(n: int, prompt_len: int, vocab: int, seed: int):
    """``n`` first-round prompts of ``prompt_len`` tokens drawn from
    ``seed`` (token 1 opens each, as BOS)."""
    import numpy as np
    rng = np.random.default_rng(seed)
    return [[1] + rng.integers(3, vocab, prompt_len - 1).tolist()
            for _ in range(n)]


def serve_rounds(engine, convos, rounds: int, max_new: int, vocab: int,
                 seed: int):
    """Serve ``rounds`` reflection rounds over ``convos`` (grown in
    place: draft + a seeded feedback turn per round).  Returns the
    requests of every round."""
    import numpy as np

    from repro.serving.request import Request
    rng = np.random.default_rng(seed + 1)
    served = []
    for _ in range(rounds):
        reqs = [Request(prompt=list(c), max_new_tokens=max_new, eos_id=None)
                for c in convos]
        for r in reqs:
            engine.submit(r)
        engine.run()
        for c, r in zip(convos, reqs):
            c += r.output + rng.integers(3, vocab, FEEDBACK_TOKENS).tolist()
        served.append(reqs)
    return served


def main(argv=None):
    args = parse_args(argv)
    need = args.replicas
    if args.mesh:
        d, _, t = args.mesh.partition("x")
        need = max(need, int(d) * int(t or 1))
    if (need > 1 and cpu_platform() and "xla_force_host_platform_device_count"
            not in os.environ.get("XLA_FLAGS", "")):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={need}").strip()

    import jax

    from repro.configs.base import ServeConfig
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_device_mesh
    from repro.serving.engine import Engine

    print(f"compile cache: {enable_compile_cache()}")
    cfg, model, params = load_model(args.arch, args.smoke, args.seed)
    print(f"model {cfg.name}: {cfg.num_layers} layers, d_model "
          f"{cfg.d_model}, {cfg.dtype} activations, {cfg.param_dtype} "
          f"params; devices {jax.device_count()} x "
          f"{jax.devices()[0].device_kind}")

    if args.replicas > 0:
        from collections import Counter

        from repro.serving.fleet import EngineReplica, Router, RouterConfig
        from repro.serving.trace import TraceConfig, generate_trace

        scfg = ServeConfig(max_batch=args.max_batch, max_seq=args.max_seq,
                           page_size=PAGE_SIZE, aot_warmup=args.aot,
                           prefix_cache=not args.no_prefix_cache)
        devices = jax.devices()
        t_init = time.perf_counter()
        # one replica per device while devices last (wrapping after)
        replicas = [EngineReplica(i, Engine(
            model, params, scfg,
            mesh=(make_device_mesh(devices[i % len(devices)])
                  if len(devices) > 1 else None)))
            for i in range(args.replicas)]
        startup = time.perf_counter() - t_init
        trace = generate_trace(TraceConfig(
            n_requests=args.trace_requests, seed=args.trace_seed,
            mean_rate=50.0, vocab=cfg.vocab_size,
            out_tokens=(4, args.max_new)))
        router = Router(replicas, RouterConfig(policy=args.policy))
        t0 = time.perf_counter()
        report = router.run_trace(trace)
        dt = time.perf_counter() - t0
        s = report.summary()
        per_rep = Counter(rid for _, rid in report.assignments)
        print(f"fleet: {args.replicas} replicas, policy={args.policy}, "
              f"{s['requests']} requests in {dt:.2f}s "
              f"(startup {startup:.2f}s)")
        print(f"  assignment: "
              + " ".join(f"r{i}={per_rep.get(i, 0)}"
                         for i in range(args.replicas))
              + f"  spillovers={s['spillovers']} steals={s['steals']}")
        print(f"  ttft p50={s['p50_ttft_ms']:.1f}ms "
              f"p99={s['p99_ttft_ms']:.1f}ms goodput={s['goodput']:.3f} "
              f"prefix_hit_rate={s['prefix_hit_rate']:.3f}")
        print(f"  preempt/slo/timeout: {s['preemptions']}"
              f"/{s['slo_rejections']}/{s['timeouts']}")
        leaked = router.shutdown_check()
        print(f"  leaked pages after cache release: {leaked}")
        for r in replicas:
            st = r.engine.stats_snapshot()
            pc = st.get("prefix_cache", {})
            devs = {str(d) for x in jax.tree_util.tree_leaves(
                r.engine.params) for d in x.devices()}
            print(f"  r{r.rid} on {','.join(sorted(devs))}: "
                  f"prefill={st['prefill_tokens']} "
                  f"decode={st['decode_tokens']} "
                  f"pre={st['preemptions']} "
                  f"cache hits={pc.get('hits', 0)}"
                  f"+{pc.get('partial_hits', 0)}p"
                  f"/{pc.get('misses', 0)}m")
        return

    t_init = time.perf_counter()
    engine = Engine(model, params,
                    ServeConfig(max_batch=args.max_batch,
                                max_seq=args.max_seq, page_size=PAGE_SIZE,
                                prefix_cache=not args.no_prefix_cache,
                                mesh=args.mesh, aot_warmup=args.aot))
    startup = time.perf_counter() - t_init

    convos = reflection_prompts(args.requests, args.prompt_len,
                                cfg.vocab_size, args.seed)
    t0 = time.perf_counter()
    serve_rounds(engine, convos, args.rounds, args.max_new, cfg.vocab_size,
                 args.seed)
    dt = time.perf_counter() - t0
    st = engine.stats()
    print(f"{args.requests} requests x {args.rounds} rounds in {dt:.2f}s")
    print(f"decode {st['decode_steps']} tok, prefill "
          f"{st['prefill_tokens']} tok, extend {st['extend_tokens']} tok "
          f"({st['prefill_chunks']} chunks, {st['mixed_steps']} mixed "
          f"steps, max {st['max_step_prefill_tokens']} prefill tok/step)")
    print(f"mesh {st['mesh'] or 'single-device'} ({st['n_devices']} dev, "
          f"attn_impl {st['attn_impl']}): resident KV "
          f"{st['resident_kv_bytes']} B total, "
          f"{st['resident_kv_bytes_per_device']} B/device "
          f"(pool {st.get('kv_pool_pages_used', 0)}/"
          f"{st.get('kv_pool_pages', 0)} pages)")
    print(f"startup {startup:.2f}s (AOT compile "
          f"{st['startup_compile_s']:.2f}s, {st['aot_warmed']} shapes); "
          f"mid-serve recompiles: {st['step_compiles']}")
    if engine.prefix_cache:
        print(f"prefix cache: {engine.prefix_cache.stats_snapshot()}")


if __name__ == "__main__":
    main()
