"""Mesh-sharded serving benchmark: greedy parity + per-device resident
KV + AOT step latency, single-device vs a 1x2 host mesh
(docs/SERVING.md#sharded-serving).

On a TPU host both engines run in this process, on the real chips (a
chip belongs to one process: a child could not reach it while this
process holds it).  On the CPU the devices are virtual:
``xla_force_host_platform_device_count`` must be in XLA_FLAGS BEFORE
the first jax import, which the parent harness (and anything else that
already imported jax) cannot retrofit — so there the measurement runs in
a CHILD process this module re-execs with the flag set, and the parent
parses one JSON line from its stdout.

Per engine (paged KV + int8 KV + speculative decoding all ON, the
acceptance-criteria configuration):
  * greedy outputs of a two-round reflection workload on a ramp-fitted
    smoke model — sharded must match single-device token-for-token;
  * resident-KV bytes per device from Engine.stats() (the 'pages' axis
    shards the pool along 'model', so the mesh halves this);
  * AOT-compiled step latency: wall time per model call over the pure
    decode phase, after startup warmup — with the recompile tripwire
    asserting the serve loop hit zero mid-serve compilations.

Usage: PYTHONPATH=src python benchmarks/sharded_serve.py [--smoke]
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

_DEVICES = 8
_MESH = "1x2"


def _serve_one(mesh: str | None, smoke: bool):
    """One engine, full workload, measurements."""
    import jax

    from repro.configs.base import ServeConfig
    from repro.models.registry import build_model, get_smoke_config
    from repro.serving.engine import Engine
    from repro.serving.request import Request, Status
    from repro.train.quick_fit import quick_fit_ramp, ramp_prompt

    cfg = get_smoke_config("reflect_demo_100m").replace(dtype="float32")
    m = build_model(cfg)
    params = quick_fit_ramp(m, m.init(jax.random.PRNGKey(0)), steps=120)

    n_req = 4
    new_tokens = 8 if smoke else 16
    scfg = ServeConfig(max_batch=n_req, max_seq=256, page_size=16,
                       kv_dtype="int8", spec_decode=True, spec_tokens=4,
                       aot_warmup=True, mesh=mesh)
    t0 = time.perf_counter()
    eng = Engine(m, params, scfg)
    startup_s = time.perf_counter() - t0

    outputs = []
    step_us = 0.0
    for rnd in range(2):
        reqs = [Request(prompt=ramp_prompt(10 + 7 * i, 32 + rnd * 11),
                        max_new_tokens=new_tokens, eos_id=None)
                for i in range(n_req)]
        for r in reqs:
            eng.submit(r)
        # split the round into prefill and a timed pure-decode phase
        while not all(r.status in (Status.DECODING, Status.DONE)
                      for r in reqs):
            eng.step()
        calls0 = sum(eng.model_steps[k] for k in
                     ("decode_batch_steps", "verify_steps", "mixed_steps"))
        peak_resident = eng.stats()["resident_kv_bytes_per_device"]
        t0 = time.perf_counter()
        eng.run()
        dt = time.perf_counter() - t0
        calls = sum(eng.model_steps[k] for k in
                    ("decode_batch_steps", "verify_steps", "mixed_steps"))
        step_us = dt / max(calls - calls0, 1) * 1e6
        assert all(r.status is Status.DONE for r in reqs)
        outputs.append([list(r.output) for r in reqs])
    eng.pool.check()
    st = eng.stats()
    return {"outputs": outputs, "step_us": step_us,
            "startup_s": startup_s,
            "resident_per_device": peak_resident,
            "stats": {k: st[k] for k in
                      ("step_compiles", "aot_warmed", "n_devices",
                       "startup_compile_s", "attn_impl",
                       "resident_kv_bytes", "spec_accepted")}}


def _measure(smoke: bool) -> dict:
    return {"single": _serve_one(None, smoke),
            "mesh": _serve_one(_MESH, smoke)}


def _child(smoke: bool) -> None:
    print("RESULT " + json.dumps(_measure(smoke)))


def _spawn(smoke: bool) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + f" --xla_force_host_platform_device_count="
                          f"{_DEVICES}").strip()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = (os.path.join(root, "src") + os.pathsep
                         + env.get("PYTHONPATH", ""))
    cmd = [sys.executable, os.path.abspath(__file__), "--child"]
    if smoke:
        cmd.append("--smoke")
    proc = subprocess.run(cmd, env=env, capture_output=True, text=True,
                          timeout=1200)
    if proc.returncode != 0:
        raise RuntimeError(f"sharded-serve child failed:\n{proc.stdout}\n"
                           f"{proc.stderr}")
    for line in proc.stdout.splitlines():
        if line.startswith("RESULT "):
            return json.loads(line[len("RESULT "):])
    raise RuntimeError(f"no RESULT line in child output:\n{proc.stdout}")


def run(verbose: bool = True, smoke: bool = False):
    import jax
    res = _spawn(smoke) if jax.default_backend() == "cpu" else _measure(smoke)
    single, mesh = res["single"], res["mesh"]
    match = single["outputs"] == mesh["outputs"]
    assert match, (
        f"sharded greedy outputs diverged from single-device:\n"
        f"  single: {single['outputs']}\n  mesh:   {mesh['outputs']}")
    for name, eng in (("single", single), ("mesh", mesh)):
        assert eng["stats"]["step_compiles"] == 0, (
            f"{name} engine recompiled mid-serve: {eng['stats']}")
    assert mesh["stats"]["n_devices"] == 2
    assert mesh["stats"]["attn_impl"] == "xla"
    shrink = (single["resident_per_device"]
              / max(mesh["resident_per_device"], 1))

    if verbose:
        print(f"sharded serve (mesh {_MESH}, paged+int8+spec, AOT warmup):")
        print(f"  greedy outputs match single-device: {match}")
        print(f"  resident KV/device: single {single['resident_per_device']}"
              f" B -> mesh {mesh['resident_per_device']} B "
              f"({shrink:.2f}x smaller)")
        print(f"  decode-phase step latency: single {single['step_us']:.0f}"
              f" us/call -> mesh {mesh['step_us']:.0f} us/call")
        print(f"  startup: single {single['startup_s']:.1f}s "
              f"(compile {single['stats']['startup_compile_s']:.1f}s, "
              f"{single['stats']['aot_warmed']} shapes), mesh "
              f"{mesh['startup_s']:.1f}s "
              f"(compile {mesh['stats']['startup_compile_s']:.1f}s); "
              f"mid-serve recompiles: 0 / 0")
    return [
        ("sharded_serve_greedy_match", 0.0, str(match)),
        ("sharded_serve_resident_kv_per_device_b", 0.0,
         str(mesh["resident_per_device"])),
        ("sharded_serve_kv_shrink", 0.0, f"{shrink:.2f}x"),
        ("sharded_aot_decode_step", mesh["step_us"],
         f"single={single['step_us']:.0f}us"),
        ("sharded_aot_startup_compile_s", 0.0,
         f"{mesh['stats']['startup_compile_s']:.2f}"),
        ("sharded_serve_recompiles", 0.0, "0"),
    ]


if __name__ == "__main__":
    if "--child" in sys.argv:
        _child("--smoke" in sys.argv)
    else:
        t0 = time.time()
        for r in run(smoke="--smoke" in sys.argv):
            print(",".join(map(str, r)))
        print(f"sharded_serve: OK ({time.time()-t0:.1f}s)")
