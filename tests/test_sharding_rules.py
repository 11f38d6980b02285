"""Sharding-rule and HLO-cost-model tests (host mesh; the 512-device
production mesh is exercised by launch/dryrun.py in its own process,
and the multi-device serving parity pin below spawns its own child
because the host-device-count XLA flag must precede jax init)."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.launch.hlocost import analyze, parse_module
from repro.launch.rules import DEFAULT_RULES, serve_rules, spec_for


class FakeMesh:
    def __init__(self, names, shape):
        self.axis_names = names
        import numpy as np
        self.devices = np.zeros(shape)


MESH = FakeMesh(("data", "model"), (16, 16))          # make_production_mesh
MESH3 = FakeMesh(("pod", "data", "model"), (2, 16, 16))
HOST2 = FakeMesh(("data", "model"), (1, 2))           # make_serve_mesh("1x2")


def test_basic_rules():
    # weight [embed, ff]: FSDP data x tensor model
    assert spec_for((4096, 11008), ("embed", "ff"), MESH) == P("data", "model")
    # batch picks both pod and data on the 3-axis mesh
    assert spec_for((256, 4096), ("batch", None), MESH3) == P(("pod", "data"))


def test_divisibility_fallback():
    # 24 heads don't divide model=16 -> replicated
    assert spec_for((3072, 24, 128), ("embed", "heads", None), MESH) == \
        P("data")
    # batch=1 can't shard -> kv_seq absorbs everything available
    spec = spec_for((1, 524288, 8, 128), ("batch", "kv_seq", "kv_heads", None),
                    MESH3)
    assert spec == P(None, ("model", "pod", "data"))


def test_axis_conflict_resolution():
    # experts take model first; ff can't reuse it
    spec = spec_for((32, 1024, 512), ("experts", "embed", "ff"), MESH)
    assert spec == P("model", "data")


def test_kv_cache_spec():
    # decode_32k style: batch over data, capacity over model
    spec = spec_for((128, 32768, 8, 128), ("batch", "kv_seq", "kv_heads", None),
                    MESH)
    assert spec == P("data", "model")


# ---------------------------------------------------------------------------
# serve rules: paged pool + int8 sidecar placement (mesh-sharded Engine)
# ---------------------------------------------------------------------------

def test_serve_rules_paged_pool_production_mesh():
    """Pool leaves shard by PHYSICAL PAGE along 'model' on the production
    (16, 16) mesh shape; kv_heads on the same leaf falls back replicated
    (spec_for's used-axis rule), and the int8 scale sidecars follow their
    pages so COW/snapshot mechanics move scales with payload."""
    r = serve_rules()
    # payload pools [pages, page_size, kv_heads, head_dim]
    assert spec_for((256, 16, 8, 128), ("pages", None, "kv_heads", None),
                    MESH, r) == P("model")
    # int8 scale sidecars [pages, page_size, kv_heads]
    assert spec_for((256, 16, 8), ("pages", None, "kv_heads"),
                    MESH, r) == P("model")
    # scan-stacked pool leaf: layers never sharded, pages still are
    assert spec_for((28, 256, 16, 8, 128),
                    ("layers", "pages", None, "kv_heads", None),
                    MESH, r) == P(None, "model")
    # serve rules are tensor-parallel: no FSDP shard on embed
    assert spec_for((4096, 11008), ("embed", "ff"), MESH, r) == \
        P(None, "model")


def test_serve_rules_paged_pool_host_mesh():
    """Same placement on the 1x2 host serving mesh (the sharded smoke
    configuration scripts/verify.sh gates on)."""
    r = serve_rules()
    assert spec_for((128, 16, 2, 64), ("pages", None, "kv_heads", None),
                    HOST2, r) == P("model")
    assert spec_for((128, 16, 2), ("pages", None, "kv_heads"),
                    HOST2, r) == P("model")
    # an odd page count can't split 2 ways: pages drops to replicated and
    # kv_heads (2 % 2 == 0) picks the now-free model axis instead.  The
    # engine rounds num_pages up to a model-axis multiple so the pool
    # never actually lands here.
    assert spec_for((127, 16, 2, 64), ("pages", None, "kv_heads", None),
                    HOST2, r) == P(None, None, "model")


def test_paged_pool_defs_resolve_sharded():
    """The REAL pool defs (attention.paged_kv_cache_def with int8 KV)
    carry logical axes that resolve to page-sharded placement under
    serve_rules — payload pools and all three scale sidecars."""
    from repro.models.attention import paged_kv_cache_def
    from repro.models.registry import get_smoke_config

    cfg = get_smoke_config("qwen3_0_6b")
    d = paged_kv_cache_def(cfg, num_pages=256, page_size=16,
                           dtype=jnp.float32, kv_dtype="int8")
    assert {"kp", "vp", "ksp", "kzp", "vsp"} <= set(d)
    for name, leaf in d.items():
        spec = spec_for(leaf.shape, leaf.axes, MESH, serve_rules())
        assert spec == P("model"), (name, spec)


_PARITY_CHILD = textwrap.dedent("""
    import jax, json
    from repro.configs.base import ServeConfig
    from repro.models.registry import build_model, get_smoke_config
    from repro.serving.engine import Engine
    from repro.serving.request import Request, Status

    cfg = get_smoke_config("qwen3_0_6b").replace(dtype="float32")
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    outs = {}
    for mesh in (None, "1x2"):
        eng = Engine(m, params,
                     ServeConfig(max_batch=4, max_seq=128, page_size=16,
                                 kv_dtype="int8", spec_decode=True,
                                 spec_tokens=4, aot_warmup=True, mesh=mesh))
        motif = list(range(5, 12))
        rr = [Request(prompt=[1 + i] + motif * 3, max_new_tokens=8,
                      eos_id=None) for i in range(3)]
        for r in rr:
            eng.submit(r)
        eng.run()
        assert all(r.status is Status.DONE for r in rr)
        st = eng.stats()
        assert st["step_compiles"] == 0, st
        assert st["n_devices"] == (2 if mesh else 1)
        outs[str(mesh)] = [r.output for r in rr]
    assert outs["None"] == outs["1x2"], outs
    print("PARITY_OK", json.dumps(outs["1x2"]))
""")


@pytest.mark.slow
def test_sharded_serving_greedy_parity_host_mesh():
    """A 1x2 host-mesh engine with paged KV + int8 KV + speculative
    decoding ALL ON must serve greedy outputs bit-identical to the
    single-device engine, with zero mid-serve recompiles after AOT
    warmup.  Child process: the host-device-count flag must be exported
    before the first jax import."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(repo, "src")
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8").strip()
    out = subprocess.run([sys.executable, "-c", _PARITY_CHILD],
                         capture_output=True, text=True, env=env,
                         timeout=560, cwd=repo)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    assert "PARITY_OK" in out.stdout


_MESH_WRITE_CHILD = textwrap.dedent("""
    import re, sys
    import jax
    from repro.configs.base import ServeConfig
    from repro.models.registry import build_model, get_smoke_config
    from repro.serving.engine import Engine

    P = 116                     # pages: 29 a device, a count no other dim has
    cfg = get_smoke_config("qwen3_0_6b").replace(
        dtype="float32", num_layers=4, block_pattern=("attn",) * 4)
    m = build_model(cfg)
    eng = Engine(m, m.init(jax.random.PRNGKey(0)),
                 ServeConfig(max_batch=4, max_seq=128, page_size=8,
                             num_pages=P, mesh="1x4", spec_decode=True,
                             spec_tokens=4, kv_dtype=sys.argv[1] or None))
    eng.aot_compile()
    pool = [a for a in jax.tree_util.tree_leaves(eng.cache) if a.ndim == 5]
    assert pool and all(a.shape[1] == P for a in pool)
    assert all(a.sharding.spec[1] == "model" for a in pool)
    exes = eng.compiled_steps()
    assert len(exes) >= 3               # decode, mixed, verify
    moved = []
    for exe in exes:
        for res, op in re.findall(
                r"= (.+?) (all-gather|all-to-all|all-reduce|reduce-scatter|"
                r"collective-permute)(?:-start)?\\(", exe.as_text()):
            dims = [d.split(",") for d in re.findall(r"\\[([\\d,]*)\\]", res)]
            if any(str(P) in d for d in dims):
                moved.append(op + " " + res)
    assert not moved, moved
    print("MESH_WRITE_OK", len(exes))
""")


@pytest.mark.parametrize("kv_dtype", ["", "int8"], ids=["fp", "int8"])
def test_mesh_step_programs_gather_no_pool(kv_dtype):
    """Under a 1x4 host mesh, with the pool's pages sharded on 'model',
    the compiled decode, mixed and verify programs write the new K/V rows
    and read the pages with no collective over the pool: a write that
    reads a window at a traced page (``dynamic_slice``) and writes it
    back all-gathers the stacked pool instead.  Child process: the
    host-device-count flag must precede jax init."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(repo, "src")
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4").strip()
    out = subprocess.run([sys.executable, "-c", _MESH_WRITE_CHILD, kv_dtype],
                         capture_output=True, text=True, env=env,
                         timeout=300, cwd=repo)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    assert "MESH_WRITE_OK" in out.stdout


# ---------------------------------------------------------------------------
# hlocost
# ---------------------------------------------------------------------------

def test_hlocost_scan_trip_counts():
    def f(x, w):
        def body(c, _):
            return jax.nn.relu(c @ w), None
        c, _ = jax.lax.scan(body, x, None, length=10)
        return c

    x = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    w = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    comp = jax.jit(f).lower(x, w).compile()
    r = analyze(comp.as_text())
    want = 10 * 2 * 128 * 256 * 256
    assert abs(r["flops"] - want) / want < 0.01
    assert r["unparsed_while"] == 0


def test_hlocost_matches_xla_unrolled():
    def f(x, w):
        for _ in range(4):
            x = x @ w
        return x

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    w = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    comp = jax.jit(f).lower(x, w).compile()
    r = analyze(comp.as_text())
    cost = comp.cost_analysis()
    if isinstance(cost, (list, tuple)):       # older jax: per-partition list
        cost = cost[0]
    xla = cost["flops"]
    assert abs(r["flops"] - xla) / xla < 0.05


def test_hlocost_parse_module_structure():
    def f(x):
        return jnp.tanh(x) * 2

    comp = jax.jit(f).lower(jax.ShapeDtypeStruct((8, 8), jnp.float32)).compile()
    comps = parse_module(comp.as_text())
    assert any(c.is_entry for c in comps.values())
