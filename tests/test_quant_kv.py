"""Quantized KV cache (``kv_dtype="int8"``) correctness.

Covers the contracts of kernels/kv_quant.py + the quantized cache paths:
  * number format — per-slot-per-head asymmetric-K / symmetric-V int8
    round-trips within half a quantization step;
  * kernels — the fused-dequant Pallas kernels (ring + paged) match the
    kv_quant-dequantizing oracles to float ulps;
  * model parity — quantized paged chunked-prefill + decode stays within
    quantization tolerance of the fp ring path across attention, MoE and
    hybrid-recurrent architectures;
  * engine — greedy decode on a (quickly fitted) smoke model matches the
    fp engine token-for-token, and the quantized engine is
    self-consistent through COW divergence and preemption replay
    (deterministic quantization: a replay re-produces bit-identical
    pages);
  * the ``kv_dtype="model"`` default — pinned to the PR-2 fp layout
    (no sidecar leaves, model-dtype pools) and to bit-identical
    paged==ring engine outputs.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ServeConfig
from repro.kernels import kv_quant as Q
from repro.kernels import ops, ref
from repro.models import layers as L
from repro.models.registry import build_model, get_smoke_config
from repro.serving.engine import Engine
from repro.serving.request import Request, Status
from repro.train.quick_fit import quick_fit_ramp, ramp_prompt

# jit-compile-heavy end-to-end module: deselected by `make test-fast`
pytestmark = pytest.mark.slow

PARITY_ARCHS = ["qwen3_0_6b", "granite_moe_1b_a400m", "recurrentgemma_9b"]


def _f32(a):
    return np.asarray(a, dtype=np.float32)


def make_engine(arch="qwen3_0_6b", **kw):
    cfg = get_smoke_config(arch).replace(dtype="float32")
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    scfg = ServeConfig(**{**dict(max_batch=3, max_seq=160, page_size=8), **kw})
    return Engine(m, params, scfg), m, params


# ---------------------------------------------------------------------------
# number format
# ---------------------------------------------------------------------------

def test_quant_roundtrip_error_bound():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((4, 7, 3, 32)) * 3.0, jnp.float32)
    kq, ks, kz = Q.quantize_k(x)
    assert kq.dtype == jnp.int8
    # asymmetric K: error <= half a step (= scale/2) everywhere
    err = np.abs(_f32(Q.dequantize_k(kq, ks, kz)) - _f32(x))
    assert (err <= _f32(ks)[..., None] * 0.5 + 1e-6).all()
    vq, vs = Q.quantize_v(x)
    err = np.abs(_f32(Q.dequantize_v(vq, vs)) - _f32(x))
    assert (err <= _f32(vs)[..., None] * 0.5 + 1e-6).all()
    # degenerate constant rows survive exactly (EPS guard, no 0/0)
    c = jnp.full((2, 5, 1, 16), 1.25, jnp.float32)
    kq, ks, kz = Q.quantize_k(c)
    np.testing.assert_allclose(_f32(Q.dequantize_k(kq, ks, kz)), 1.25,
                               atol=1e-5)
    vq, vs = Q.quantize_v(jnp.zeros((2, 5, 1, 16), jnp.float32))
    np.testing.assert_array_equal(_f32(Q.dequantize_v(vq, vs)), 0.0)


def test_quantization_is_deterministic():
    """Replay/COW exactness relies on re-quantizing the same values
    producing bit-identical int8 pages."""
    x = jnp.asarray(np.random.default_rng(1).standard_normal((3, 8, 2, 16)),
                    jnp.float32)
    a = Q.quantize_k(x)
    b = Q.quantize_k(jnp.array(x))
    for l, r in zip(a, b):
        np.testing.assert_array_equal(np.asarray(l), np.asarray(r))


# ---------------------------------------------------------------------------
# fused-dequant kernels vs oracles
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("window", [None, 10])
def test_quant_paged_kernel_parity(window):
    rng = np.random.default_rng(0)
    B, K, G, hd, P, ps, NP = 3, 2, 2, 32, 16, 8, 5
    q = jnp.asarray(rng.standard_normal((B, K, G, hd)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((P, K, ps, hd)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((P, K, ps, hd)), jnp.float32)
    pos = jnp.asarray([3, 17, 38], jnp.int32)
    pt = np.full((B, NP), -1, np.int32)
    perm, u = rng.permutation(P), 0
    for b in range(B):
        n = int(pos[b]) // ps + 1
        pt[b, :n] = perm[u:u + n]
        u += n
    pt = jnp.asarray(pt)
    kq, ks, kz = Q.quantize_k(kp)
    vq, vs = Q.quantize_v(vp)
    got = ops.paged_decode_attention(q, kq, vq, pt, pos, k_scale=ks,
                                     k_zero=kz, v_scale=vs, window=window)
    want = ref.paged_decode_attention_ref(q, kq, vq, pt, pos, k_scale=ks,
                                          k_zero=kz, v_scale=vs,
                                          window=window)
    np.testing.assert_allclose(_f32(got), _f32(want), atol=2e-5, rtol=2e-5)
    # and the quantized answer stays near the fp answer (same pool values)
    fp = ref.paged_decode_attention_ref(q, kp, vp, pt, pos, window=window)
    np.testing.assert_allclose(_f32(got), _f32(fp), atol=0.05, rtol=0.05)


def test_quant_decode_kernel_parity():
    rng = np.random.default_rng(2)
    B, K, G, hd, C = 2, 2, 2, 32, 64
    q = jnp.asarray(rng.standard_normal((B, K, G, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, C, K, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, C, K, hd)), jnp.float32)
    tok = jnp.broadcast_to(jnp.arange(C)[None], (B, C)).astype(jnp.int32)
    pos = jnp.asarray([40, 63], jnp.int32)
    kq, ks, kz = Q.quantize_k(k)
    vq, vs = Q.quantize_v(v)
    got = ops.decode_attention(q, kq, vq, tok, pos, k_scale=ks, k_zero=kz,
                               v_scale=vs, bk=16)
    want = ref.decode_attention_ref(q, kq, vq, tok, pos, k_scale=ks,
                                    k_zero=kz, v_scale=vs)
    np.testing.assert_allclose(_f32(got), _f32(want), atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# model-level parity: quantized paged vs fp ring, within quant tolerance
# ---------------------------------------------------------------------------

# int8 KV error on these random-init smoke models, in logits (range
# ~5-7).  It grows with the size of the attention scores: qwen3 (qk-norm)
# measures ~0.02.  granite has no qk-norm, so its random-init q.k scores
# are several times larger and the same K quantization step moves them
# further: ~0.21 at the last prefill position (a granite with plain
# attention blocks in place of MoE measures ~0.98, and qwen3 with qk-norm
# off ~0.58 — the MoE routing is not the cause).  The hybrid compounds
# the error through rg_attn layers feeding fp recurrences.  That the
# paged walk itself adds nothing is pinned exactly against the int8 ring
# cache below.
QUANT_ATOL = {"qwen3_0_6b": 0.08, "granite_moe_1b_a400m": 0.3,
              "recurrentgemma_9b": 0.4}


def _chunked_walk(m, params, cache, tokens, page_table=None):
    """Chunked prefill of ``tokens`` (rows advance 5 and 3 lanes per
    step); returns (last-position logits, cache)."""
    B, S = tokens.shape
    kw = {} if page_table is None else {"page_table": page_table}
    sizes, prog = [5, 3], [0, 0]
    lg = np.zeros((B, m.cfg.vocab_size), np.float32)
    while min(prog) < S:
        blk = np.zeros((B, 5), np.int32)
        nv = np.zeros(B, np.int32)
        p0 = np.zeros(B, np.int32)
        for b in range(B):
            n = min(sizes[b], S - prog[b])
            blk[b, :n] = np.asarray(tokens)[b, prog[b]:prog[b] + n]
            nv[b], p0[b] = n, prog[b]
            prog[b] += n
        lg_new, cache = m.prefill_extend(params, cache, jnp.asarray(blk),
                                         jnp.asarray(p0), jnp.asarray(nv),
                                         **kw)
        for b in range(B):
            if prog[b] == S and nv[b] > 0:
                lg[b] = _f32(lg_new)[b]
    return lg, cache


@pytest.mark.parametrize("arch", PARITY_ARCHS)
def test_paged_quant_close_to_fp(arch):
    """Chunked int8 paged prefill + decode tracks the fp ring path within
    quantization tolerance across attn / MoE / hybrid models (the fp
    counterpart of this walk agrees to fp32 rounding — test_paged_kv.py),
    and reproduces the int8 RING cache under the same chunk walk: both
    quantize the same rows the same way, so the head-major page pool,
    its scale sidecars and the page-table walk add no error at all."""
    cfg = get_smoke_config(arch).replace(dtype="float32", capacity_factor=8.0)
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    B, S, max_seq, ps = 2, 13, 32, 4
    NP = max_seq // ps
    tokens = jax.random.randint(jax.random.PRNGKey(3), (B, S), 3,
                                cfg.vocab_size)
    lg_ring, cache_ring = m.prefill(params, tokens, max_seq=max_seq)

    pt = jnp.asarray(np.stack([np.arange(NP) + b * NP for b in range(B)])
                     .astype(np.int32))
    cache = L.init_empty_cache(
        m.cache_defs_paged(B, B * NP, ps, kv_dtype="int8"))
    for leaf, d in zip(jax.tree_util.tree_leaves(cache),
                       L.tree_defs(m.cache_defs_paged(B, B * NP, ps,
                                                      kv_dtype="int8"))):
        if d.axes and d.axes[0] == "pages" and leaf.ndim == 4:
            assert leaf.dtype == jnp.int8
    lg, cache = _chunked_walk(m, params, cache, tokens, pt)
    lg_q, cache_q = _chunked_walk(
        m, params, L.init_empty_cache(m.cache_defs(
            B, max_seq, seq_shard=False, kv_dtype="int8")), tokens)
    np.testing.assert_allclose(lg, lg_q, atol=1e-5, rtol=1e-5)

    atol = QUANT_ATOL[arch]
    np.testing.assert_allclose(lg, _f32(lg_ring), atol=atol, rtol=0.05)
    # the error must also be small relative to the logit spread
    rel = (np.linalg.norm(lg - _f32(lg_ring))
           / max(np.linalg.norm(_f32(lg_ring)), 1e-9))
    assert rel < 0.1, rel

    nxt = jnp.argmax(lg_ring, -1)[:, None].astype(jnp.int32)
    pos = jnp.full((B,), S, jnp.int32)
    d_ring, _ = m.decode_step(params, cache_ring, nxt, pos)
    d_paged, _ = m.decode_step(params, cache, nxt, pos, page_table=pt)
    d_q, _ = m.decode_step(params, cache_q, nxt, pos)
    np.testing.assert_allclose(_f32(d_paged), _f32(d_q), atol=1e-5,
                               rtol=1e-5)
    np.testing.assert_allclose(_f32(d_paged), _f32(d_ring), atol=atol,
                               rtol=0.05)


def test_quant_ring_close_to_fp():
    """The dense ring fallback quantizes too: int8 ring engine tracks the
    int8 paged engine token-for-token (same quantized values through two
    different storage layouts)."""
    prompts = [[1] + list(range(10, 40)), [1] + list(range(50, 63))]
    outs = {}
    for paged in (True, False):
        eng, _, _ = make_engine(paged_kv=paged, kv_dtype="int8",
                                max_batch=2)
        reqs = [Request(prompt=list(p), max_new_tokens=6, eos_id=None)
                for p in prompts]
        for r in reqs:
            eng.submit(r)
        eng.run()
        assert all(r.status is Status.DONE for r in reqs)
        outs[paged] = [r.output for r in reqs]
    assert outs[True] == outs[False]


# ---------------------------------------------------------------------------
# engine: greedy token match vs fp on a non-degenerate model
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def fitted_smoke():
    """Smoke model quickly fitted to +1 ramps: random-init logits are
    near-uniform (any perturbation flips argmax); the fitted model has
    real logit gaps, making token-for-token parity meaningful."""
    cfg = get_smoke_config("reflect_demo_100m").replace(dtype="float32")
    m = build_model(cfg)
    params = quick_fit_ramp(m, m.init(jax.random.PRNGKey(0)))
    return m, params


def test_engine_quant_greedy_matches_fp(fitted_smoke):
    m, params = fitted_smoke
    prompts = [ramp_prompt(10 + 7 * i, 32) for i in range(4)]
    outs = {}
    for kvd in ("model", "int8"):
        eng = Engine(m, params, ServeConfig(max_batch=4, max_seq=192,
                                            page_size=16, kv_dtype=kvd))
        reqs = [Request(prompt=list(p), max_new_tokens=16, eos_id=None)
                for p in prompts]
        for r in reqs:
            eng.submit(r)
        eng.run()
        assert all(r.status is Status.DONE for r in reqs)
        outs[kvd] = [r.output for r in reqs]
    assert outs["int8"] == outs["model"], \
        "int8 KV flipped greedy tokens on the fitted smoke model"


# ---------------------------------------------------------------------------
# engine: COW divergence + preemption replay with quantized pages
# ---------------------------------------------------------------------------

def test_quant_cow_divergence_is_exact():
    """Divergence inside a shared partially-filled page copies the int8
    payload AND its scale sidecars (same pages-axis scatter); cached vs
    uncached runs must emit identical tokens."""
    prompt = [1] + list(range(10, 30))                  # 21 tokens, ps=8
    outs = {}
    for pc in (True, False):
        eng, _, _ = make_engine(prefix_cache=pc, kv_dtype="int8",
                                max_batch=2, max_seq=96)
        r1 = Request(prompt=list(prompt), max_new_tokens=4, eos_id=None)
        eng.submit(r1)
        eng.run()
        r2 = Request(prompt=list(prompt) + r1.output + [70, 71],
                     max_new_tokens=4, eos_id=None)
        eng.submit(r2)
        eng.run()
        outs[pc] = (r1.output, r2.output)
        if pc:
            assert r2.usage.cache_read_tokens > 0
            assert eng.pool.stats["cow_copies"] >= 1
            eng.pool.check()
    assert outs[True] == outs[False]


def test_quant_preemption_replay_is_exact():
    """Pool exhaustion with quantized pages: the preempted request's
    replay re-quantizes the same tokens deterministically and finishes
    with exactly the tokens of an uncontested int8 run."""
    long_prompts = [[1] + list(range(10, 50)),
                    [2] + list(range(60, 100))]
    solo = []
    for p in long_prompts:
        eng, _, _ = make_engine(prefix_cache=False, kv_dtype="int8",
                                max_batch=1, max_seq=64)
        r = Request(prompt=list(p), max_new_tokens=6, eos_id=None)
        eng.submit(r)
        eng.run()
        solo.append(r.output)

    eng, _, _ = make_engine(prefix_cache=False, kv_dtype="int8",
                            max_batch=2, max_seq=64, num_pages=8)
    reqs = [Request(prompt=list(p), max_new_tokens=6, eos_id=None)
            for p in long_prompts]
    for r in reqs:
        eng.submit(r)
    eng.run()
    assert all(r.status is Status.DONE for r in reqs)
    assert eng.model_steps["preemptions"] >= 1
    assert [r.output for r in reqs] == solo
    eng.pool.check()
    assert eng.pool.used_pages == 0


# ---------------------------------------------------------------------------
# kv_dtype="model": the PR-2 fp layout, pinned
# ---------------------------------------------------------------------------

def test_kv_dtype_model_keeps_fp_layout_and_bit_parity():
    """The default (and explicit "model") kv_dtype must keep the exact
    PR-2 cache layout — model-dtype pools, no scale sidecars — and the
    bit-identical paged==ring guarantee of tests/test_paged_kv.py."""
    for kvd in (None, "model"):
        eng, m, _ = make_engine(kv_dtype=kvd)
        defs = L.tree_defs(eng.cache_defs)
        leaves = jax.tree_util.tree_leaves(eng.cache)
        assert all(leaf.dtype != jnp.int8 for leaf in leaves)
        # same tree structure as the pre-quantization paged defs
        ref_defs = m.cache_defs_paged(eng.scfg.max_batch,
                                      eng.pool.num_pages,
                                      eng.scfg.page_size, kv_dtype="model")
        assert (jax.tree_util.tree_structure(ref_defs)
                == jax.tree_util.tree_structure(eng.cache_defs))
        for leaf, d in zip(leaves, defs):
            if d.axes and d.axes[0] == "pages":
                assert leaf.dtype == jnp.dtype("float32")

    prompts = [[1] + list(range(10, 40)), [1] + list(range(50, 63))]
    outs = {}
    for paged in (True, False):
        eng, _, _ = make_engine(kv_dtype="model", paged_kv=paged,
                                max_batch=2)
        reqs = [Request(prompt=list(p), max_new_tokens=6, eos_id=None)
                for p in prompts]
        for r in reqs:
            eng.submit(r)
        eng.run()
        outs[paged] = [r.output for r in reqs]
    assert outs[True] == outs[False]


def test_serve_config_overrides_model_config():
    """ServeConfig.kv_dtype wins over ModelConfig.kv_dtype (and None
    inherits it)."""
    cfg = get_smoke_config("qwen3_0_6b").replace(dtype="float32",
                                                 kv_dtype="int8")
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    eng = Engine(m, params, ServeConfig(max_batch=1, max_seq=64, page_size=8))
    assert eng.kv_dtype == "int8"
    assert any(leaf.dtype == jnp.int8
               for leaf in jax.tree_util.tree_leaves(eng.cache))
    eng = Engine(m, params, ServeConfig(max_batch=1, max_seq=64, page_size=8,
                                        kv_dtype="model"))
    assert eng.kv_dtype == "model"
    assert all(leaf.dtype != jnp.int8
               for leaf in jax.tree_util.tree_leaves(eng.cache))
