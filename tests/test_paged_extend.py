"""Fused paged extend/verify kernel (kernels/paged_extend.py).

Two layers of contract:
  1. kernel == oracle: the Pallas page-table walk reproduces the dense
     XLA gather oracle to fp32 tolerance across fp/int8 x windowed x
     block tilings x scattered/unmapped page tables.
  2. engine bit-parity: greedy serving outputs are token-for-token
     identical with ``attn_impl="pallas"`` vs ``"xla"`` across attn /
     MoE / hybrid archs, fp and int8 KV, with chunked prefill AND
     speculative verify in the loop — the acceptance bar for swapping
     the ``_gather_pages`` densify out of the hot path.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ServeConfig
from repro.kernels import kv_quant as Q
from repro.kernels import ops, ref, tuning
from repro.models.registry import build_model, get_smoke_config
from repro.serving.engine import Engine
from repro.serving.request import Request, Status


def _inputs(B=3, Sx=6, K=2, G=4, hd=64, ps=16, NP=8, P=40, seed=0):
    """Scattered physical pages, per-request unmapped tails past the
    lane frontier — the pool state mid-serve."""
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(B, Sx, K, G, hd)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(P, K, ps, hd)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(P, K, ps, hd)), jnp.float32)
    pt = rng.permutation(P)[: B * NP].reshape(B, NP).astype(np.int32)
    pos0 = jnp.asarray([37, 90, NP * ps - Sx], jnp.int32)[:B]
    for b in range(B):
        used = (int(pos0[b]) + Sx + ps - 1) // ps
        pt[b, used:] = -1
    return q, kp, vp, jnp.asarray(pt), pos0


@pytest.mark.parametrize("window", [None, 48])
@pytest.mark.parametrize("bq,ppb", [(None, None), (8, 1), (64, 2), (16, 4)])
def test_extend_kernel_matches_oracle_fp(window, bq, ppb):
    q, kp, vp, pt, pos0 = _inputs()
    got = ops.paged_extend_attention(q, kp, vp, pt, pos0, window=window,
                                     bq=bq, pages_per_block=ppb)
    want = ref.paged_extend_attention_ref(q, kp, vp, pt, pos0,
                                          window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("window", [None, 48])
@pytest.mark.parametrize("ppb", [1, 2])
def test_extend_kernel_matches_oracle_int8(window, ppb):
    q, kp, vp, pt, pos0 = _inputs(seed=1)
    kq, ks, kz = Q.quantize_k(kp)
    vq, vs = Q.quantize_v(vp)
    got = ops.paged_extend_attention(q, kq, vq, pt, pos0, k_scale=ks,
                                     k_zero=kz, v_scale=vs, window=window,
                                     pages_per_block=ppb)
    want = ref.paged_extend_attention_ref(q, kq, vq, pt, pos0, k_scale=ks,
                                          k_zero=kz, v_scale=vs,
                                          window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


def test_extend_kernel_single_lane_equals_decode_kernel():
    """An Sx=1 extend is a decode step: both kernels must agree on the
    same pool state (shared page-read-once contract)."""
    q, kp, vp, pt, pos0 = _inputs(Sx=1)
    got = ops.paged_extend_attention(q, kp, vp, pt, pos0)
    dec = ops.paged_decode_attention(q[:, 0], kp, vp, pt, pos0)
    np.testing.assert_allclose(np.asarray(got[:, 0]), np.asarray(dec),
                               atol=1e-4, rtol=1e-4)


def test_tuning_lookup_falls_back_to_defaults():
    """Unknown shapes and missing tables must degrade to the historical
    hardcoded blocks, never crash trace-time dispatch."""
    params = tuning.lookup("paged_extend", r=7, hd=999, ctx=12345)
    assert set(params) == {"bq", "pages_per_block"}
    assert tuning.lookup("flash", s=31, hd=1)["bq"] == 128
    assert tuning.lookup("no_such_kernel") == {}


def test_tuning_table_entries_resolve():
    """Every committed table entry must carry params the wrapper accepts
    and the measurement metadata the sweep promises."""
    table = tuning.load_table(refresh=True)
    assert "paged_extend" in table, "sweep table missing extend entries"
    for kernel, backends in table.items():
        allowed = set(tuning.DEFAULTS[kernel])
        for be, entries in backends.items():
            for key, entry in entries.items():
                assert set(entry["params"]) <= allowed, (kernel, key)
                assert entry["us"] > 0 and entry["model_us"] > 0


# ---------------------------------------------------------------- engine

REP_PROMPT = [1] + list(range(10, 22)) * 3


def _greedy_serve(m, params, impl, kv_dtype="model", spec=True,
                  prompt=REP_PROMPT, new=6):
    eng = Engine(m, params,
                 ServeConfig(max_batch=2, max_seq=64, page_size=8,
                             spec_decode=spec, spec_tokens=4,
                             kv_dtype=kv_dtype, attn_impl=impl))
    assert eng.attn_impl == impl
    r = Request(prompt=list(prompt), max_new_tokens=new, eos_id=None)
    eng.submit(r)
    eng.run()
    assert r.status == Status.DONE
    eng.pool.check()
    return list(r.output), eng


@pytest.mark.slow
@pytest.mark.parametrize("arch,kv_dtype", [
    ("qwen3_0_6b", "model"),            # dense attention
    ("qwen3_0_6b", "int8"),             # quantized pool, sidecar dequant
    ("granite_moe_1b_a400m", "model"),  # MoE extend/decode wiring
    ("recurrentgemma_9b", "model"),     # hybrid: windowed rg_attn layers
])
def test_engine_greedy_bit_parity_pallas_vs_xla(arch, kv_dtype):
    """Chunked prefill + verify + decode through the Pallas kernels must
    emit exactly the tokens of the XLA gather path."""
    cfg = get_smoke_config(arch).replace(dtype="float32")
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    out_x, _ = _greedy_serve(m, params, "xla", kv_dtype)
    out_p, eng = _greedy_serve(m, params, "pallas", kv_dtype)
    assert out_p == out_x, f"pallas path changed greedy tokens for {arch}"
    if arch != "recurrentgemma_9b":
        assert eng.spec, "speculation should be on for this arch"
    if arch == "qwen3_0_6b":
        # the self-repeating prompt must actually drive drafts through
        # the Pallas verify step at least once (MoE/hybrid smoke models
        # may legitimately never draft in 6 tokens)
        assert eng.model_steps["verify_steps"] > 0, \
            "parity run never exercised the Pallas verify step"


@pytest.mark.slow
def test_engine_parity_windowed_attention_pallas():
    """Sliding-window masking inside the kernel must agree with the XLA
    path while pages slide out of the window and get freed."""
    cfg = get_smoke_config("qwen3_0_6b").replace(dtype="float32",
                                                 sliding_window=32)
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    outs = {}
    for impl in ("xla", "pallas"):
        outs[impl], _ = _greedy_serve(m, params, impl, new=10)
    assert outs["pallas"] == outs["xla"]
