"""Per-kernel validation: shape/dtype sweeps vs the ref.py jnp oracles,
plus hypothesis property tests (interpret=True executes kernel bodies on
CPU; TPU is the compilation target)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis")  # optional dep: skip, don't error
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref

# jit-compile-heavy end-to-end module: deselected by `make test-fast`
pytestmark = pytest.mark.slow


def _mk(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,K,S,hd,window", [
    (1, 4, 2, 256, 64, None),
    (2, 4, 4, 128, 32, None),       # MHA
    (2, 8, 2, 256, 64, 64),         # GQA + sliding window
    (1, 2, 1, 512, 128, 128),       # MQA, MXU-aligned head dim
])
def test_flash_attention_sweep(B, H, K, S, hd, window, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = _mk(ks[0], (B, H, S, hd), dtype)
    k = _mk(ks[1], (B, K, S, hd), dtype)
    v = _mk(ks[2], (B, K, S, hd), dtype)
    got = ops.flash_attention(q, k, v, window=window, bq=64, bk=64)
    want = ref.flash_attention_ref(q, k, v, window=window)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


@settings(max_examples=10, deadline=None)
@given(bq=st.sampled_from([32, 64, 128]), bk=st.sampled_from([32, 64]),
       seed=st.integers(0, 2 ** 16))
def test_flash_attention_block_invariance(bq, bk, seed):
    """Property: output independent of block decomposition."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = _mk(ks[0], (1, 2, 128, 32), jnp.float32)
    k = _mk(ks[1], (1, 2, 128, 32), jnp.float32)
    v = _mk(ks[2], (1, 2, 128, 32), jnp.float32)
    got = ops.flash_attention(q, k, v, bq=bq, bk=bk)
    want = ref.flash_attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5,
                               rtol=3e-5)


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,K,G,C,hd,window", [
    (2, 2, 2, 256, 64, None),
    (1, 4, 1, 128, 32, None),
    (2, 1, 8, 256, 64, 64),         # MQA ring with window
])
def test_decode_attention_sweep(B, K, G, C, hd, window, dtype):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = _mk(ks[0], (B, K, G, hd), dtype)
    k = _mk(ks[1], (B, C, K, hd), dtype)
    v = _mk(ks[2], (B, C, K, hd), dtype)
    pos = jnp.array([C // 2 + 3] * B, jnp.int32)
    # ring occupancy: tokens 0..pos written (slot = t % C), rest empty
    tok = jnp.where(jnp.arange(C)[None, :] <= pos[:, None],
                    jnp.arange(C)[None, :], -1).astype(jnp.int32)
    got = ops.decode_attention(q, k, v, tok, pos, window=window, bk=64)
    want = ref.decode_attention_ref(q, k, v, tok, pos, window=window)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


def test_decode_attention_empty_slots_ignored():
    """Slots with tok=-1 must contribute nothing."""
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    B, K, G, C, hd = 1, 2, 2, 128, 32
    q = _mk(ks[0], (B, K, G, hd), jnp.float32)
    k = _mk(ks[1], (B, C, K, hd), jnp.float32)
    v = _mk(ks[2], (B, C, K, hd), jnp.float32)
    pos = jnp.array([20], jnp.int32)
    tok = jnp.where(jnp.arange(C)[None, :] <= 20,
                    jnp.arange(C)[None, :], -1).astype(jnp.int32)
    got = ops.decode_attention(q, k, v, tok, pos, bk=64)
    # poisoning empty slots must not change the result
    k2 = k.at[:, 21:].set(1e4)
    v2 = v.at[:, 21:].set(-1e4)
    got2 = ops.decode_attention(q, k2, v2, tok, pos, bk=64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(got2), atol=1e-6)


# ---------------------------------------------------------------------------
# mamba selective scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,D,N,bd", [
    (1, 64, 128, 8, 64),
    (2, 32, 256, 16, 128),
    (1, 128, 64, 4, 64),
])
def test_mamba_scan_sweep(B, S, D, N, bd):
    ks = jax.random.split(jax.random.PRNGKey(3), 6)
    dt = jax.nn.softplus(jax.random.normal(ks[0], (B, S, D))) * 0.1
    Bm = jax.random.normal(ks[1], (B, S, N))
    Cm = jax.random.normal(ks[2], (B, S, N))
    x = jax.random.normal(ks[3], (B, S, D))
    A = -jnp.exp(jax.random.normal(ks[4], (D, N)) * 0.3)
    Dsk = jax.random.normal(ks[5], (D,))
    h0 = jnp.zeros((B, D, N))
    y, h = ops.mamba_scan(dt, Bm, Cm, x, A, Dsk, h0, bd=bd)
    y_ref, h_ref = ref.mamba_scan_ref(dt, Bm, Cm, x, A, Dsk, h0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=1e-4,
                               rtol=1e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref), atol=1e-4,
                               rtol=1e-4)


def test_mamba_scan_initial_state():
    """Prefix-extension property: scan(x, h0=scan(x1).h) == scan(x1+x2)."""
    ks = jax.random.split(jax.random.PRNGKey(4), 6)
    B, S, D, N = 1, 64, 64, 8
    dt = jax.nn.softplus(jax.random.normal(ks[0], (B, S, D))) * 0.1
    Bm = jax.random.normal(ks[1], (B, S, N))
    Cm = jax.random.normal(ks[2], (B, S, N))
    x = jax.random.normal(ks[3], (B, S, D))
    A = -jnp.exp(jax.random.normal(ks[4], (D, N)) * 0.3)
    Dsk = jax.random.normal(ks[5], (D,))
    h0 = jnp.zeros((B, D, N))
    y_full, h_full = ops.mamba_scan(dt, Bm, Cm, x, A, Dsk, h0)
    half = S // 2
    _, h1 = ops.mamba_scan(dt[:, :half], Bm[:, :half], Cm[:, :half],
                           x[:, :half], A, Dsk, h0)
    y2, h2 = ops.mamba_scan(dt[:, half:], Bm[:, half:], Cm[:, half:],
                            x[:, half:], A, Dsk, h1)
    np.testing.assert_allclose(np.asarray(y2), np.asarray(y_full[:, half:]),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(h2), np.asarray(h_full), atol=1e-4,
                               rtol=1e-4)


# ---------------------------------------------------------------------------
# rg-lru scan
# ---------------------------------------------------------------------------

@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2 ** 16), bw=st.sampled_from([64, 128, 256]))
def test_rglru_scan_property(seed, bw):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    B, S, W = 2, 48, 256
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (B, S, W)))  # decay in (0,1)
    b = jax.random.normal(ks[1], (B, S, W))
    h0 = jax.random.normal(ks[2], (B, W))
    hs, h = ops.rglru_scan(a, b, h0, bw=bw)
    hs_ref, h_ref = ref.rglru_scan_ref(a, b, h0)
    np.testing.assert_allclose(np.asarray(hs), np.asarray(hs_ref), atol=1e-5,
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref), atol=1e-5,
                               rtol=1e-5)


def test_rglru_matches_model_block():
    """Kernel agrees with the rglru model layer's own chunked scan."""
    from repro.models.mamba import _chunked_scan
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    B, S, W = 2, 64, 128
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (B, S, W)))
    b = jax.random.normal(ks[1], (B, S, W))
    h0 = jnp.zeros((B, W))
    hs_model, h_model = _chunked_scan(a, b, h0)
    hs_kern, h_kern = ops.rglru_scan(a, b, h0)
    np.testing.assert_allclose(np.asarray(hs_kern), np.asarray(hs_model),
                               atol=1e-5, rtol=1e-5)
