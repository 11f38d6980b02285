"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import kv_quant

NEG_INF = -1e30


def _maybe_dequant(k, v, k_scale, k_zero, v_scale):
    """Dequantize int8 K/V (+ per-slot-per-head scales) to float32; pass
    fp caches through.  Shared by the decode/paged-decode oracles so the
    quantized kernels are checked against exactly kv_quant's math."""
    if k_scale is None:
        return k.astype(jnp.float32), v.astype(jnp.float32)
    return (kv_quant.dequantize_k(k, k_scale, k_zero),
            kv_quant.dequantize_v(v, v_scale))


def flash_attention_ref(q, k, v, *, window: Optional[int] = None):
    """q: [B,H,S,hd]; k/v: [B,K,S,hd] -> [B,H,S,hd] (causal)."""
    B, H, S, hd = q.shape
    K = k.shape[1]
    G = H // K
    qf = q.reshape(B, K, G, S, hd).astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    s = jnp.einsum("bkgsd,bktd->bkgst", qf, kf) * hd ** -0.5
    i = jnp.arange(S)[:, None]
    j = jnp.arange(S)[None, :]
    mask = j <= i
    if window is not None:
        mask = mask & (j > i - window)
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgst,bktd->bkgsd", p, vf)
    return o.reshape(B, H, S, hd).astype(q.dtype)


def decode_attention_ref(q, k, v, tok, pos, *, k_scale=None, k_zero=None,
                         v_scale=None, window: Optional[int] = None):
    """q: [B,K,G,hd]; k/v: [B,C,K,hd]; tok: [B,C]; pos: [B].  Optional
    scales ([B,C,K]) mark an int8 cache (dequantized here)."""
    B, K, G, hd = q.shape
    kf, vf = _maybe_dequant(k, v, k_scale, k_zero, v_scale)
    qf = q.astype(jnp.float32)
    s = jnp.einsum("bkgd,btkd->bkgt", qf, kf) * hd ** -0.5
    valid = (tok >= 0) & (tok <= pos[:, None])
    if window is not None:
        valid = valid & (tok > pos[:, None] - window)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgt,btkd->bkgd", p, vf)
    return o.astype(q.dtype)


def _gather_logical(pool, page_table):
    """Head-major pool leaf [P, K, ps, ...] + [B, NP] table -> dense
    logical [B, NP*ps, K, ...] (unmapped entries gather page 0)."""
    B, NP = page_table.shape
    g = pool[jnp.maximum(page_table, 0)]                      # [B,NP,K,ps,...]
    g = jnp.moveaxis(g, 3, 2)                                 # [B,NP,ps,K,...]
    return g.reshape(B, NP * g.shape[2], *g.shape[3:])


def paged_decode_attention_ref(q, k_pool, v_pool, page_table, pos, *,
                               k_scale=None, k_zero=None, v_scale=None,
                               window: Optional[int] = None):
    """q: [B,K,G,hd]; k/v_pool: [P,K,ps,hd]; page_table: [B,NP]; pos: [B].

    One-lane case of :func:`paged_extend_attention_ref` (decode step)."""
    return paged_extend_attention_ref(
        q[:, None], k_pool, v_pool, page_table, pos, k_scale=k_scale,
        k_zero=k_zero, v_scale=v_scale, window=window)[:, 0]


def paged_extend_attention_ref(q, k_pool, v_pool, page_table, pos0, *,
                               k_scale=None, k_zero=None, v_scale=None,
                               window: Optional[int] = None):
    """q: [B,Sx,K,G,hd]; k/v_pool: [P,K,ps,hd]; page_table: [B,NP];
    pos0: [B] (absolute position of query lane 0).

    Dense-gather oracle for the paged attention kernel: lane l of
    request b sits at ``pos0[b] + l`` and attends every mapped slot
    ``t <= pos0[b] + l`` (minus the sliding window, when set) — the
    per-lane staircase mask of ``attention_extend_paged``.  Optional
    scale sidecar pools ([P,K,ps]) mark an int8 pool.
    """
    B, Sx, K, G, hd = q.shape
    ps = k_pool.shape[2]
    NP = page_table.shape[1]
    kg = _gather_logical(k_pool, page_table)                  # [B,T,K,hd]
    vg = _gather_logical(v_pool, page_table)
    if k_scale is not None:
        kg = kv_quant.dequantize_k(kg, _gather_logical(k_scale, page_table),
                                   _gather_logical(k_zero, page_table))
        vg = kv_quant.dequantize_v(vg, _gather_logical(v_scale, page_table))
    qf = q.astype(jnp.float32)
    s = jnp.einsum("bskgd,btkd->bskgt", qf, kg.astype(jnp.float32)) \
        * hd ** -0.5
    t = jnp.arange(NP * ps)[None, None, :]                    # [1,1,T]
    pos_lane = pos0[:, None] + jnp.arange(Sx)[None, :]        # [B,Sx]
    mapped = jnp.repeat(page_table >= 0, ps, axis=1)[:, None, :]
    valid = mapped & (t <= pos_lane[..., None])
    if window is not None:
        valid = valid & (t > pos_lane[..., None] - window)
    s = jnp.where(valid[:, :, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bskgt,btkd->bskgd", p, vg.astype(jnp.float32))
    return o.astype(q.dtype)


def mamba_scan_ref(dt, Bm, Cm, x, A, Dsk, h0):
    """Sequential reference for the selective scan."""
    B, S, D = dt.shape

    def step(h, t):
        a = jnp.exp(dt[:, t, :, None] * A[None])            # [B,D,N]
        h = a * h + (dt[:, t] * x[:, t].astype(jnp.float32))[..., None] \
            * Bm[:, t, None, :].astype(jnp.float32)
        y = jnp.einsum("bdn,bn->bd", h, Cm[:, t].astype(jnp.float32))
        y = y + Dsk[None] * x[:, t].astype(jnp.float32)
        return h, y

    h, ys = jax.lax.scan(step, h0, jnp.arange(S))
    return ys.swapaxes(0, 1), h


def rglru_scan_ref(a, b, h0):
    def step(h, t):
        h = a[:, t] * h + b[:, t]
        return h, h

    h, hs = jax.lax.scan(step, h0, jnp.arange(a.shape[1]))
    return hs.swapaxes(0, 1), h
