"""Pallas TPU flash-decode attention: one query token over a KV ring cache.

  q:   [B, K, G, hd]          (single position, grouped-query layout)
  k,v: [B, C, K, hd]          (ring cache, C slots)
  tok: [B, C] int32           (absolute token index per slot, -1 = empty)
  pos: [B] int32              (current position)
  out: [B, K, G, hd]

Grid (B, K, nc) with the LAST axis sequential (TPU semantics): kv tiles
stream through VMEM while m/l/acc accumulators persist in scratch across
the nc iterations; the final iteration writes out.  This is the
distributed-friendly layout matching the seq-sharded cache of the
serving dry-run.

QUANTIZED mode (``k_scale``/``k_zero``/``v_scale`` [B, C, K] given; k/v
int8): tiles are dequantized IN-REGISTER — the [bk] scale vectors ride
in the same block walk as their int8 rows, and the fp32 multiply-add
happens on the VMEM tile right before the QK^T / PV matmuls, so HBM
traffic is the int8 bytes plus an hd-th of scales (kernels/kv_quant.py
defines the number format; softmax accumulators stay fp32 as in the fp
kernel).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(q_ref, k_ref, v_ref, *rest, bk: int, nc: int,
                   scale: float, window: Optional[int]):
    """One body for fp and int8 modes.  Quantized calls pass three extra
    scale refs ([1, bk, 1] blocks of the [B, C, K] sidecars) and the k/v
    tiles are dequantized in-register (asymmetric K: (q+128)*scale+zero;
    symmetric V: q*scale) before the shared online-softmax update."""
    if len(rest) == 9:                                        # quantized
        ks_ref, kz_ref, vs_ref, tok_ref, pos_ref, o_ref, \
            m_ref, l_ref, acc_ref = rest
    else:
        ks_ref = kz_ref = vs_ref = None
        tok_ref, pos_ref, o_ref, m_ref, l_ref, acc_ref = rest
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale               # [G, hd]
    k = k_ref[0, :, 0].astype(jnp.float32)                    # [bk, hd]
    v = v_ref[0, :, 0].astype(jnp.float32)
    if ks_ref is not None:
        k = ((k + 128.0) * ks_ref[0, :, 0][:, None]
             + kz_ref[0, :, 0][:, None])
        v = v * vs_ref[0, :, 0][:, None]
    tok = tok_ref[0]                                          # [bk]
    pos = pos_ref[0, 0]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))   # [G, bk]
    valid = (tok >= 0) & (tok <= pos)
    if window is not None:
        valid = valid & (tok > pos - window)
    s = jnp.where(valid[None, :], s, NEG_INF)

    m_prev, l_prev, acc_prev = m_ref[...], l_ref[...], acc_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(p, axis=1)
    acc_new = acc_prev * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())))
    m_ref[...], l_ref[...], acc_ref[...] = m_new, l_new, acc_new

    @pl.when(c == nc - 1)
    def _fin():
        o_ref[0, 0] = (acc_new / jnp.maximum(l_new, 1e-30)[:, None]
                       ).astype(o_ref.dtype)


def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     tok: jax.Array, pos: jax.Array,
                     *, k_scale: Optional[jax.Array] = None,
                     k_zero: Optional[jax.Array] = None,
                     v_scale: Optional[jax.Array] = None,
                     window: Optional[int] = None, bk: int = 128,
                     interpret: bool) -> jax.Array:
    """q: [B,K,G,hd]; k/v: [B,C,K,hd]; tok: [B,C]; pos: [B].
    With k_scale/k_zero/v_scale ([B,C,K] f32), k/v are int8 and
    dequantized inside the kernel."""
    B, K, G, hd = q.shape
    C = k.shape[1]
    bk = min(bk, C)
    assert C % bk == 0, (C, bk)
    nc = C // bk
    scale = hd ** -0.5
    quant = k_scale is not None
    assert quant == (k_zero is not None) == (v_scale is not None)
    pos2 = pos[:, None]                                       # [B,1] for SMEM
    sc_spec = pl.BlockSpec((1, bk, 1), lambda b, h, c: (b, c, h))
    kernel = functools.partial(_decode_kernel, bk=bk, nc=nc, scale=scale,
                               window=window)
    if quant:
        extra_in, extra_specs = ([k_scale, k_zero, v_scale],
                                 [sc_spec, sc_spec, sc_spec])
    else:
        extra_in, extra_specs = [], []
    return pl.pallas_call(
        kernel,
        grid=(B, K, nc),
        in_specs=[
            pl.BlockSpec((1, 1, G, hd), lambda b, h, c: (b, h, 0, 0)),
            pl.BlockSpec((1, bk, 1, hd), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, bk, 1, hd), lambda b, h, c: (b, c, h, 0)),
            *extra_specs,
            pl.BlockSpec((1, bk), lambda b, h, c: (b, c)),
            pl.BlockSpec((1, 1), lambda b, h, c: (b, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, hd), lambda b, h, c: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, K, G, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G, hd), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, *extra_in, tok, pos2)
