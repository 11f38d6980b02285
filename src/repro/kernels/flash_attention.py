"""Pallas TPU flash attention (prefill): causal GQA + optional window.

TARGET is TPU (MXU-aligned 128x tiles, VMEM accumulators); validated on
CPU via interpret=True against ref.py.  Layout:

  q:   [B, H, S, hd]     (H = K * G query heads)
  k,v: [B, K, S, hd]
  out: [B, H, S, hd]

Grid (B, H, nq): each program owns one q tile and streams kv tiles from
the per-(batch, kv-head) VMEM block with an online-softmax fori_loop,
skipping tiles beyond the causal frontier (and outside the sliding
window when set).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, bq: int, bk: int,
                  seq: int, scale: float, window: Optional[int]):
    qi = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32) * scale            # [bq, hd]
    hd = q.shape[-1]
    row = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)

    # causal frontier: kv tiles strictly above the diagonal contribute 0
    hi = jnp.minimum((qi * bq + bq + bk - 1) // bk, seq // bk)
    if window is not None:
        lo = jnp.maximum((qi * bq - window) // bk, 0)
    else:
        lo = 0

    def body(t, carry):
        m, l, acc = carry
        k = k_ref[0, 0, pl.ds(t * bk, bk)].astype(jnp.float32)   # [bk, hd]
        v = v_ref[0, 0, pl.ds(t * bk, bk)].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # [bq, bk]
        col = t * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = col <= row
        if window is not None:
            mask = mask & (col > row - window)
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=1)
        acc_new = acc * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())))
        return m_new, l_new, acc_new

    m0 = jnp.full((bq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    a0 = jnp.zeros((bq, hd), jnp.float32)
    m, l, acc = jax.lax.fori_loop(lo, hi, body, (m0, l0, a0))
    out = acc / jnp.maximum(l, 1e-30)[:, None]
    o_ref[0, 0] = out.astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    *, window: Optional[int] = None, bq: int = 128,
                    bk: int = 128, interpret: bool) -> jax.Array:
    """q: [B,H,S,hd]; k,v: [B,K,S,hd] -> [B,H,S,hd]."""
    B, H, S, hd = q.shape
    K = k.shape[1]
    G = H // K
    bq = min(bq, S)
    bk = min(bk, S)
    assert S % bq == 0 and S % bk == 0, (S, bq, bk)
    scale = hd ** -0.5
    grid = (B, H, S // bq)
    kernel = functools.partial(_flash_kernel, bq=bq, bk=bk, seq=S,
                               scale=scale, window=window)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, S, hd), lambda b, h, i: (b, h // G, 0, 0)),
            pl.BlockSpec((1, 1, S, hd), lambda b, h, i: (b, h // G, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda b, h, i: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(q, k, v)
