"""Pallas TPU RG-LRU linear recurrence: h_t = a_t * h_{t-1} + b_t.

  a, b: [B, S, W] f32 (decay / gated input, precomputed by the block)
  h0:   [B, W]    f32
  out:  hs [B, S, W] f32, h_last [B, W] f32

Grid (B, nw): width tiles are independent (this is exactly why lru_width
shards cleanly over the model axis); time is scanned sequentially on-chip.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _rglru_kernel(a_ref, b_ref, h0_ref, hs_ref, h_ref, *, seq: int):
    h = h0_ref[0]                                         # [bw]

    def body(t, h):
        h = a_ref[0, t] * h + b_ref[0, t]
        hs_ref[0, t] = h
        return h

    h = jax.lax.fori_loop(0, seq, body, h)
    h_ref[0] = h


def rglru_scan(a: jax.Array, b: jax.Array, h0: jax.Array,
               *, bw: int = 512, interpret: bool):
    B, S, W = a.shape
    bw = min(bw, W)
    assert W % bw == 0, (W, bw)
    kernel = functools.partial(_rglru_kernel, seq=S)
    return pl.pallas_call(
        kernel,
        grid=(B, W // bw),
        in_specs=[
            pl.BlockSpec((1, S, bw), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, S, bw), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, bw), lambda i, j: (i, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, S, bw), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, bw), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, W), jnp.float32),
            jax.ShapeDtypeStruct((B, W), jnp.float32),
        ],
        interpret=interpret,
    )(a, b, h0)
