"""Pallas TPU Mamba-1 selective scan.

  dt:   [B, S, D]  f32   (post-softplus step sizes)
  Bm:   [B, S, N]  f32   (input matrix rows)
  Cm:   [B, S, N]  f32   (output matrix rows)
  x:    [B, S, D]        (post-conv activations)
  A:    [D, N]     f32   (negative-definite state matrix)
  Dsk:  [D]        f32   (skip connection)
  h0:   [B, D, N]  f32   (initial state — prefix-cache extension)
  out:  y [B, S, D], h_last [B, D, N]

Grid (B, nd): each program owns a d_inner tile and scans time
sequentially in VMEM — the recurrent dim stays on-chip, matching how the
d_inner axis is model-sharded in the dry-run (state never crosses chips).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _mamba_kernel(dt_ref, b_ref, c_ref, x_ref, a_ref, dsk_ref, h0_ref,
                  y_ref, h_ref, *, seq: int):
    A = a_ref[...]                                        # [bd, N]
    dsk = dsk_ref[...]                                    # [bd]
    h = h0_ref[0]                                         # [bd, N]

    def body(t, h):
        dt = dt_ref[0, t].astype(jnp.float32)             # [bd]
        xb = x_ref[0, t].astype(jnp.float32)              # [bd]
        Bm = b_ref[0, t].astype(jnp.float32)              # [N]
        Cm = c_ref[0, t].astype(jnp.float32)              # [N]
        a = jnp.exp(dt[:, None] * A)                      # [bd, N]
        h = a * h + (dt * xb)[:, None] * Bm[None, :]
        y = jnp.sum(h * Cm[None, :], axis=1) + dsk * xb
        y_ref[0, t] = y.astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, seq, body, h)
    h_ref[0] = h


def mamba_scan(dt: jax.Array, Bm: jax.Array, Cm: jax.Array, x: jax.Array,
               A: jax.Array, Dsk: jax.Array, h0: jax.Array,
               *, bd: int = 256, interpret: bool):
    """Returns (y [B,S,D] f32, h_last [B,D,N] f32)."""
    B, S, D = dt.shape
    N = A.shape[1]
    bd = min(bd, D)
    assert D % bd == 0, (D, bd)
    grid = (B, D // bd)
    kernel = functools.partial(_mamba_kernel, seq=S)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, S, bd), lambda b, d: (b, 0, d)),   # dt
            pl.BlockSpec((1, S, N), lambda b, d: (b, 0, 0)),    # Bm
            pl.BlockSpec((1, S, N), lambda b, d: (b, 0, 0)),    # Cm
            pl.BlockSpec((1, S, bd), lambda b, d: (b, 0, d)),   # x
            pl.BlockSpec((bd, N), lambda b, d: (d, 0)),         # A
            pl.BlockSpec((bd,), lambda b, d: (d,)),             # Dsk
            pl.BlockSpec((1, bd, N), lambda b, d: (b, d, 0)),   # h0
        ],
        out_specs=[
            pl.BlockSpec((1, S, bd), lambda b, d: (b, 0, d)),
            pl.BlockSpec((1, bd, N), lambda b, d: (b, d, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, D), jnp.float32),
            jax.ShapeDtypeStruct((B, D, N), jnp.float32),
        ],
        interpret=interpret,
    )(dt, Bm, Cm, x, A, Dsk, h0)
