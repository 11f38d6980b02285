"""int8 KV-cache quantization math, shared by every layer of the stack.

One recipe, four callers: the write-time quantizers in
``models/attention.py`` (ring selects/scatters and paged-pool scatters),
the XLA read paths (dequantize the gathered logical view), the Pallas
kernels (in-register tile dequant — the fused path), and the ``ref.py``
oracles.  Keeping the arithmetic here is what makes "kernel == oracle ==
XLA path" a meaningful parity statement.

Granularity is per *cache slot* per *kv head* (reduction over the head
dim only):

  * K — ASYMMETRIC:  q = round((k - min) / scale) - 128,
        scale = (max - min) / 255, zero = min.  RoPE'd keys are not
        zero-centered per head, so the zero-point buys ~1 bit of
        effective precision over symmetric quant.
  * V — SYMMETRIC:   q = round(v / scale), scale = amax / 127.
        Values are consumed through a convex combination (softmax
        weights sum to 1), so a zero-point would cancel anyway.

Scales/zeros are float32 sidecars shaped like the cache minus the head
dim ([..., K] for a [..., K, hd] cache) — in paged mode they live in
``[num_pages, K, page_size]`` pools that carry the same ``pages``
logical axis as the int8 payload, so copy-on-write, snapshot pinning
and nbytes accounting move them with their pages for free.

Quantization is deterministic (round-half-even, no stochastic
rounding): replaying the same tokens after a preemption, or re-writing
a position through a different chunking, reproduces bit-identical int8
pages — the engine's replay/COW exactness tests rely on this.
"""
from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp

# Guard for degenerate (constant / all-zero) slot-head rows: keeps the
# scale strictly positive so dequant maps q -> exactly the constant.
EPS = 1e-6


def quantize_k(k: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """k: [..., hd] float -> (q int8 [..., hd], scale f32 [...], zero f32 [...])."""
    kf = k.astype(jnp.float32)
    kmin = jnp.min(kf, axis=-1)
    kmax = jnp.max(kf, axis=-1)
    scale = jnp.maximum(kmax - kmin, EPS) / 255.0
    q = jnp.round((kf - kmin[..., None]) / scale[..., None]) - 128.0
    q = jnp.clip(q, -128, 127).astype(jnp.int8)
    return q, scale, kmin


def dequantize_k(q: jnp.ndarray, scale: jnp.ndarray,
                 zero: jnp.ndarray) -> jnp.ndarray:
    """Inverse of :func:`quantize_k`; returns float32."""
    return ((q.astype(jnp.float32) + 128.0) * scale[..., None]
            + zero[..., None])


def quantize_v(v: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """v: [..., hd] float -> (q int8 [..., hd], scale f32 [...])."""
    vf = v.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(vf), axis=-1), EPS) / 127.0
    q = jnp.clip(jnp.round(vf / scale[..., None]), -128, 127).astype(jnp.int8)
    return q, scale


def dequantize_v(q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    """Inverse of :func:`quantize_v`; returns float32."""
    return q.astype(jnp.float32) * scale[..., None]
