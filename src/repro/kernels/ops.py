"""jit'd public wrappers over the Pallas kernels.

The backend decides how a kernel runs, once per process
(:func:`interpret_mode`): compiled through Mosaic on a TPU, in the
Pallas interpreter (a correctness tool) anywhere else.  The kernel
modules themselves take ``interpret`` with no default, so nothing below
this layer can fall into the interpreter unnoticed.

Block parameters (``bq``/``bk``/``pages_per_block``) default to None,
which resolves through the committed autotuning table
(kernels/tuning.py) at trace time — per (backend, kernel, shape bucket),
falling back to the old hardcoded 128s when no entry exists.  Passing an
explicit value always wins (tests and sweeps pin blocks that way).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax

from repro.kernels import tuning
from repro.kernels.decode_attention import decode_attention as _decode
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.mamba_scan import mamba_scan as _mamba
from repro.kernels.paged_extend import paged_extend_attention as _paged
from repro.kernels.rglru_scan import rglru_scan as _rglru


@functools.cache
def interpret_mode() -> bool:
    """True off-TPU: kernels then run in the Pallas interpreter."""
    return jax.default_backend() != "tpu"


def resolve_attn_impl(requested: Optional[str], n_devices: int = 1) -> str:
    """Pick the paged-attention read implementation for an engine.

    None = auto: pallas on a single TPU device, xla otherwise.  The
    Pallas paged kernel has no shard_map wrapper yet, so under a
    >1-device mesh it would be traced with *global* pool shapes; auto
    picks the XLA gather path there, which GSPMD partitions along the
    pool's sharded 'pages' axis.  An explicit ``"pallas"`` that cannot be
    honoured raises instead of quietly becoming ``"xla"``.
    """
    if requested not in (None, "pallas", "xla"):
        raise ValueError(f"unknown attn_impl {requested!r}")
    if requested is None:
        return "pallas" if not interpret_mode() and n_devices == 1 else "xla"
    if requested == "pallas" and n_devices > 1:
        raise ValueError(
            f"attn_impl='pallas' cannot run on a {n_devices}-device mesh: "
            f"the paged kernel has no shard_map wrapper; use attn_impl="
            f"None (auto) or 'xla'")
    return requested


@functools.partial(jax.jit, static_argnames=("window", "bq", "bk"))
def flash_attention(q, k, v, *, window: Optional[int] = None,
                    bq: Optional[int] = None, bk: Optional[int] = None):
    if bq is None or bk is None:
        tuned = tuning.lookup("flash", s=q.shape[2], hd=q.shape[3])
        bq = tuned["bq"] if bq is None else bq
        bk = tuned["bk"] if bk is None else bk
    return _flash(q, k, v, window=window, bq=bq, bk=bk,
                  interpret=interpret_mode())


@functools.partial(jax.jit, static_argnames=("window", "bk"))
def decode_attention(q, k, v, tok, pos, *, k_scale=None, k_zero=None,
                     v_scale=None, window: Optional[int] = None,
                     bk: Optional[int] = None):
    """k_scale/k_zero/v_scale ([B,C,K] f32) select the fused-dequant int8
    kernel (k/v int8)."""
    if bk is None:
        bk = tuning.lookup("decode", ctx=k.shape[1], hd=q.shape[-1])["bk"]
    return _decode(q, k, v, tok, pos, k_scale=k_scale, k_zero=k_zero,
                   v_scale=v_scale, window=window, bk=bk,
                   interpret=interpret_mode())


def paged_decode_attention(q, k_pool, v_pool, page_table, pos, *,
                           k_scale=None, k_zero=None, v_scale=None,
                           window: Optional[int] = None):
    """One-token decode over the page pool (q: [B,K,G,hd]): the paged
    kernel at Sx=1.  Scale sidecar pools ([P,K,ps] f32) select the
    fused-dequant int8 variant (pools int8)."""
    return paged_extend_attention(
        q[:, None], k_pool, v_pool, page_table, pos, k_scale=k_scale,
        k_zero=k_zero, v_scale=v_scale, window=window)[:, 0]


@functools.partial(jax.jit, static_argnames=("window", "bq",
                                             "pages_per_block"))
def paged_extend_attention(q, k_pool, v_pool, page_table, pos0, *,
                           k_scale=None, k_zero=None, v_scale=None,
                           window: Optional[int] = None,
                           bq: Optional[int] = None,
                           pages_per_block: Optional[int] = None):
    """Paged multi-lane extend/verify attention (q: [B,Sx,K,G,hd];
    pools [P,K,ps,hd]); the kernel behind decode, chunked prefill and
    speculative verify.  Scale sidecar pools ([P,K,ps] f32) select the
    fused-dequant int8 variant."""
    if bq is None or pages_per_block is None:
        B, Sx, K, G, hd = q.shape
        tuned = tuning.lookup("paged_extend", r=Sx * G, hd=hd,
                              ctx=page_table.shape[1] * k_pool.shape[2])
        bq = tuned["bq"] if bq is None else bq
        if pages_per_block is None:
            pages_per_block = tuned["pages_per_block"]
    return _paged(q, k_pool, v_pool, page_table, pos0, k_scale=k_scale,
                  k_zero=k_zero, v_scale=v_scale, window=window, bq=bq,
                  pages_per_block=pages_per_block,
                  interpret=interpret_mode())


@functools.partial(jax.jit, static_argnames=("bd",))
def mamba_scan(dt, Bm, Cm, x, A, Dsk, h0, *, bd: int = 256):
    return _mamba(dt, Bm, Cm, x, A, Dsk, h0, bd=bd,
                  interpret=interpret_mode())


@functools.partial(jax.jit, static_argnames=("bw",))
def rglru_scan(a, b, h0, *, bw: int = 512):
    return _rglru(a, b, h0, bw=bw, interpret=interpret_mode())
