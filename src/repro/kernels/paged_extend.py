"""Pallas TPU paged attention: Sx query lanes per request over a paged
KV pool.  The one read kernel behind every paged engine step — decode
(Sx=1), chunked-prefill / mixed steps, and the speculative VERIFY step
([max_batch, 1 + spec_tokens]).

  q:          [B, Sx, K, G, hd]    (lane l sits at absolute pos0[b] + l)
  k_pool:     [L, P, K, ps, hd]    (stacked head-major page pools, one per
                                    layer, P physical pages each)
  v_pool:     [L, P, K, ps, hd]
  page_table: [B, NP] int32        (logical page -> physical page, -1 = unmapped)
  pos0:       [B] int32            (absolute position of lane 0)
  layer:      int32 scalar         (which of the L pools this call reads)
  out:        [B, Sx, K, G, hd]

The kernel reads the layer scan's whole stacked pool in place
(docs/SERVING.md#the-pool-through-the-layer-scan): slicing one layer's
pool out first would materialize a pool-sized copy per layer per step.
A single layer's pool ``[P, K, ps, hd]`` is the stack ``pool[None]`` at
layer 0.

TPU tiling: a block's last two dims must be multiples of the (8, 128)
tile or span the whole array dim.  The pool is HEAD-MAJOR so that one
page block ``(None, 1, K, ps, hd)`` — the layer dim squeezed — spans its
last three dims whole, legal for any page size and head count, and the
int8 scale sidecars ``[L, P, K, ps]`` likewise ride as
``(None, 1, K, ps)`` blocks.  The page table, ``pos0`` and ``layer`` are
SCALAR-PREFETCH operands (``pltpu.PrefetchScalarGridSpec``, in SMEM):
the k/v index maps resolve ``(layer, page_table[b, j], 0, 0, 0)`` into
the HBM page to DMA next, so the gather never materializes a dense
[B, NP*ps, K, hd] copy the way the XLA path (``_gather_pages``) does,
and each page is read ONCE for all Sx lanes and all K heads.

Grid (B, NP/ppb) with the last axis sequential: page blocks stream
through VMEM while fp32 m/l/acc accumulators persist in scratch; the
final block writes out.  ``pages_per_block`` (ppb) widens one step to
ppb page DMAs — physically scattered pages cannot form one block, so
the pool rides in as ppb separate inputs whose index maps walk
``page_table[b, jb*ppb + i]``.  ``bq`` tiles the Sx*G query rows per
matmul.  Both come from the autotuned table (kernels/tuning.py) when
not forced.

Query rows are laid out ``r = lane * G + g`` per kv head, so lane
``r // G`` attends token ``t`` iff ``G * (t - pos0) <= r`` (causal) and
``r < G * (t - pos0 + window)`` (sliding window) — integer products
only, no vector division.  Pages that are unmapped, wholly in the
future of every lane, or wholly behind the window skip their compute,
and their index map repeats the nearest needed page so the pipeline
issues no DMA for them.  Pad lanes (engine ``n_valid``) compute rows no
caller consumes, exactly as the XLA path does.

QUANTIZED mode (``k_scale``/``k_zero``/``v_scale`` sidecar pools
[L, P, K, ps] f32; payload pools int8): the scales fold into the score and
probability rows (``s = ks * (q . kq) + sum(q) * (128 ks + kz)``;
``p * vs`` before the PV matmul — the same factored dequant as the XLA
path, kernels/kv_quant.py), with fp32 accumulators unchanged.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

NEG_INF = -1e30


def _needed_pages(pos0, *, sx: int, ps: int, np_: int,
                  window: Optional[int]):
    """First and last logical page any lane of a row can attend."""
    last = jnp.minimum((pos0 + sx - 1) // ps, np_ - 1)
    if window is None:
        return 0, last
    first = jnp.minimum(jnp.maximum(pos0 - window + 1, 0) // ps, last)
    return first, last


def _kernel(pt_ref, pos_ref, layer_ref, q_ref, *rest, ps: int, np_: int,
            ppb: int, npb: int, sx: int, g: int, heads: int, bq: int,
            scale: float, window: Optional[int], quant: bool):
    """One body for fp and int8.  ``rest`` carries ppb interleaved page
    refs — (k, v) or (k, v, ks, kz, vs) per sub-page — then the output
    and the three fp32 scratch accumulators.  ``layer_ref`` is read by
    the index maps alone."""
    per = 5 if quant else 2
    pages, (o_ref, m_ref, l_ref, acc_ref) = rest[:ppb * per], rest[ppb * per:]
    b = pl.program_id(0)
    jb = pl.program_id(1)
    pos0 = pos_ref[b]
    rows = sx * g
    first, last = _needed_pages(pos0, sx=sx, ps=ps, np_=np_, window=window)

    @pl.when(jb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    for i in range(ppb):
        j = jb * ppb + i                                  # logical page
        live = (pt_ref[b * np_ + j] >= 0) & (j >= first) & (j <= last)

        @pl.when(live)
        def _page(i=i, j=j):
            refs = pages[i * per:(i + 1) * per]
            r = jax.lax.broadcasted_iota(jnp.int32, (rows, ps), 0)
            d = j * ps - pos0 + jax.lax.broadcasted_iota(
                jnp.int32, (rows, ps), 1)                 # t - pos0
            valid = d * g <= r
            if window is not None:
                valid = valid & (r < (d + window) * g)
            for h in range(heads):
                k = refs[0][0, h].astype(jnp.float32)     # [ps, hd]
                v = refs[1][0, h].astype(jnp.float32)
                if quant:
                    ks, kz, vs = (x[0, h:h + 1, :] for x in refs[2:5])
                for r0 in range(0, rows, bq):
                    rs = slice(r0, min(r0 + bq, rows))
                    q = q_ref[0, h, rs].astype(jnp.float32) * scale
                    s = jax.lax.dot_general(
                        q, k, (((1,), (1,)), ((), ())))   # [bq, ps]
                    if quant:
                        s = s * ks + (jnp.sum(q, axis=1, keepdims=True)
                                      * (128.0 * ks + kz))
                    s = jnp.where(valid[rs], s, NEG_INF)
                    m_prev, l_prev = m_ref[h, rs], l_ref[h, rs]
                    m_new = jnp.maximum(m_prev,
                                        jnp.max(s, axis=1, keepdims=True))
                    p = jnp.exp(s - m_new)
                    corr = jnp.exp(m_prev - m_new)
                    l_ref[h, rs] = l_prev * corr + jnp.sum(p, axis=1,
                                                           keepdims=True)
                    if quant:
                        p = p * vs
                    acc_ref[h, rs] = acc_ref[h, rs] * corr + \
                        jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())))
                    m_ref[h, rs] = m_new

    @pl.when(jb == npb - 1)
    def _fin():
        for h in range(heads):
            o_ref[0, h] = (acc_ref[h] / jnp.maximum(l_ref[h], 1e-30)
                           ).astype(o_ref.dtype)


def paged_extend_attention(q: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array, page_table: jax.Array,
                           pos0: jax.Array, layer,
                           *, k_scale: Optional[jax.Array] = None,
                           k_zero: Optional[jax.Array] = None,
                           v_scale: Optional[jax.Array] = None,
                           window: Optional[int] = None,
                           bq: Optional[int] = None,
                           pages_per_block: int = 1,
                           interpret: bool) -> jax.Array:
    """q: [B,Sx,K,G,hd]; k/v_pool: [L,P,K,ps,hd]; page_table: [B,NP];
    pos0: [B]; layer: int32 scalar in [0, L).  With k_scale/k_zero/
    v_scale ([L,P,K,ps] f32 sidecar pools) the payload pools are int8
    and dequantized in-kernel."""
    B, Sx, K, G, hd = q.shape
    ps = k_pool.shape[3]
    NP = page_table.shape[1]
    quant = k_scale is not None
    if quant != (k_zero is not None) or quant != (v_scale is not None):
        raise ValueError("k_scale, k_zero and v_scale come together")
    rows = Sx * G
    bq = rows if bq is None else max(1, min(bq, rows))
    # physically scattered pages cannot widen a DMA block, so ppb rides as
    # ppb separate page-walk inputs; it must tile the table exactly
    ppb = max(d for d in range(1, max(1, pages_per_block) + 1)
              if NP % d == 0)
    npb = NP // ppb

    def page_map(i, trailing):
        def index_map(b, jb, pt, pos, lyr):
            first, last = _needed_pages(pos[b], sx=Sx, ps=ps, np_=NP,
                                        window=window)
            j = jnp.clip(jb * ppb + i, first, last)
            # unmapped pages DMA physical page 0; the body skips them
            return (lyr[0], jnp.maximum(pt[b * NP + j], 0)) + (0,) * trailing
        return index_map

    # the layer dim is squeezed: the body sees (1, K, ps[, hd]) blocks
    page_in, page_specs = [], []
    for i in range(ppb):
        page_in += [k_pool, v_pool]
        page_specs += [pl.BlockSpec((None, 1, K, ps, hd),
                                    page_map(i, 3))] * 2
        if quant:
            page_in += [k_scale, k_zero, v_scale]
            page_specs += [pl.BlockSpec((None, 1, K, ps),
                                        page_map(i, 2))] * 3

    kernel = functools.partial(_kernel, ps=ps, np_=NP, ppb=ppb, npb=npb,
                               sx=Sx, g=G, heads=K, bq=bq,
                               scale=hd ** -0.5, window=window, quant=quant)
    row_spec = pl.BlockSpec((1, K, rows, hd), lambda b, jb, pt, pos, lyr:
                            (b, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, npb),
        in_specs=[row_spec, *page_specs],
        out_specs=row_spec,
        scratch_shapes=[
            pltpu.VMEM((K, rows, 1), jnp.float32),
            pltpu.VMEM((K, rows, 1), jnp.float32),
            pltpu.VMEM((K, rows, hd), jnp.float32),
        ],
    )
    # rows r = lane * G + g per kv head
    qh = jnp.transpose(q, (0, 2, 1, 3, 4)).reshape(B, K, rows, hd)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, rows, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="paged_attention",
    )(page_table.astype(jnp.int32).reshape(-1), pos0.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), qh, *page_in)
    return jnp.transpose(out.reshape(B, K, Sx, G, hd), (0, 2, 1, 3, 4))
