"""GQA attention blocks: full-sequence (train/prefill) and single-token decode.

KV cache layout per layer: ``{"k": [B, C, K, D], "v": [B, C, K, D],
"tok": [B, C] int32}`` where ``C`` is the cache capacity (ring buffer when a
sliding window is active).  ``tok`` stores the absolute token index held in
each slot (-1 = empty) which makes windowed/ring masking trivial and exact.

The ``impl`` switch selects the XLA einsum path (default; used for training
and dry-run lowering) or the Pallas TPU kernels in ``repro.kernels``.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import kv_quant as Q
from repro.kernels import ops
from repro.models import layers as L

NEG_INF = -1e30
ATTN_CHUNK = 1024          # flash path kicks in above this sequence length
# §Perf hillclimb #1: iterate only lower-triangular (q-chunk, kv-chunk)
# pairs for causal attention instead of masking the full nq x nk grid —
# halves attention FLOPs (the dominant term for small-d archs at 4k+).
CAUSAL_SKIP = True


# ---------------------------------------------------------------------------
# Parameter definitions
# ---------------------------------------------------------------------------

def attn_def(cfg: ModelConfig, dtype) -> Dict:
    d, H, K, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    p = {
        "wq": L.ParamDef((d, H, hd), ("embed", "heads", None), dtype),
        "wk": L.ParamDef((d, K, hd), ("embed", "kv_heads", None), dtype),
        "wv": L.ParamDef((d, K, hd), ("embed", "kv_heads", None), dtype),
        "wo": L.ParamDef((H, hd, d), ("heads", None, "embed"), dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = L.ParamDef((hd,), (None,), dtype, init="ones")
        p["k_norm"] = L.ParamDef((hd,), (None,), dtype, init="ones")
    return p


def attn_block_def(cfg: ModelConfig, dtype, window_attn: bool = False) -> Dict:
    return {
        "ln1": L.rmsnorm_def(cfg.d_model, dtype),
        "attn": attn_def(cfg, dtype),
        "ln2": L.rmsnorm_def(cfg.d_model, dtype),
        "mlp": L.mlp_def(cfg.d_model, cfg.d_ff, cfg.mlp_act, dtype),
    }


# ---------------------------------------------------------------------------
# Projections
# ---------------------------------------------------------------------------

def _qkv(cfg: ModelConfig, p: Dict, x: jax.Array, positions: jax.Array):
    with jax.named_scope("attn_in"):
        dt = x.dtype
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
        k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(dt))
        v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(dt))
        if cfg.qk_norm:
            q = L.rmsnorm_heads(p["q_norm"], q, cfg.norm_eps)
            k = L.rmsnorm_heads(p["k_norm"], k, cfg.norm_eps)
        q = L.rope(q, positions, cfg.rope_theta)
        k = L.rope(k, positions, cfg.rope_theta)
        return q, k, v


# ---------------------------------------------------------------------------
# Full-sequence attention (training / prefill)
# ---------------------------------------------------------------------------

def _pick_chunk(S: int, target: int) -> int:
    """Largest divisor of S that is <= target (bounded search)."""
    c = min(S, target)
    for cand in range(c, 0, -1):
        if S % cand == 0:
            return cand
        if c - cand > 4096:
            break
    return S


def _flash_attention(cfg: ModelConfig, q, k, v, positions, window,
                     lengths, prefix_len, dt) -> jax.Array:
    """Chunked online-softmax attention (XLA flash): bounded working set.

    q: [B,S,K,G,hd]; k/v: [B,S,K,hd].  Sliding windows use a *banded* kv
    range per query chunk (static band width, dynamic offset), so windowed
    prefill does O(S * window) work rather than O(S^2).
    """
    B, S, K, G, hd = q.shape
    cq = _pick_chunk(S, ATTN_CHUNK)
    ck = cq
    nq = S // cq
    scale = hd ** -0.5
    pos = positions  # [Bp, S]
    Bp = pos.shape[0]

    if window is not None:
        band = -(-(window + cq - 1) // ck) * ck
        band = min(band, S)
        n_inner = band // ck
    else:
        n_inner = S // ck

    def one_q_chunk(_, qi):
        qc = jax.lax.dynamic_slice_in_dim(q, qi * cq, cq, axis=1)
        qp = jax.lax.dynamic_slice_in_dim(pos, qi * cq, cq, axis=1)  # [Bp,cq]
        if window is not None:
            kv0 = jnp.clip(qi * cq + cq - band, 0, S - band)
        else:
            kv0 = 0

        def inner(carry, j):
            m, l, acc = carry
            off = kv0 + j * ck
            kc = jax.lax.dynamic_slice_in_dim(k, off, ck, axis=1)
            vc = jax.lax.dynamic_slice_in_dim(v, off, ck, axis=1)
            kp = jax.lax.dynamic_slice_in_dim(pos, off, ck, axis=1)  # [Bp,ck]
            s = jnp.einsum("bqkgd,btkd->bkgqt", qc, kc).astype(jnp.float32)
            s = s * scale
            mask = kp[:, None, :] <= qp[:, :, None]                  # [Bp,cq,ck]
            if prefix_len:
                mask = mask | ((qp[:, :, None] < prefix_len)
                               & (kp[:, None, :] < prefix_len))
            if window is not None:
                mask = mask & (kp[:, None, :] > qp[:, :, None] - window)
            if lengths is not None:
                mask = mask & (kp[:, None, :] < lengths[:, None, None])
            s = jnp.where(mask[:, None, None, :, :], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p_ = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = l * corr + jnp.sum(p_, axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bkgqt,btkd->bkgqd", p_.astype(dt), vc).astype(jnp.float32)
            return (m_new, l, acc), None

        m0 = jnp.full((B, K, G, cq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, K, G, cq), jnp.float32)
        a0 = jnp.zeros((B, K, G, cq, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(inner, (m0, l0, a0),
                                      jnp.arange(n_inner))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return None, out.astype(dt)                                  # [B,K,G,cq,hd]

    _, outs = jax.lax.scan(one_q_chunk, None, jnp.arange(nq))
    # [nq,B,K,G,cq,hd] -> [B, nq*cq, K, G, hd]
    out = jnp.moveaxis(outs, 0, 3).reshape(B, K, G, S, hd)
    return jnp.transpose(out, (0, 3, 1, 2, 4))


def _flash_attention_causal_skip(cfg: ModelConfig, q, k, v, positions,
                                 lengths, prefix_len, dt) -> jax.Array:
    """Causal flash over ONLY the lower-triangular block pairs.

    One scan over nq(nq+1)/2 (qi, kj) pairs in row-major order; m/l/acc
    reset at each row start, the finished row is written into the output
    carry at row end.  Work = (nq+1)/(2*nq) of the masked-full grid.
    """
    import numpy as np

    B, S, K, G, hd = q.shape
    cq = _pick_chunk(S, ATTN_CHUNK)
    ck = cq
    nq = S // cq
    scale = hd ** -0.5
    pos = positions
    pairs = [(qi, kj) for qi in range(nq) for kj in range(qi + 1)]
    qi_arr = jnp.asarray(np.array([p_[0] for p_ in pairs], np.int32))
    kj_arr = jnp.asarray(np.array([p_[1] for p_ in pairs], np.int32))
    row_start = jnp.asarray(np.array([p_[1] == 0 for p_ in pairs], np.bool_))
    row_end = jnp.asarray(np.array([p_[0] == p_[1] for p_ in pairs], np.bool_))

    def body(carry, xs):
        m, l, acc, out = carry
        qi, kj, rs, re = xs
        m = jnp.where(rs, jnp.full_like(m, NEG_INF), m)
        l = jnp.where(rs, jnp.zeros_like(l), l)
        acc = jnp.where(rs, jnp.zeros_like(acc), acc)
        qc = jax.lax.dynamic_slice_in_dim(q, qi * cq, cq, axis=1)
        qp = jax.lax.dynamic_slice_in_dim(pos, qi * cq, cq, axis=1)
        kc = jax.lax.dynamic_slice_in_dim(k, kj * ck, ck, axis=1)
        vc = jax.lax.dynamic_slice_in_dim(v, kj * ck, ck, axis=1)
        kp = jax.lax.dynamic_slice_in_dim(pos, kj * ck, ck, axis=1)
        s = jnp.einsum("bqkgd,btkd->bkgqt", qc, kc).astype(jnp.float32) * scale
        mask = kp[:, None, :] <= qp[:, :, None]
        if prefix_len:
            mask = mask | ((qp[:, :, None] < prefix_len)
                           & (kp[:, None, :] < prefix_len))
        if lengths is not None:
            mask = mask & (kp[:, None, :] < lengths[:, None, None])
        s = jnp.where(mask[:, None, None, :, :], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p_ = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p_, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bkgqt,btkd->bkgqd", p_.astype(dt), vc).astype(jnp.float32)
        res = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(dt)
        out = jax.lax.cond(
            re,
            lambda o: jax.lax.dynamic_update_slice_in_dim(
                o, res[None], qi, axis=0),
            lambda o: o, out)
        return (m_new, l, acc, out), None

    m0 = jnp.full((B, K, G, cq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, K, G, cq), jnp.float32)
    a0 = jnp.zeros((B, K, G, cq, hd), jnp.float32)
    o0 = jnp.zeros((nq, B, K, G, cq, hd), dt)
    (_, _, _, out), _ = jax.lax.scan(
        body, (m0, l0, a0, o0), (qi_arr, kj_arr, row_start, row_end))
    out = jnp.moveaxis(out, 0, 3).reshape(B, K, G, S, hd)
    return jnp.transpose(out, (0, 3, 1, 2, 4))


def attention_full_qkv(cfg: ModelConfig, p: Dict, q, k, v,
                       positions: jax.Array, window: Optional[int],
                       lengths: Optional[jax.Array] = None,
                       prefix_len: int = 0,
                       out_dtype=None) -> jax.Array:
    """Causal (optionally sliding-window) attention given projected q/k/v.

    ``prefix_len`` marks a bidirectional prefix (VLM image patches attend
    among themselves); tokens after the prefix remain causal.  Sequences
    longer than ATTN_CHUNK take the chunked flash path (bounded memory);
    pure-causal flash additionally skips above-diagonal blocks when
    CAUSAL_SKIP is on (§Perf hillclimb #1).
    """
    B, S = q.shape[0], q.shape[1]
    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    G = H // K
    dt = out_dtype or q.dtype
    q = q.reshape(B, S, K, G, hd)
    if S > ATTN_CHUNK:
        pos2 = positions if positions.ndim == 2 else positions[None, :]
        cq = _pick_chunk(S, ATTN_CHUNK)
        if CAUSAL_SKIP and window is None and prefix_len <= cq:
            ctx = _flash_attention_causal_skip(cfg, q, k, v, pos2, lengths,
                                               prefix_len, dt)
        else:
            ctx = _flash_attention(cfg, q, k, v, pos2, window, lengths,
                                   prefix_len, dt)
        out = ctx.reshape(B, S, H, hd)
        return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dt))

    scale = hd ** -0.5
    scores = jnp.einsum("bskgd,btkd->bkgst", q, k).astype(jnp.float32) * scale

    i = positions[:, :, None] if positions.ndim == 2 else positions[None, :, None]
    j = positions[:, None, :] if positions.ndim == 2 else positions[None, None, :]
    mask = j <= i                                     # causal
    if prefix_len:
        both_prefix = (i < prefix_len) & (j < prefix_len)
        mask = mask | both_prefix                     # bidirectional prefix
    if window is not None:
        mask = mask & (j > i - window)
    if lengths is not None:
        mask = mask & (j < lengths[:, None, None])
    scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
    prob = jax.nn.softmax(scores, axis=-1).astype(dt)
    out = jnp.einsum("bkgst,btkd->bskgd", prob, v).reshape(B, S, H, hd)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dt))


def attention_full(cfg: ModelConfig, p: Dict, x: jax.Array,
                   positions: jax.Array, window: Optional[int],
                   lengths: Optional[jax.Array] = None,
                   prefix_len: int = 0) -> jax.Array:
    q, k, v = _qkv(cfg, p, x, positions)
    return attention_full_qkv(cfg, p, q, k, v, positions, window,
                              lengths, prefix_len, out_dtype=x.dtype)


# ---------------------------------------------------------------------------
# KV cache
# ---------------------------------------------------------------------------

def resolve_kv_dtype(cfg: ModelConfig, kv_dtype: Optional[str]) -> str:
    kvd = kv_dtype or cfg.kv_dtype
    assert kvd in ("model", "int8"), f"unknown kv_dtype {kvd!r}"
    return kvd


def kv_cache_def(cfg: ModelConfig, batch: int, capacity: int, dtype,
                 seq_shard: bool = True,
                 kv_dtype: Optional[str] = None) -> Dict:
    """ShapeDtypeStruct-compatible cache spec for one attention layer.

    The capacity dim carries the ``kv_seq`` logical axis: GQA kv_heads
    (typically 8) cannot divide a 16-way model axis, so the cache is
    sharded along *sequence* instead (flash-decoding layout; partial
    softmax combines become collectives).  For batch-1 long-context
    decode the same axis picks up the (pod, data) axes too.

    ``kv_dtype`` (None = ModelConfig.kv_dtype): "int8" stores K/V
    quantized with per-slot-per-head float32 scale sidecars ``ks``/``kz``
    (asymmetric K) and ``vs`` (symmetric V) — kernels/kv_quant.py.
    """
    K, hd = cfg.num_kv_heads, cfg.head_dim
    seq_ax = "kv_seq" if seq_shard else None
    kv_axes = ("batch", seq_ax, "kv_heads", None)
    d = {
        "k": L.ParamDef((batch, capacity, K, hd), kv_axes, dtype, init="zeros"),
        "v": L.ParamDef((batch, capacity, K, hd), kv_axes, dtype, init="zeros"),
        "tok": L.ParamDef((batch, capacity), ("batch", seq_ax), jnp.int32, init="zeros"),
    }
    if resolve_kv_dtype(cfg, kv_dtype) == "int8":
        for leaf in ("k", "v"):
            d[leaf] = L.ParamDef(d[leaf].shape, kv_axes, jnp.int8, init="zeros")
        sc_axes = ("batch", seq_ax, "kv_heads")
        for leaf in ("ks", "kz", "vs"):
            d[leaf] = L.ParamDef((batch, capacity, K), sc_axes, jnp.float32,
                                 init="zeros")
    return d


def init_kv_cache(cfg: ModelConfig, batch: int, capacity: int, dtype,
                  kv_dtype: Optional[str] = None) -> Dict:
    return L.init_empty_cache(
        kv_cache_def(cfg, batch, capacity, dtype, kv_dtype=kv_dtype))


def prefill_into_cache(cache: Dict, k: jax.Array, v: jax.Array,
                       lengths: jax.Array) -> Dict:
    """Write a full prefill's K/V into the (ring) cache.

    Tokens with index >= length are left unwritten (tok=-1).  When S exceeds
    capacity, only the last ``capacity`` tokens of each sequence survive —
    exactly the sliding-window semantics.
    """
    B, S = k.shape[0], k.shape[1]
    C = cache["k"].shape[1]
    t = jnp.arange(S)[None, :]                                    # [1,S]
    valid = t < lengths[:, None]
    # Keep only tokens in the final window [length-C, length).
    keep = valid & (t >= lengths[:, None] - C)
    slot = t % C
    b = jnp.broadcast_to(jnp.arange(B)[:, None], (B, S))
    slot_b = jnp.broadcast_to(slot, (B, S))
    # Route dropped tokens to a scratch slot (C) and slice it off.
    slot_safe = jnp.where(keep, slot_b, C)
    if "ks" in cache:                                   # quantized cache
        k, ks, kz = Q.quantize_k(k)
        v, vs = Q.quantize_v(v)
    k_new = jnp.zeros_like(cache["k"], shape=(B, C + 1) + cache["k"].shape[2:])
    v_new = jnp.zeros_like(k_new)
    tok_new = jnp.full((B, C + 1), -1, jnp.int32)
    k_new = k_new.at[b, slot_safe].set(k.astype(cache["k"].dtype))
    v_new = v_new.at[b, slot_safe].set(v.astype(cache["v"].dtype))
    tok_new = tok_new.at[b, slot_safe].set(jnp.where(keep, t, -1))
    out = {"k": k_new[:, :C], "v": v_new[:, :C], "tok": tok_new[:, :C]}
    if "ks" in cache:
        Ksc = cache["ks"].shape[2]
        for name, val in (("ks", ks), ("kz", kz), ("vs", vs)):
            s_new = jnp.zeros((B, C + 1, Ksc), jnp.float32)
            out[name] = s_new.at[b, slot_safe].set(val)[:, :C]
    return out


# --- factored dequant (int8 read path) -------------------------------------
#
# Dequantizing K before QK^T costs an O(T*hd) multiply-add per head; but
#   q . ((kq + 128) * ks_t + kz_t)  ==  ks_t * (q . kq) + (128*ks_t + kz_t) * sum(q)
# so the scales can be folded into the [.., T] score matrix AFTER the int8
# matmul — hd/G times fewer elementwise ops (the XLA mirror of the
# kernels' in-register dequant).  Same for V: fold vs_t into the softmax
# weights instead of dequantizing the [T, hd] tile.  Ring and paged reads
# share these helpers, so both layouts produce bit-identical scores for
# identical cached values.


def _quant_scores(q4: jax.Array, kq: jax.Array, ks: jax.Array,
                  kz: jax.Array) -> jax.Array:
    """q4: [B,K,G,hd]; kq: [B,T,K,hd] int8; ks/kz: [B,T,K].
    Returns f32 scores [B,K,G,T] == q4 . dequant(kq)^T (unscaled)."""
    s0 = jnp.einsum("bkgd,btkd->bkgt", q4,
                    kq.astype(q4.dtype)).astype(jnp.float32)
    qs = jnp.sum(q4.astype(jnp.float32), axis=-1)            # [B,K,G]
    ksT = jnp.moveaxis(ks, 1, 2)[:, :, None, :]              # [B,K,1,T]
    kzT = jnp.moveaxis(kz, 1, 2)[:, :, None, :]
    return s0 * ksT + qs[..., None] * (128.0 * ksT + kzT)


def _quant_scores_ext(q5: jax.Array, kq: jax.Array, ks: jax.Array,
                      kz: jax.Array) -> jax.Array:
    """q5: [B,S,K,G,hd]; kq: [B,T,K,hd] int8.  f32 [B,K,G,S,T]."""
    s0 = jnp.einsum("bskgd,btkd->bkgst", q5,
                    kq.astype(q5.dtype)).astype(jnp.float32)
    qs = jnp.transpose(jnp.sum(q5.astype(jnp.float32), axis=-1),
                       (0, 2, 3, 1))                         # [B,K,G,S]
    ksT = jnp.moveaxis(ks, 1, 2)[:, :, None, None, :]        # [B,K,1,1,T]
    kzT = jnp.moveaxis(kz, 1, 2)[:, :, None, None, :]
    return s0 * ksT + qs[..., None] * (128.0 * ksT + kzT)


def _quant_pv(prob: jax.Array, vq: jax.Array, vs: jax.Array) -> jax.Array:
    """prob: [B,K,G,T]; vq: [B,T,K,hd] int8; vs: [B,T,K] -> [B,K,G,hd]."""
    probv = prob * jnp.moveaxis(vs, 1, 2)[:, :, None, :].astype(prob.dtype)
    return jnp.einsum("bkgt,btkd->bkgd", probv, vq.astype(prob.dtype))


def _quant_pv_ext(prob: jax.Array, vq: jax.Array, vs: jax.Array) -> jax.Array:
    """prob: [B,K,G,S,T]; vq: [B,T,K,hd] int8 -> [B,S,K,G,hd]."""
    probv = prob * jnp.moveaxis(vs, 1, 2)[:, :, None, None, :].astype(
        prob.dtype)
    return jnp.einsum("bkgst,btkd->bskgd", probv, vq.astype(prob.dtype))


def attention_decode(cfg: ModelConfig, p: Dict, x: jax.Array, cache: Dict,
                     pos: jax.Array, window: Optional[int]) -> Tuple[jax.Array, Dict]:
    """One-token decode.  x: [B, 1, d]; pos: [B] absolute positions."""
    B = x.shape[0]
    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    G = H // K
    C = cache["k"].shape[1]
    q, k, v = _qkv(cfg, p, x, pos[:, None])
    # Write the current token into the ring via a masked select rather
    # than a scatter: scatters onto the kv_seq-SHARDED capacity dim force
    # GSPMD to all-gather the cache per layer (§Perf hillclimb #3 — this
    # select is elementwise, so every shard updates locally).
    with jax.named_scope("kv_write"):
        slot = pos % C
        hit = jnp.arange(C)[None, :] == slot[:, None]          # [B, C]
        if "ks" in cache:                               # quantized ring
            kq, ks, kz = Q.quantize_k(k[:, 0:1])
            vq, vs = Q.quantize_v(v[:, 0:1])
            cache = {
                "k": jnp.where(hit[:, :, None, None], kq, cache["k"]),
                "v": jnp.where(hit[:, :, None, None], vq, cache["v"]),
                "ks": jnp.where(hit[:, :, None], ks, cache["ks"]),
                "kz": jnp.where(hit[:, :, None], kz, cache["kz"]),
                "vs": jnp.where(hit[:, :, None], vs, cache["vs"]),
                "tok": jnp.where(hit, pos[:, None], cache["tok"]),
            }
        else:
            cache = {
                "k": jnp.where(hit[:, :, None, None],
                               k[:, 0:1].astype(cache["k"].dtype),
                               cache["k"]),
                "v": jnp.where(hit[:, :, None, None],
                               v[:, 0:1].astype(cache["v"].dtype),
                               cache["v"]),
                "tok": jnp.where(hit, pos[:, None], cache["tok"]),
            }
    q = q.reshape(B, K, G, hd)
    with jax.named_scope("attn_kernel"):
        scale = hd ** -0.5
        quant = "ks" in cache
        if quant:
            scores = _quant_scores(q, cache["k"], cache["ks"],
                                   cache["kz"]) * scale
        else:
            scores = jnp.einsum("bkgd,btkd->bkgt", q,
                                cache["k"].astype(x.dtype)) * scale
            scores = scores.astype(jnp.float32)
        tok = cache["tok"]
        valid = (tok >= 0) & (tok <= pos[:, None])
        if window is not None:
            valid = valid & (tok > pos[:, None] - window)
        scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
        prob = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
        if quant:
            out = _quant_pv(prob, cache["v"], cache["vs"])
        else:
            out = jnp.einsum("bkgt,btkd->bkgd", prob,
                             cache["v"].astype(x.dtype))
        out = out.reshape(B, 1, H, hd)
    return _attn_out(p, out, x.dtype), cache


def _attn_out(p: Dict, out: jax.Array, dt) -> jax.Array:
    """The output projection of attention heads [B, S, H, hd] -> [B, S, d]."""
    with jax.named_scope("attn_out"):
        return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dt))


# ---------------------------------------------------------------------------
# Paged KV cache (vLLM-style shared page pool; docs/SERVING.md)
# ---------------------------------------------------------------------------
#
# Layout per attention layer: ``{"kp": [P, K, ps, hd], "vp": [P, K, ps, hd]}``
# — a POOL of P physical pages of ps tokens each, shared by every request.
# Pages are HEAD-MAJOR so the Pallas kernel's page block ``(1, K, ps, hd)``
# spans its last dims whole, which the TPU's (8, 128) tiling requires of
# a block (kernels/paged_extend.py).
# The functions below take the pool STACKED, ``[L, P, K, ps, hd]``, and a
# ``layer`` index: the layer scan carries every scanned layer's pool
# whole and each layer writes and reads its own in place, so no step
# slices out, relayouts or writes back a layer's pool
# (docs/SERVING.md#the-pool-through-the-layer-scan).  An unscanned
# layer's pool is the stack ``pool[None]`` at layer 0.
# With ``kv_dtype="int8"`` the pools are int8 and carry float32 scale
# sidecar pools ``ksp``/``kzp``/``vsp`` ([P, K, ps]; asymmetric K,
# symmetric V — kernels/kv_quant.py): quantized at write time in
# ``_paged_write``, dequantized at read via the factored scale-fold
# (``_quant_scores*`` / ``_quant_pv*``) or in-register in the Pallas
# kernels.  The sidecars share the ``pages`` logical axis, so COW,
# snapshot pins and nbytes accounting move scales with their pages.
# There is no batch axis and no ``tok`` slot-index array: each request owns
# a page table [NP] mapping logical page (position // ps) to a physical
# page (-1 = unmapped), so a token's absolute position is explicit from its
# (logical page, offset) coordinates and masking is pure position
# arithmetic.  Writes go in place into uniquely-owned pages (the serving
# engine's copy-on-write invariant); reads gather the request's pages into
# a dense logical view (XLA path) or walk the table page-by-page
# (kernels/paged_extend.py).


def paged_kv_cache_def(cfg: ModelConfig, num_pages: int, page_size: int,
                       dtype, kv_dtype: Optional[str] = None) -> Dict:
    """ShapeDtypeStruct-compatible page-pool spec for one attention layer.

    The leading ``pages`` logical axis is how the serving engine recognises
    pool leaves (no ``batch`` axis => shared across requests, snapshotted
    by page reference instead of by value).

    ``kv_dtype`` (None = ModelConfig.kv_dtype): "int8" stores the pools
    quantized, with float32 scale SIDECAR pools ``ksp``/``kzp``
    (asymmetric K) and ``vsp`` (symmetric V) of shape
    ``[num_pages, K, page_size]``.  The sidecars carry the same ``pages``
    axis as the payload, so every pages-axis mechanism — COW page
    copies, snapshot pins, per-page nbytes accounting — moves scales
    with their pages without special cases.
    """
    K, hd = cfg.num_kv_heads, cfg.head_dim
    pool_dtype = (jnp.int8 if resolve_kv_dtype(cfg, kv_dtype) == "int8"
                  else dtype)
    d = {
        "kp": L.ParamDef((num_pages, K, page_size, hd),
                         ("pages", "kv_heads", None, None), pool_dtype,
                         init="zeros"),
        "vp": L.ParamDef((num_pages, K, page_size, hd),
                         ("pages", "kv_heads", None, None), pool_dtype,
                         init="zeros"),
    }
    if pool_dtype == jnp.int8:
        for leaf in ("ksp", "kzp", "vsp"):
            d[leaf] = L.ParamDef((num_pages, K, page_size),
                                 ("pages", "kv_heads", None), jnp.float32,
                                 init="zeros")
    return d


def _gather_pages(pool_leaf: jax.Array, layer, page_table: jax.Array
                  ) -> jax.Array:
    """Stacked head-major [L, P, K, ps, ...] pool + layer + [B, NP] table
    -> that layer's dense logical [B, NP*ps, K, ...], gathered straight
    from the stack (no layer slice first).

    Unmapped entries (-1) gather page 0; callers mask them by position.
    """
    idx = jnp.maximum(page_table, 0)
    g = jnp.moveaxis(pool_leaf[layer, idx], 3, 2)       # [B, NP, ps, K, ...]
    B, NP, ps = g.shape[0], g.shape[1], g.shape[2]
    out = g.reshape(B, NP * ps, *g.shape[3:])
    # Mesh serving: the pool is sharded by physical page along 'model',
    # so this gather is an all-to-all.  Pin the densified result to the
    # attention compute layout — batch over 'data', kv_heads over
    # 'model' — instead of letting GSPMD keep it page-sharded, where
    # every einsum against head-sharded q would re-shuffle it per layer.
    # No-op without an active mesh (launch/rules.shard_activation).
    from repro.launch.rules import shard_activation
    axes = ("batch", None, "kv_heads") + (None,) * (out.ndim - 3)
    return shard_activation(out, axes)


def _paged_write(pool: Dict, layer, k: jax.Array, v: jax.Array,
                 phys: jax.Array, off: jax.Array) -> Dict:
    """Write K/V lanes ([..., K, hd]) into the head-major pages of the
    stacked pool's ``layer``, in place.  phys/off: [B] or [B, Sx]; a lane
    with phys >= P is dropped — the route for pad lanes and unmapped
    positions.  Quantized pools (``ksp`` present) quantize HERE, at write
    time: the scales land at the same (page, offset) as their int8 rows.

    A scatter costs about the same per lane whether the lane is dropped
    or not, and a mixed step's [B, Sx] block is mostly pad lanes (its
    decode rows hold one valid lane each).  So the kept lanes are moved
    to the front and scattered ``C = max(B, Sx)`` at a time — a decode
    batch, or one full row — in a loop over the chunks they fill; a
    block of at most C lanes (decode) is one chunk.

    For K/V, layer, page, kv head and slot are all index dims, so each
    update window is one ``hd`` row.  A ``[K, hd]`` window
    (``.at[layer, phys, :, off]``) computes the same values, but TPU
    layout assignment then relayouts the carried pool token-major and
    copies it whole at the layer loop's entry and exit.  The scale
    sidecars take the ``[K]`` window: with scalar windows the compiler
    flattens them to 1-D, a relayout of the whole sidecar.  On a mesh the
    pool is sharded by page and the scatter partitions by page.

    VERIFY-WRITE-THEN-TRUNCATE (speculative decoding): the engine's
    verify step writes drafted tokens here BEFORE knowing whether they
    are accepted.  Rejection needs no device-side undo because (a) every
    read path masks by absolute position (``t <= pos``), so positions
    past the committed frontier are never attended, and (b) the engine
    always re-writes positions from the committed frontier forward at
    the start of the next step — the write comes before the read within
    a step — so a rejected position is overwritten before any query
    position could reach it.  Distinct positions map to distinct
    (page, offset) slots (no ring aliasing), which is why paged engines
    can speculate for every attention/MoE arch; the host merely truncates
    page-table tails (serving/page_pool.py::truncate_tail)."""
    P = pool["kp"].shape[1]
    C = max(phys.shape)
    n = phys.size
    phys, off = phys.reshape(n), off.reshape(n)
    k, v = k.reshape(n, *k.shape[-2:]), v.reshape(n, *v.shape[-2:])
    if "ksp" in pool:
        kq, ks, kz = Q.quantize_k(k)
        vq, vs = Q.quantize_v(v)
        vals = {"kp": kq, "vp": vq, "ksp": ks, "kzp": kz, "vsp": vs}
    else:
        vals = {"kp": k, "vp": v}
    heads = jnp.arange(k.shape[-2])

    def scatter(pool, vals, phys, off):
        out = {}
        for name, val in vals.items():
            if name in ("kp", "vp"):    # [n, 1] indices, broadcast to K
                at = (layer, phys[:, None], heads, off[:, None])
            else:                       # [K] scales at one (page, slot)
                at = (layer, phys, slice(None), off)
            out[name] = pool[name].at[at].set(val.astype(pool[name].dtype),
                                              mode="drop")
        return out

    if n <= C:
        return scatter(pool, vals, phys, off)
    keep = phys < P
    pad = -n % C                        # the last chunk's tail: dropped
    order = jnp.argsort(~keep, stable=True)          # kept lanes first

    def front(a, fill):
        return jnp.pad(a[order], [(0, pad)] + [(0, 0)] * (a.ndim - 1),
                       constant_values=fill)

    vals = {name: front(val, 0) for name, val in vals.items()}
    phys, off = front(phys, P), front(off, 0)

    def chunk(i, pool):
        def rows(a):
            return jax.lax.dynamic_slice_in_dim(a, i * C, C)
        return scatter(pool, {name: rows(val) for name, val in vals.items()},
                       rows(phys), rows(off))

    n_chunks = (jnp.sum(keep, dtype=jnp.int32) + C - 1) // C
    return jax.lax.fori_loop(0, n_chunks, chunk, pool)



def attention_decode_paged(cfg: ModelConfig, p: Dict, x: jax.Array,
                           pool: Dict, layer, pos: jax.Array,
                           page_table: jax.Array, window: Optional[int],
                           impl: Optional[str] = None
                           ) -> Tuple[jax.Array, Dict]:
    """One-token decode over the page pool.  x: [B,1,d]; pool: stacked
    leaves [L, P, K, ps, ...] of which this is ``layer``; pos: [B];
    page_table: [B, NP] int32.  ``impl="pallas"`` reads the pool with the
    page-table-walking kernel (kernels/paged_extend.py) instead of the
    XLA ``_gather_pages`` densify; the write scatter stays XLA either
    way, and the kernel reads the post-write pool."""
    B = x.shape[0]
    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    G = H // K
    P, ps = pool["kp"].shape[1], pool["kp"].shape[3]
    NP = page_table.shape[1]
    q, k, v = _qkv(cfg, p, x, pos[:, None])
    with jax.named_scope("kv_write"):
        lpage = jnp.clip(pos // ps, 0, NP - 1)
        phys = jnp.take_along_axis(page_table, lpage[:, None], axis=1)[:, 0]
        phys = jnp.where(phys >= 0, phys, P)            # unmapped -> dropped
        pool = _paged_write(pool, layer, k[:, 0], v[:, 0], phys, pos % ps)

    q = q.reshape(B, K, G, hd)
    with jax.named_scope("attn_kernel"):
        if impl == "pallas":
            out = ops.paged_decode_attention(
                q, pool["kp"], pool["vp"], page_table, pos, layer,
                k_scale=pool.get("ksp"), k_zero=pool.get("kzp"),
                v_scale=pool.get("vsp"), window=window)
            out = out.reshape(B, 1, H, hd).astype(x.dtype)
        else:
            out = _decode_read_xla(q, pool, layer, page_table, pos, window,
                                   x.dtype).reshape(B, 1, H, hd)
    return _attn_out(p, out, x.dtype), pool


def _decode_read_xla(q, pool: Dict, layer, page_table: jax.Array,
                     pos: jax.Array, window: Optional[int], dt) -> jax.Array:
    """The XLA read of one-token decode over the pool's ``layer``:
    ``_gather_pages`` densify, masked softmax, weighted V.
    q: [B, K, G, hd]."""
    hd, ps = q.shape[-1], pool["kp"].shape[3]
    NP = page_table.shape[1]
    scale = hd ** -0.5
    quant = "ksp" in pool

    def gather(name):
        return _gather_pages(pool[name], layer, page_table)

    if quant:
        kg = gather("kp")                                       # [B,T,K,hd]
        scores = _quant_scores(q, kg, gather("ksp"), gather("kzp")) * scale
    else:
        kg = gather("kp").astype(dt)
        scores = jnp.einsum("bkgd,btkd->bkgt", q, kg) * scale
        scores = scores.astype(jnp.float32)
    t = jnp.arange(NP * ps)[None, :]
    valid = jnp.repeat(page_table >= 0, ps, axis=1) & (t <= pos[:, None])
    if window is not None:
        valid = valid & (t > pos[:, None] - window)
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    prob = jax.nn.softmax(scores, axis=-1).astype(dt)
    if quant:
        return _quant_pv(prob, gather("vp"), gather("vsp"))
    vg = gather("vp").astype(dt)
    return jnp.einsum("bkgt,btkd->bkgd", prob, vg)


def attention_extend_paged(cfg: ModelConfig, p: Dict, x: jax.Array,
                           pool: Dict, layer, pos0: jax.Array,
                           window: Optional[int], page_table: jax.Array,
                           valid: Optional[jax.Array] = None,
                           impl: Optional[str] = None
                           ) -> Tuple[jax.Array, Dict]:
    """Multi-token extension over the page pool (stacked leaves
    [L, P, K, ps, ...], of which this is ``layer``): x: [B, Sx, d]
    continues at position pos0 [B]; the engine has already mapped (and
    COW-resolved) every logical page the valid lanes touch.  Lane l
    writes page table[(pos0+l)//ps] offset (pos0+l)%ps; invalid lanes
    never reach the pool.  There is no ring aliasing: distinct positions
    always land in distinct (page, offset) slots, so — unlike the dense
    ring path — no lane-deduplication or capacity clamp is needed.

    ``impl="pallas"`` reads the post-write pool with the fused paged
    attention kernel (kernels/paged_extend.py): each mapped page is
    DMA'd once for all Sx lanes instead of densifying the whole pool via
    ``_gather_pages``.  Invalid lanes compute unused rows on both paths;
    the write scatter stays XLA on both paths."""
    B, Sx, _ = x.shape
    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    G = H // K
    P, ps = pool["kp"].shape[1], pool["kp"].shape[3]
    NP = page_table.shape[1]
    positions = pos0[:, None] + jnp.arange(Sx)[None, :]          # [B,Sx]
    q, k, v = _qkv(cfg, p, x, positions)
    with jax.named_scope("kv_write"):
        lpage = jnp.clip(positions // ps, 0, NP - 1)
        phys = jnp.take_along_axis(page_table, lpage, axis=1)    # [B,Sx]
        keep = phys >= 0
        if valid is not None:
            keep = keep & valid
        phys = jnp.where(keep, phys, P)                          # drop pads
        pool = _paged_write(pool, layer, k, v, phys, positions % ps)

    q = q.reshape(B, Sx, K, G, hd)
    with jax.named_scope("attn_kernel"):
        if impl == "pallas":
            out = ops.paged_extend_attention(
                q, pool["kp"], pool["vp"], page_table, pos0, layer,
                k_scale=pool.get("ksp"), k_zero=pool.get("kzp"),
                v_scale=pool.get("vsp"), window=window)
            out = out.reshape(B, Sx, H, hd).astype(x.dtype)
        else:
            out = _extend_read_xla(q, pool, layer, page_table, positions,
                                   window, x.dtype).reshape(B, Sx, H, hd)
    return _attn_out(p, out, x.dtype), pool


def _extend_read_xla(q, pool: Dict, layer, page_table: jax.Array,
                     positions: jax.Array, window: Optional[int],
                     dt) -> jax.Array:
    """The XLA read of a multi-token extension over the pool's ``layer``:
    ``_gather_pages`` densify, masked softmax, weighted V.
    q: [B, Sx, K, G, hd]; positions: [B, Sx]."""
    hd, ps = q.shape[-1], pool["kp"].shape[3]
    NP = page_table.shape[1]
    scale = hd ** -0.5
    quant = "ksp" in pool

    def gather(name):
        return _gather_pages(pool[name], layer, page_table)

    if quant:
        kg = gather("kp")                                        # [B,T,K,hd]
        scores = _quant_scores_ext(q, kg, gather("ksp"),
                                   gather("kzp")) * scale
    else:
        kg = gather("kp").astype(dt)
        scores = jnp.einsum("bskgd,btkd->bkgst", q, kg) * scale
        scores = scores.astype(jnp.float32)
    t = jnp.arange(NP * ps)[None, None, :]
    attendable = (jnp.repeat(page_table >= 0, ps, axis=1)[:, None, :]
                  & (t <= positions[:, :, None]))
    if window is not None:
        attendable = attendable & (t > positions[:, :, None] - window)
    scores = jnp.where(attendable[:, None, None, :, :], scores, NEG_INF)
    prob = jax.nn.softmax(scores, axis=-1).astype(dt)
    if quant:
        return _quant_pv_ext(prob, gather("vp"), gather("vsp"))
    vg = gather("vp").astype(dt)
    return jnp.einsum("bkgst,btkd->bskgd", prob, vg)


# ---------------------------------------------------------------------------
# Residual blocks (attn mixer + MLP)
# ---------------------------------------------------------------------------

def block_window(cfg: ModelConfig, kind: str) -> Optional[int]:
    if kind == "rg_attn":
        return cfg.local_window
    return cfg.sliding_window


def attn_block_forward(cfg: ModelConfig, p: Dict, x: jax.Array,
                       positions: jax.Array, kind: str = "attn",
                       lengths: Optional[jax.Array] = None,
                       prefix_len: int = 0) -> jax.Array:
    h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
    x = x + attention_full(cfg, p["attn"], h, positions,
                           block_window(cfg, kind), lengths, prefix_len)
    h = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
    return x + L.mlp(p["mlp"], h, cfg.mlp_act)


def attn_block_prefill(cfg: ModelConfig, p: Dict, x: jax.Array,
                       positions: jax.Array, lengths: jax.Array,
                       capacity: int, kind: str = "attn",
                       prefix_len: int = 0) -> Tuple[jax.Array, Dict]:
    """Full-seq forward that also returns the primed decode cache."""
    h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
    q, k, v = _qkv(cfg, p["attn"], h, positions)
    cache = init_kv_cache(cfg, x.shape[0], capacity, x.dtype)
    cache = prefill_into_cache(cache, k, v, lengths)
    x = x + attention_full_qkv(cfg, p["attn"], q, k, v, positions,
                               block_window(cfg, kind), lengths, prefix_len,
                               out_dtype=x.dtype)
    h = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
    return x + L.mlp(p["mlp"], h, cfg.mlp_act), cache


def attn_block_decode(cfg: ModelConfig, p: Dict, x: jax.Array, cache: Dict,
                      pos: jax.Array, kind: str = "attn",
                      page_table: Optional[jax.Array] = None,
                      impl: Optional[str] = None, layer=None
                      ) -> Tuple[jax.Array, Dict]:
    """``cache``: a ring cache, or a stacked page pool read and written at
    ``layer`` (a paged pool layer: it has ``kp``)."""
    h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
    if "kp" in cache:                                   # paged pool layer
        y, cache = attention_decode_paged(cfg, p["attn"], h, cache, layer,
                                          pos, page_table,
                                          block_window(cfg, kind), impl=impl)
    else:
        y, cache = attention_decode(cfg, p["attn"], h, cache, pos,
                                    block_window(cfg, kind))
    x = x + y
    h = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
    return x + L.mlp(p["mlp"], h, cfg.mlp_act), cache


# ---------------------------------------------------------------------------
# Prefix-extension (prompt caching): prefill a SUFFIX on top of a cache
# ---------------------------------------------------------------------------

def _masked_ring_write(cache: Dict, k: jax.Array, v: jax.Array,
                       positions: jax.Array, valid: jax.Array) -> Dict:
    """Write only the valid lanes of a [B, Sx] block into the ring cache.

    Uses a one-hot select (no scatter) so the kv_seq-sharded capacity dim
    never forces GSPMD resharding, mirroring the decode-path write.  When
    Sx exceeds the ring capacity, two lanes can alias one slot; the later
    lane wins (the earlier token has already left the window).

    Speculative verify writes follow the same write-then-mask rollback
    contract as ``_paged_write``: rejected lanes leave ``tok`` entries at
    positions past the committed frontier, which every read masks
    (``tok <= pos``) and the next step overwrites.  BUT a ring slot write
    at position p EVICTS position p - C; when C is window-clamped the
    evicted token may still be attendable after a rejection rolls the
    frontier back, so the engine only enables speculation on rings whose
    capacity equals max_seq (no aliasing) — paged caches have no such
    hazard.
    """
    B, Sx = positions.shape
    C = cache["k"].shape[1]
    lane = jnp.arange(Sx)
    # last-wins de-duplication of lanes aliasing the same ring slot
    same = (positions[:, :, None] % C) == (positions[:, None, :] % C)
    later = lane[None, None, :] > lane[None, :, None]
    dup = jnp.any(same & later & valid[:, None, :], axis=-1)
    keep = valid & ~dup
    onehot = ((positions[:, :, None] % C) == jnp.arange(C)[None, None, :]) \
        & keep[:, :, None]                                          # [B,Sx,C]
    written = jnp.any(onehot, axis=1)                               # [B,C]
    if "ks" in cache:
        # quantize, then route through the one-hot in float32: |q| <= 128
        # is exactly representable, so the select stays lossless
        kq, ks, kz = Q.quantize_k(k)
        vq, vs = Q.quantize_v(v)
        ohf = onehot.astype(jnp.float32)
        out = {}
        for name, val in (("k", kq), ("v", vq)):
            sel = jnp.einsum("bsc,bskd->bckd", ohf, val.astype(jnp.float32))
            out[name] = jnp.where(written[:, :, None, None],
                                  sel.astype(jnp.int8), cache[name])
        for name, val in (("ks", ks), ("kz", kz), ("vs", vs)):
            sel = jnp.einsum("bsc,bsk->bck", ohf, val)
            out[name] = jnp.where(written[:, :, None], sel, cache[name])
        tok_new = jnp.sum(onehot.astype(jnp.int32) * positions[:, :, None],
                          axis=1)
        out["tok"] = jnp.where(written, tok_new, cache["tok"])
        return out
    oh = onehot.astype(k.dtype)
    k_new = jnp.einsum("bsc,bskd->bckd", oh, k)
    v_new = jnp.einsum("bsc,bskd->bckd", oh, v)
    tok_new = jnp.sum(onehot.astype(jnp.int32) * positions[:, :, None],
                      axis=1)
    return {
        "k": jnp.where(written[:, :, None, None],
                       k_new.astype(cache["k"].dtype), cache["k"]),
        "v": jnp.where(written[:, :, None, None],
                       v_new.astype(cache["v"].dtype), cache["v"]),
        "tok": jnp.where(written, tok_new, cache["tok"]),
    }


def attention_extend(cfg: ModelConfig, p: Dict, x: jax.Array, cache: Dict,
                     pos0: jax.Array, window: Optional[int],
                     valid: Optional[jax.Array] = None
                     ) -> Tuple[jax.Array, Dict]:
    """Multi-token extension: x: [B, Sx, d] continues at position pos0 [B].

    Writes the suffix K/V into the cache then attends over the whole cache
    (cached prefix + suffix) with exact token-index masking.  This is the
    mechanism behind reflection-round prompt caching: round r+1 re-pays
    prefill only for its suffix.

    ``valid`` ([B, Sx] bool, trailing-pad mask) marks the lanes that carry
    real tokens; invalid lanes are never written to the cache, which is
    what lets the serving engine batch rows with different chunk sizes
    (chunked prefill + decode) into one call.
    """
    B, Sx, _ = x.shape
    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    G = H // K
    C = cache["k"].shape[1]
    positions = pos0[:, None] + jnp.arange(Sx)[None, :]            # [B,Sx]
    q, k, v = _qkv(cfg, p, x, positions)
    with jax.named_scope("kv_write"):
        if valid is None:
            slots = positions % C                                   # [B,Sx]
            b = jnp.broadcast_to(jnp.arange(B)[:, None], (B, Sx))
            if "ks" in cache:
                kq, ks, kz = Q.quantize_k(k)
                vq, vs = Q.quantize_v(v)
                cache = {
                    "k": cache["k"].at[b, slots].set(kq),
                    "v": cache["v"].at[b, slots].set(vq),
                    "ks": cache["ks"].at[b, slots].set(ks),
                    "kz": cache["kz"].at[b, slots].set(kz),
                    "vs": cache["vs"].at[b, slots].set(vs),
                    "tok": cache["tok"].at[b, slots].set(positions),
                }
            else:
                cache = {
                    "k": cache["k"].at[b, slots].set(
                        k.astype(cache["k"].dtype)),
                    "v": cache["v"].at[b, slots].set(
                        v.astype(cache["v"].dtype)),
                    "tok": cache["tok"].at[b, slots].set(positions),
                }
        else:
            cache = _masked_ring_write(cache, k, v, positions, valid)
    q = q.reshape(B, Sx, K, G, hd)
    with jax.named_scope("attn_kernel"):
        scale = hd ** -0.5
        quant = "ks" in cache
        if quant:
            scores = _quant_scores_ext(q, cache["k"], cache["ks"],
                                       cache["kz"]) * scale
        else:
            scores = jnp.einsum("bskgd,btkd->bkgst", q,
                                cache["k"].astype(x.dtype)) * scale
            scores = scores.astype(jnp.float32)
        tok = cache["tok"]                                          # [B,C]
        # distinct name from the `valid` lane mask: this is the [B,Sx,C]
        # which-cache-slots-may-each-query-attend mask
        attendable = ((tok[:, None, :] >= 0)
                      & (tok[:, None, :] <= positions[:, :, None]))
        if window is not None:
            attendable = attendable & (tok[:, None, :]
                                       > positions[:, :, None] - window)
        scores = jnp.where(attendable[:, None, None, :, :], scores, NEG_INF)
        prob = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
        if quant:
            out = _quant_pv_ext(prob, cache["v"], cache["vs"])
        else:
            out = jnp.einsum("bkgst,btkd->bskgd", prob,
                             cache["v"].astype(x.dtype))
        out = out.reshape(B, Sx, H, hd)
    return _attn_out(p, out, x.dtype), cache


def attn_block_extend(cfg: ModelConfig, p: Dict, x: jax.Array, cache: Dict,
                      pos0: jax.Array, kind: str = "attn",
                      valid: Optional[jax.Array] = None,
                      page_table: Optional[jax.Array] = None,
                      impl: Optional[str] = None, layer=None
                      ) -> Tuple[jax.Array, Dict]:
    """``cache`` and ``layer`` as in :func:`attn_block_decode`."""
    h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
    if "kp" in cache:                                   # paged pool layer
        y, cache = attention_extend_paged(cfg, p["attn"], h, cache, layer,
                                          pos0, block_window(cfg, kind),
                                          page_table, valid, impl=impl)
    else:
        y, cache = attention_extend(cfg, p["attn"], h, cache, pos0,
                                    block_window(cfg, kind), valid)
    x = x + y
    h = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
    return x + L.mlp(p["mlp"], h, cfg.mlp_act), cache
