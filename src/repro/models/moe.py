"""Mixture-of-Experts FFN with sort-based capacity dispatch.

Dispatch is gather/scatter based (MegaBlocks-lite) rather than the GShard
one-hot einsum: the (tokens, experts, capacity) one-hot tensor is never
materialized, so memory is O(tokens * k * d) — the inherent top-k blow-up —
instead of O(tokens * E * C).  Experts are sharded over the ``model`` mesh
axis (expert parallelism); GSPMD inserts the all-to-all.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import attention as A


def moe_def(cfg: ModelConfig, dtype) -> Dict:
    d, ff, E = cfg.d_model, cfg.d_ff, cfg.num_experts
    p = {
        "router": L.ParamDef((d, E), ("embed", "experts"), dtype, scale=0.1),
        "wi": L.ParamDef((E, d, ff), ("experts", "embed", "ff"), dtype),
        "wo": L.ParamDef((E, ff, d), ("experts", "ff", "embed"), dtype),
    }
    if cfg.mlp_act == "swiglu":
        p["wg"] = L.ParamDef((E, d, ff), ("experts", "embed", "ff"), dtype)
    return p


def moe_block_def(cfg: ModelConfig, dtype) -> Dict:
    return {
        "ln1": L.rmsnorm_def(cfg.d_model, dtype),
        "attn": A.attn_def(cfg, dtype),
        "ln2": L.rmsnorm_def(cfg.d_model, dtype),
        "moe": moe_def(cfg, dtype),
    }


def _capacity(cfg: ModelConfig, num_tokens: int) -> int:
    c = int(num_tokens * cfg.experts_per_token * cfg.capacity_factor
            / max(cfg.num_experts, 1))
    # MXU-friendly and never zero.
    return max(8, -(-c // 8) * 8)


def moe_ffn(cfg: ModelConfig, p: Dict, x: jax.Array,
            valid: Optional[jax.Array] = None
            ) -> Tuple[jax.Array, jax.Array]:
    """x: [B, S, d] (or [T, d]).  Returns (y, aux_loss).

    ``valid`` ([B, S] bool) excludes pad lanes from dispatch entirely:
    they are routed to a past-the-end expert id so they neither consume
    expert capacity nor perturb valid tokens' outputs (chunked serving
    prefill batches rows of unequal length).

    GROUPED sort-based dispatch (GShard groups = batch rows): every sort,
    prefix-sum and scatter is per-row, so with batch sharded over 'data'
    they stay shard-local — a flat global sort forces GSPMD to replicate
    [T*k, d] arrays and all-reduce them per layer (measured 8 GB x 96 on
    granite train, §Perf bonus iteration).  Capacity is per row.
    """
    dt = x.dtype
    orig_shape = x.shape
    d = orig_shape[-1]
    # Grouping: per batch row for long sequences (keeps sorts shard-local);
    # ONE group for short rows (decode: per-row capacity floors would pad
    # E * C_min slots per token — 384x waste on kimi-k2).
    if x.ndim == 3 and x.shape[1] >= 256:
        x3 = x
        valid3 = valid
    else:
        x3 = x.reshape((1, -1, d))
        valid3 = None if valid is None else valid.reshape((1, -1))
    B, S, _ = x3.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    C = _capacity(cfg, S)

    logits = jnp.einsum("bsd,de->bse", x3,
                        p["router"].astype(dt)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                # [B, S, E]
    gate, expert_ids = jax.lax.top_k(probs, k)             # [B, S, k]
    gate = gate / jnp.sum(gate, axis=-1, keepdims=True)

    # Switch-style load-balance auxiliary loss.
    me = jnp.mean(probs, axis=(0, 1))                      # [E]
    ce = jnp.mean(jax.nn.one_hot(expert_ids[..., 0], E, dtype=jnp.float32),
                  axis=(0, 1))
    aux = E * jnp.sum(me * ce)

    # ---- grouped sort-based dispatch -----------------------------------
    Tk = S * k
    flat_e = expert_ids.reshape(B, Tk)                     # [B, S*k]
    if valid3 is not None:
        # pad lanes route to a past-the-end expert id: zero one-hot counts,
        # sorted last, dropped by the capacity test below.
        lane_valid = jnp.repeat(valid3, k, axis=1)         # [B, S*k]
        flat_e = jnp.where(lane_valid, flat_e, E)
    flat_gate = gate.reshape(B, Tk)
    order = jnp.argsort(flat_e, axis=1, stable=True)       # per-row sort
    s_expert = jnp.take_along_axis(flat_e, order, axis=1)
    s_token = order // k                                   # source token row
    s_gate = jnp.take_along_axis(flat_gate, order, axis=1)
    counts = jnp.sum(jax.nn.one_hot(flat_e, E, dtype=jnp.int32), axis=1)
    starts = jnp.cumsum(counts, axis=1) - counts           # [B, E]
    pos_in_e = jnp.arange(Tk)[None, :] - jnp.take_along_axis(
        starts, jnp.minimum(s_expert, E - 1), axis=1)
    keep = (pos_in_e < C) & (s_expert < E)
    slot = jnp.where(keep, s_expert * C + pos_in_e, E * C)  # scratch slot
    bidx = jnp.arange(B)[:, None]

    gathered = jnp.take_along_axis(x3, s_token[..., None], axis=1)  # [B,Tk,d]
    buf = jnp.zeros((B, E * C + 1, d), dt).at[bidx, slot].add(gathered)
    buf = buf[:, :-1].reshape(B, E, C, d)
    # NOTE: deliberately no sharding constraint on buf — forcing
    # experts->model here makes GSPMD gather/reshard the dispatch buffer
    # (measured +2 TB all-gather); with buf batch-sharded the expert
    # einsum resolves to cheap weight movement instead.
    from repro.launch.rules import shard_activation
    buf = shard_activation(buf, ("batch", None, None, None))

    # ---- expert computation --------------------------------------------
    h = jnp.einsum("becd,edf->becf", buf, p["wi"].astype(dt))
    if cfg.mlp_act == "swiglu":
        g = jnp.einsum("becd,edf->becf", buf, p["wg"].astype(dt))
        h = jax.nn.silu(g) * h
    elif cfg.mlp_act == "relu2":
        h = jnp.square(jax.nn.relu(h))
    else:
        h = jax.nn.gelu(h)
    out = jnp.einsum("becf,efd->becd", h, p["wo"].astype(dt))

    # ---- combine ---------------------------------------------------------
    out_flat = out.reshape(B, E * C, d)
    expanded = jnp.take_along_axis(
        out_flat, jnp.minimum(slot, E * C - 1)[..., None], axis=1)
    expanded = jnp.where(keep[..., None], expanded, 0.0)
    expanded = expanded * s_gate[..., None].astype(dt)
    y = jnp.zeros((B, S, d), dt).at[bidx, s_token].add(expanded)
    # Pin the combine output back on the residual layout (batch over
    # 'data', replicated along 'model'): the scatter-add otherwise
    # inherits the expert buffer's layout and every MoE block's residual
    # add would reshard under a serving mesh.
    y = shard_activation(y, ("batch", None, None))
    return y.reshape(orig_shape), aux.astype(jnp.float32)


def moe_block_forward(cfg: ModelConfig, p: Dict, x: jax.Array,
                      positions: jax.Array,
                      lengths: Optional[jax.Array] = None,
                      prefix_len: int = 0) -> Tuple[jax.Array, jax.Array]:
    h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
    x = x + A.attention_full(cfg, p["attn"], h, positions,
                             cfg.sliding_window, lengths, prefix_len)
    h = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
    with jax.named_scope("mlp"):
        y, aux = moe_ffn(cfg, p["moe"], h)
    return x + y, aux


def moe_block_prefill(cfg: ModelConfig, p: Dict, x: jax.Array,
                      positions: jax.Array, lengths: jax.Array,
                      capacity: int, prefix_len: int = 0):
    h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
    q, kk, v = A._qkv(cfg, p["attn"], h, positions)
    cache = A.init_kv_cache(cfg, x.shape[0], capacity, x.dtype)
    cache = A.prefill_into_cache(cache, kk, v, lengths)
    x = x + A.attention_full_qkv(cfg, p["attn"], q, kk, v, positions,
                                 cfg.sliding_window, lengths, prefix_len,
                                 out_dtype=x.dtype)
    h = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
    with jax.named_scope("mlp"):
        y, aux = moe_ffn(cfg, p["moe"], h)
    return x + y, cache, aux


def moe_block_extend(cfg: ModelConfig, p: Dict, x: jax.Array, cache: Dict,
                     pos0: jax.Array, valid: Optional[jax.Array] = None,
                     page_table=None, impl: Optional[str] = None,
                     layer=None):
    h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
    if "kp" in cache:                                   # paged pool layer
        y, cache = A.attention_extend_paged(cfg, p["attn"], h, cache, layer,
                                            pos0, cfg.sliding_window,
                                            page_table, valid, impl=impl)
    else:
        y, cache = A.attention_extend(cfg, p["attn"], h, cache, pos0,
                                      cfg.sliding_window, valid)
    x = x + y
    h = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
    with jax.named_scope("mlp"):
        y, _ = moe_ffn(cfg, p["moe"], h, valid=valid)
    return x + y, cache


def moe_block_decode(cfg: ModelConfig, p: Dict, x: jax.Array, cache: Dict,
                     pos: jax.Array, page_table=None,
                     impl: Optional[str] = None, layer=None):
    h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
    if "kp" in cache:                                   # paged pool layer
        y, cache = A.attention_decode_paged(cfg, p["attn"], h, cache, layer,
                                            pos, page_table,
                                            cfg.sliding_window, impl=impl)
    else:
        y, cache = A.attention_decode(cfg, p["attn"], h, cache, pos,
                                      cfg.sliding_window)
    x = x + y
    h = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
    with jax.named_scope("mlp"):
        y, _ = moe_ffn(cfg, p["moe"], h)
    return x + y, cache
