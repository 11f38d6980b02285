"""Decoder-only transformer assembling heterogeneous block patterns.

The layer pattern is grouped into a minimal repeating *unit* which is
scanned with stacked params (``lax.scan``), keeping HLO compact enough to
compile 80 dry-run combinations; a short non-repeating tail is unrolled.

Three entry points: ``forward`` (teacher forcing), ``prefill`` (forward +
primed decode cache), ``decode_step`` (one token).  Decode caches mirror
the stage structure: attention layers carry KV ring buffers, mamba/rglru
layers carry O(1) recurrent state snapshots — this heterogeneity is what
the prefix cache (serving/prefix_cache.py) snapshots per reflection round.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as A
from repro.models import layers as L
from repro.models import mamba as M
from repro.models import moe as MOE
from repro.models import rglru as RG

PyTree = Any
MAX_UNIT = 6


def find_unit(pattern: Tuple[str, ...]) -> Tuple[Tuple[str, ...], int, Tuple[str, ...]]:
    """Minimal repeating unit + repeat count + unrolled tail."""
    best = (pattern, 1, ())
    best_covered = len(pattern)  # unit-len * 1
    for ulen in range(1, min(MAX_UNIT, len(pattern)) + 1):
        unit = pattern[:ulen]
        r = 1
        while pattern[:(r + 1) * ulen] == unit * (r + 1):
            r += 1
        covered = r * ulen
        if r >= 2 and (covered > best_covered or best[1] < 2):
            best = (unit, r, pattern[covered:])
            best_covered = covered
    if best[1] < 2:
        return pattern, 1, ()
    return best


def block_def(cfg: ModelConfig, kind: str, dtype) -> Dict:
    if kind in ("attn", "rg_attn"):
        return A.attn_block_def(cfg, dtype)
    if kind == "moe":
        return MOE.moe_block_def(cfg, dtype)
    if kind == "mamba":
        return M.mamba_block_def(cfg, dtype)
    if kind == "rglru":
        return RG.rglru_block_def(cfg, dtype)
    raise ValueError(f"unknown block kind {kind}")


def block_cache_def(cfg: ModelConfig, kind: str, batch: int, capacity: int,
                    dtype, seq_shard: bool, kv_dtype=None) -> Dict:
    if kind == "attn":
        return A.kv_cache_def(cfg, batch, capacity, dtype, seq_shard,
                              kv_dtype)
    if kind == "rg_attn":
        return A.kv_cache_def(cfg, batch, min(capacity, cfg.local_window),
                              dtype, seq_shard, kv_dtype)
    if kind == "moe":
        return A.kv_cache_def(cfg, batch, capacity, dtype, seq_shard,
                              kv_dtype)
    if kind == "mamba":
        return M.mamba_cache_def(cfg, batch, dtype)
    if kind == "rglru":
        return RG.rglru_cache_def(cfg, batch, dtype)
    raise ValueError(kind)


def block_cache_def_paged(cfg: ModelConfig, kind: str, batch: int,
                          num_pages: int, page_size: int, dtype,
                          kv_dtype=None) -> Dict:
    """Paged variant: attention-bearing layers get a shared page POOL (no
    batch axis); recurrent layers keep their dense per-request O(1) state
    — paging only pays off where cache size grows with sequence length."""
    if kind in ("attn", "rg_attn", "moe"):
        return A.paged_kv_cache_def(cfg, num_pages, page_size, dtype,
                                    kv_dtype)
    if kind == "mamba":
        return M.mamba_cache_def(cfg, batch, dtype)
    if kind == "rglru":
        return RG.rglru_cache_def(cfg, batch, dtype)
    raise ValueError(kind)


class TransformerLM:
    """Functional LM; params/caches are plain pytrees."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.dtype = jnp.dtype(cfg.dtype)
        self.param_dtype = jnp.dtype(cfg.param_dtype)
        self.unit, self.repeats, self.tail = find_unit(cfg.block_pattern)

    # ---------------- parameter / cache definitions -----------------------

    def param_defs(self) -> PyTree:
        cfg, pd = self.cfg, self.param_dtype
        unit_defs = tuple(block_def(cfg, k, pd) for k in self.unit)
        defs = {
            "embed": L.embed_def(cfg.vocab_size, cfg.d_model, pd),
            "scan": L.stack_defs(unit_defs, self.repeats) if self.repeats > 1
                    else unit_defs,
            "tail": tuple(block_def(cfg, k, pd) for k in self.tail),
            "ln_f": L.rmsnorm_def(cfg.d_model, pd),
        }
        if not cfg.tie_embeddings:
            defs["unembed"] = L.unembed_def(cfg.d_model, cfg.vocab_size, pd)
        return defs

    def init(self, rng: jax.Array) -> PyTree:
        return L.init_params(self.param_defs(), rng)

    def attn_capacity(self, max_seq: int) -> int:
        w = self.cfg.sliding_window
        return min(max_seq, w) if w else max_seq

    # prefill_extend accepts all_logits=True (speculative verify step);
    # models without the flag (e.g. whisper's encoder-decoder) are
    # excluded from speculation by the engine via this marker.
    supports_verify = True

    def cache_defs(self, batch: int, max_seq: int, seq_shard: bool = True,
                   kv_dtype=None) -> PyTree:
        cfg = self.cfg
        cap = self.attn_capacity(max_seq)
        unit_caches = tuple(
            block_cache_def(cfg, k, batch, cap, self.dtype, seq_shard,
                            kv_dtype)
            for k in self.unit)
        return {
            "scan": (L.stack_defs(unit_caches, self.repeats)
                     if self.repeats > 1 else unit_caches),
            "tail": tuple(block_cache_def(cfg, k, batch, cap, self.dtype,
                                          seq_shard, kv_dtype)
                          for k in self.tail),
        }

    def init_cache(self, batch: int, max_seq: int,
                   seq_shard: bool = True) -> PyTree:
        return L.init_empty_cache(self.cache_defs(batch, max_seq, seq_shard))

    def cache_defs_paged(self, batch: int, num_pages: int, page_size: int,
                         kv_dtype=None) -> PyTree:
        """Decode-cache defs with attention KV in a shared page pool
        (scan-stacked pools are [layers, P, K, ps, hd]); recurrent layers
        keep their dense [batch, ...] state.  ``kv_dtype`` (None =
        ModelConfig.kv_dtype): "int8" adds per-page scale sidecar pools."""
        cfg = self.cfg
        unit_caches = tuple(
            block_cache_def_paged(cfg, k, batch, num_pages, page_size,
                                  self.dtype, kv_dtype)
            for k in self.unit)
        return {
            "scan": (L.stack_defs(unit_caches, self.repeats)
                     if self.repeats > 1 else unit_caches),
            "tail": tuple(block_cache_def_paged(cfg, k, batch, num_pages,
                                                page_size, self.dtype,
                                                kv_dtype)
                          for k in self.tail),
        }

    # ---------------- activation sharding ---------------------------------

    def _maybe_shard_seq(self, x: jax.Array) -> jax.Array:
        """Megatron-SP: residual stream seq-sharded over 'model' between
        blocks (no-op without an active mesh or when disabled)."""
        if not self.cfg.shard_seq_activations or x.ndim != 3 or x.shape[1] <= 1:
            return x
        from repro.launch.rules import shard_activation
        return shard_activation(x, ("batch", "seq_act", None))

    def _shard_serve_act(self, x: jax.Array) -> jax.Array:
        """Mesh-sharded serving steps: pin the residual stream
        batch-over-'data', REPLICATED along 'model' — tensor parallelism
        shards the weights, and the [B, <=chunk, d] decode/extend
        activations are tiny next to them, so replaying them on every
        model shard beats scattering + regathering around each block.
        No-op without an active mesh (single-device engines)."""
        from repro.launch.rules import shard_activation
        return shard_activation(x, ("batch",) + (None,) * (x.ndim - 1))

    # ---------------- embedding ------------------------------------------

    def embed(self, params: PyTree, tokens: jax.Array) -> jax.Array:
        with jax.named_scope("embed"):
            e = params["embed"].astype(self.dtype)
            return e[tokens]

    def unembed(self, params: PyTree, x: jax.Array) -> jax.Array:
        with jax.named_scope("head"):
            if self.cfg.tie_embeddings:
                w = params["embed"].astype(self.dtype).T
            else:
                w = params["unembed"].astype(self.dtype)
            return jnp.einsum("...d,dv->...v", x, w)

    # ---------------- block application -----------------------------------

    def _apply_block_fwd(self, kind: str, p: Dict, x: jax.Array, aux,
                         positions, lengths, prefix_len):
        cfg = self.cfg
        if kind in ("attn", "rg_attn"):
            return A.attn_block_forward(cfg, p, x, positions, kind,
                                        lengths, prefix_len), aux
        if kind == "moe":
            y, a = MOE.moe_block_forward(cfg, p, x, positions, lengths,
                                         prefix_len)
            return y, aux + a
        if kind == "mamba":
            return M.mamba_block_forward(cfg, p, x), aux
        if kind == "rglru":
            return RG.rglru_block_forward(cfg, p, x), aux
        raise ValueError(kind)

    def _apply_block_prefill(self, kind: str, p, x, positions, lengths,
                             capacity, prefix_len):
        cfg = self.cfg
        if kind in ("attn", "rg_attn"):
            cap = min(capacity, cfg.local_window) if kind == "rg_attn" else capacity
            y, c = A.attn_block_prefill(cfg, p, x, positions, lengths, cap,
                                        kind, prefix_len)
            return y, c
        if kind == "moe":
            y, c, _ = MOE.moe_block_prefill(cfg, p, x, positions, lengths,
                                            capacity, prefix_len)
            return y, c
        if kind == "mamba":
            return M.mamba_block_prefill(cfg, p, x)
        if kind == "rglru":
            return RG.rglru_block_prefill(cfg, p, x)
        raise ValueError(kind)

    def _apply_block_decode(self, kind: str, p, x, cache, pos,
                            page_table=None, attn_impl=None):
        cfg = self.cfg
        if kind in ("attn", "rg_attn"):
            return A.attn_block_decode(cfg, p, x, cache, pos, kind,
                                       page_table, impl=attn_impl)
        if kind == "moe":
            return MOE.moe_block_decode(cfg, p, x, cache, pos, page_table,
                                        impl=attn_impl)
        if kind == "mamba":
            return M.mamba_block_decode(cfg, p, x, cache)
        if kind == "rglru":
            return RG.rglru_block_decode(cfg, p, x, cache)
        raise ValueError(kind)

    def _apply_block_extend(self, kind: str, p, x, cache, pos0, valid=None,
                            page_table=None, attn_impl=None):
        cfg = self.cfg
        if kind in ("attn", "rg_attn"):
            return A.attn_block_extend(cfg, p, x, cache, pos0, kind, valid,
                                       page_table, impl=attn_impl)
        if kind == "moe":
            return MOE.moe_block_extend(cfg, p, x, cache, pos0, valid,
                                        page_table, impl=attn_impl)
        if kind == "mamba":
            return M.mamba_block_extend(cfg, p, x, cache, valid)
        if kind == "rglru":
            return RG.rglru_block_extend(cfg, p, x, cache, valid)
        raise ValueError(kind)

    # ---------------- forward (teacher forcing) ----------------------------

    def forward(self, params: PyTree, batch: Dict, remat: bool = False,
                prefix_embeds: Optional[jax.Array] = None,
                return_hidden: bool = False) -> Tuple[jax.Array, jax.Array]:
        """Returns (logits [B,S,V], aux_loss scalar); final hidden states
        instead of logits when ``return_hidden`` (chunked-loss path)."""
        tokens = batch["tokens"]
        x = self.embed(params, tokens)
        prefix_len = 0
        if prefix_embeds is not None:
            x = jnp.concatenate([prefix_embeds.astype(self.dtype), x], axis=1)
            prefix_len = prefix_embeds.shape[1]
        B, S, _ = x.shape
        positions = jnp.arange(S)[None, :].astype(jnp.int32)
        lengths = batch.get("lengths")
        aux0 = jnp.zeros((), jnp.float32)

        def unit_body(carry, unit_params):
            x, aux = carry
            for kind, p in zip(self.unit, unit_params):
                x = self._maybe_shard_seq(x)
                x, aux = self._apply_block_fwd(kind, p, x, aux, positions,
                                               lengths, prefix_len)
            return (self._maybe_shard_seq(x), aux), None

        body = jax.checkpoint(unit_body) if remat else unit_body
        if self.repeats > 1:
            (x, aux), _ = jax.lax.scan(body, (x, aux0), params["scan"])
        else:
            (x, aux), _ = body((x, aux0), params["scan"])
        for kind, p in zip(self.tail, params["tail"]):
            x, aux = self._apply_block_fwd(kind, p, x, aux, positions,
                                           lengths, prefix_len)
        x = L.rmsnorm(params["ln_f"], x, self.cfg.norm_eps)
        if prefix_len:
            x = x[:, prefix_len:]
        if return_hidden:
            return x, aux / max(self.cfg.num_layers, 1)
        logits = self.unembed(params, x)
        return logits, aux / max(self.cfg.num_layers, 1)

    # ---------------- prefill ----------------------------------------------

    def prefill(self, params: PyTree, tokens: jax.Array,
                lengths: Optional[jax.Array] = None,
                max_seq: Optional[int] = None,
                prefix_embeds: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, PyTree]:
        """Returns (logits at last valid position [B,V], primed cache)."""
        B, S = tokens.shape
        if lengths is None:
            lengths = jnp.full((B,), S, jnp.int32)
        x = self.embed(params, tokens)
        prefix_len = 0
        if prefix_embeds is not None:
            x = jnp.concatenate([prefix_embeds.astype(self.dtype), x], axis=1)
            prefix_len = prefix_embeds.shape[1]
            lengths = lengths + prefix_len
        S_tot = x.shape[1]
        capacity = self.attn_capacity(max_seq or S_tot)
        positions = jnp.arange(S_tot)[None, :].astype(jnp.int32)

        def unit_body(x, payload):
            unit_params = payload
            caches = []
            for kind, p in zip(self.unit, unit_params):
                x, c = self._apply_block_prefill(kind, p, x, positions,
                                                 lengths, capacity, prefix_len)
                caches.append(c)
            return x, tuple(caches)

        if self.repeats > 1:
            x, scan_caches = jax.lax.scan(unit_body, x, params["scan"])
        else:
            x, scan_caches = unit_body(x, params["scan"])
        tail_caches = []
        for kind, p in zip(self.tail, params["tail"]):
            x, c = self._apply_block_prefill(kind, p, x, positions, lengths,
                                             capacity, prefix_len)
            tail_caches.append(c)
        x = L.rmsnorm(params["ln_f"], x, self.cfg.norm_eps)
        last = jnp.take_along_axis(
            x, (lengths - 1)[:, None, None].astype(jnp.int32), axis=1)[:, 0]
        logits = self.unembed(params, last)
        return logits, {"scan": scan_caches, "tail": tuple(tail_caches)}

    # ---------------- prefix extension (prompt caching) --------------------

    def prefill_extend(self, params: PyTree, cache: PyTree, tokens: jax.Array,
                       pos0: jax.Array,
                       n_valid: Optional[jax.Array] = None,
                       page_table: Optional[jax.Array] = None,
                       all_logits: bool = False,
                       attn_impl: Optional[str] = None
                       ) -> Tuple[jax.Array, PyTree]:
        """Prefill a token SUFFIX on top of a cached prefix.

        tokens: [B, Sx] continue at absolute position pos0 [B].  Returns
        (last-token logits [B,V], updated cache).  This is what makes a
        reflection round's prefill cost proportional to the suffix only.

        ``n_valid`` ([B] int) turns this into the serving engine's MIXED
        chunked-prefill/decode step: row b processes only its first
        n_valid[b] lanes (0 = complete no-op for that row's cache), and the
        returned logits are taken at each row's last valid lane.  Pad lanes
        never reach the KV cache, recurrent state, or MoE dispatch, so a
        prompt split into arbitrary chunks reproduces monolithic prefill
        exactly — including for recurrent models, whose states must
        summarize precisely the processed prefix.

        ``page_table`` ([B, NP] int32) selects the PAGED write/read path
        for attention layers (cache leaves are shared page pools); the
        same table serves every layer.

        ``all_logits`` (static) returns logits at EVERY lane
        ([B, Sx, V] instead of [B, V]) — the serving engine's
        speculative VERIFY step: lane i's logits are the next-token
        distribution after position pos0+i, so one call scores a whole
        drafted continuation.  Logits at invalid lanes (>= n_valid) are
        meaningless and must be ignored by the caller.  The unembed cost
        grows with Sx, which is why verify steps use a narrow dedicated
        width (1 + ServeConfig.spec_tokens) rather than riding the wide
        prefill-chunk shape.

        ``attn_impl`` (static; "pallas"/"xla"/None) selects how paged
        attention layers READ the pool: the page-table-walking Pallas
        extend kernel or the XLA gather densify (default).  Ignored by
        the ring path and non-attention layers.
        """
        x = self._shard_serve_act(self.embed(params, tokens))
        valid = None
        if n_valid is not None:
            valid = jnp.arange(tokens.shape[1])[None, :] < n_valid[:, None]

        def unit_body(x, payload):
            unit_params, unit_caches = payload
            new_caches = []
            for kind, p, c in zip(self.unit, unit_params, unit_caches):
                x, c = self._apply_block_extend(kind, p, x, c, pos0, valid,
                                                page_table, attn_impl)
                new_caches.append(c)
            return x, tuple(new_caches)

        if self.repeats > 1:
            x, scan_caches = jax.lax.scan(
                unit_body, x, (params["scan"], cache["scan"]))
        else:
            x, scan_caches = unit_body(x, (params["scan"], cache["scan"]))
        tail_caches = []
        for kind, p, c in zip(self.tail, params["tail"], cache["tail"]):
            x, c = self._apply_block_extend(kind, p, x, c, pos0, valid,
                                            page_table, attn_impl)
            tail_caches.append(c)
        x = L.rmsnorm(params["ln_f"], x, self.cfg.norm_eps)
        if all_logits:
            logits = self.unembed(params, x)                    # [B, Sx, V]
        elif n_valid is None:
            logits = self.unembed(params, x[:, -1])
        else:
            last = jnp.take_along_axis(
                x, jnp.maximum(n_valid - 1, 0)[:, None, None], axis=1)[:, 0]
            logits = self.unembed(params, last)
        return logits, {"scan": scan_caches, "tail": tuple(tail_caches)}

    # ---------------- decode -----------------------------------------------

    def decode_step(self, params: PyTree, cache: PyTree, tokens: jax.Array,
                    pos: jax.Array,
                    page_table: Optional[jax.Array] = None,
                    attn_impl: Optional[str] = None
                    ) -> Tuple[jax.Array, PyTree]:
        """tokens: [B,1] int32; pos: [B] absolute position of this token.
        ``page_table`` ([B, NP]) selects the paged attention path;
        ``attn_impl`` (static) its read implementation (see
        ``prefill_extend``)."""
        x = self._shard_serve_act(self.embed(params, tokens))

        def unit_body(x, payload):
            unit_params, unit_caches = payload
            new_caches = []
            for kind, p, c in zip(self.unit, unit_params, unit_caches):
                x, c = self._apply_block_decode(kind, p, x, c, pos,
                                                page_table, attn_impl)
                new_caches.append(c)
            return x, tuple(new_caches)

        if self.repeats > 1:
            x, scan_caches = jax.lax.scan(
                unit_body, x, (params["scan"], cache["scan"]))
        else:
            x, scan_caches = unit_body(x, (params["scan"], cache["scan"]))
        tail_caches = []
        for kind, p, c in zip(self.tail, params["tail"], cache["tail"]):
            x, c = self._apply_block_decode(kind, p, x, c, pos, page_table,
                                            attn_impl)
            tail_caches.append(c)
        x = L.rmsnorm(params["ln_f"], x, self.cfg.norm_eps)
        logits = self.unembed(params, x)
        return logits[:, 0], {"scan": scan_caches, "tail": tuple(tail_caches)}
