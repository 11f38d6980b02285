"""Parameter definition system + shared neural layers (pure functional JAX).

Params are pytrees of jnp arrays.  Shapes/logical-axes/dtypes are declared
once via :class:`ParamDef` trees; ``init_params`` materializes them and
``launch.sharding`` maps logical axes onto the mesh.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

PyTree = Any


@dataclass(frozen=True)
class ParamDef:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]        # logical axis per dim
    dtype: Any = jnp.float32
    init: str = "normal"                   # normal | zeros | ones
    scale: float = 1.0                     # stddev multiplier for "normal"

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def is_def(x) -> bool:
    return isinstance(x, ParamDef)


def tree_defs(tree: PyTree):
    return jax.tree_util.tree_leaves(tree, is_leaf=is_def)


def stack_defs(tree: PyTree, repeats: int) -> PyTree:
    """Prepend a scanned-layers axis to every ParamDef in the tree."""

    def f(d: ParamDef) -> ParamDef:
        return dataclasses.replace(d, shape=(repeats,) + d.shape,
                                   axes=("layers",) + d.axes)

    return jax.tree_util.tree_map(f, tree, is_leaf=is_def)


def init_params(defs: PyTree, rng: jax.Array) -> PyTree:
    """Materialize a ParamDef tree into actual arrays."""
    leaves, treedef = jax.tree_util.tree_flatten(defs, is_leaf=is_def)
    rngs = jax.random.split(rng, len(leaves))

    def one(d: ParamDef, key):
        if d.init == "zeros":
            return jnp.zeros(d.shape, d.dtype)
        if d.init == "ones":
            return jnp.ones(d.shape, d.dtype)
        if d.init == "const":
            return jnp.full(d.shape, d.scale, d.dtype)
        fan_in = d.shape[-2] if len(d.shape) >= 2 else max(d.shape[-1], 1)
        std = d.scale / (fan_in ** 0.5)
        return (jax.random.normal(key, d.shape, jnp.float32) * std).astype(d.dtype)

    return jax.tree_util.tree_unflatten(treedef, [one(d, k) for d, k in zip(leaves, rngs)])


def abstract_params(defs: PyTree) -> PyTree:
    """ShapeDtypeStruct tree (for dry-run lowering without allocation)."""
    return jax.tree_util.tree_map(
        lambda d: jax.ShapeDtypeStruct(d.shape, d.dtype), defs, is_leaf=is_def)


def conv_tail_at(hist: jax.Array, n_valid: jax.Array, ck: int) -> jax.Array:
    """Gather the ck-1 causal-conv history entries ending at each row's
    last valid token (masked recurrent extends).  hist: [B, (ck-1)+S];
    returns [B, ck-1].  Shared by the mamba and RG-LRU mixers."""
    idx = n_valid[:, None] + jnp.arange(ck - 1)[None, :]   # [B, ck-1]
    return jnp.take_along_axis(hist, idx[:, :, None], axis=1)


def init_empty_cache(defs: PyTree) -> PyTree:
    """Materialize a decode-cache def tree in its EMPTY state: zeros
    everywhere except ``tok`` leaves, which hold -1 (= no token cached).
    The single source of this recipe for models, engine, and tests."""
    cache = init_params(defs, jax.random.PRNGKey(0))
    return jax.tree_util.tree_map_with_path(
        lambda path, x: (jnp.full_like(x, -1)
                         if any(getattr(k, "key", None) == "tok"
                                for k in path) else x), cache)


def param_count(defs: PyTree) -> int:
    import numpy as np
    return int(sum(np.prod(d.shape) for d in tree_defs(defs)))


# ---------------------------------------------------------------------------
# Normalization
# ---------------------------------------------------------------------------

def rmsnorm_def(dim: int, dtype=jnp.float32) -> PyTree:
    return {"scale": ParamDef((dim,), (None,), dtype, init="ones")}


def rmsnorm(p, x, eps: float = 1e-6):
    with jax.named_scope("norm"):
        dt = x.dtype
        x = x.astype(jnp.float32)
        var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        y = x * jax.lax.rsqrt(var + eps)
        return (y * p["scale"].astype(jnp.float32)).astype(dt)


def rmsnorm_heads(scale, x, eps: float = 1e-6):
    """qk-norm: RMS normalize over the head dim. scale: (head_dim,)."""
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)).astype(dt)


# ---------------------------------------------------------------------------
# Rotary position embedding
# ---------------------------------------------------------------------------

def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Apply RoPE.  x: [..., S, H, D]; positions: broadcastable to [..., S]."""
    d = x.shape[-1]
    half = d // 2
    freq = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions[..., None].astype(jnp.float32) * freq          # [..., S, half]
    cos = jnp.cos(ang)[..., None, :]                               # [..., S, 1, half]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP (SwiGLU / squared-ReLU / GeLU)
# ---------------------------------------------------------------------------

def mlp_def(d_model: int, d_ff: int, act: str, dtype=jnp.float32) -> PyTree:
    p = {
        "wi": ParamDef((d_model, d_ff), ("embed", "ff"), dtype),
        "wo": ParamDef((d_ff, d_model), ("ff", "embed"), dtype),
    }
    if act == "swiglu":
        p["wg"] = ParamDef((d_model, d_ff), ("embed", "ff"), dtype)
    return p


def mlp(p, x, act: str):
    with jax.named_scope("mlp"):
        dt = x.dtype
        h = jnp.einsum("...d,df->...f", x, p["wi"].astype(dt))
        if act == "swiglu":
            g = jnp.einsum("...d,df->...f", x, p["wg"].astype(dt))
            h = jax.nn.silu(g) * h
        elif act == "relu2":
            h = jnp.square(jax.nn.relu(h))
        elif act == "gelu":
            h = jax.nn.gelu(h)
        else:
            raise ValueError(f"unknown act {act}")
        return jnp.einsum("...f,fd->...d", h, p["wo"].astype(dt))


def embed_def(vocab: int, d_model: int, dtype=jnp.float32) -> ParamDef:
    return ParamDef((vocab, d_model), ("vocab", "embed"), dtype, scale=1.0)


def unembed_def(d_model: int, vocab: int, dtype=jnp.float32) -> ParamDef:
    return ParamDef((d_model, vocab), ("embed", "vocab"), dtype)
