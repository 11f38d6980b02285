"""Logical-axis rules + activation sharding constraints.

Import-light (no repro.models dependency) so model code can call
``shard_activation`` without cycles.  ``spec_for`` implements the
divisibility fallback described in DESIGN.md §5.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

# logical axis -> ordered tuple of mesh axes to try
DEFAULT_RULES = {
    "batch": ("pod", "data"),
    # KV-cache sequence dim: model axis first (flash-decoding layout, since
    # 8 kv-heads can't divide model=16); batch-1 long decode also absorbs
    # the unused (pod, data) axes = context parallelism.
    "kv_seq": ("model", "pod", "data"),
    "seq_act": ("model",),          # Megatron-style sequence parallelism
    "embed": ("data",),             # FSDP shard of weight matrices
    "vocab": ("model",),
    "ff": ("model",),
    "heads": ("model",),
    "kv_heads": ("model",),
    "experts": ("model",),
    # Paged KV pool leaves ([pages, kv_heads, page_size, hd]): the pool is
    # sharded by PHYSICAL PAGE along 'model' — every device owns
    # num_pages/M pages of every layer.  kv_heads on the same leaf then
    # falls back to replicated (spec_for's used-axis rule), which is the
    # right trade: page-granular placement keeps the write scatter and
    # COW copies local to one shard, while GQA kv_heads (2-8) rarely
    # divide a wide model axis anyway.
    "pages": ("model",),
    "layers": (),                   # scanned-layer axis: never sharded
}


def tp_rules() -> dict:
    """Tensor-parallel-only rules (no FSDP): weights replicated along
    'data', sharded along 'model' where divisible.  Used for every arch
    whose params+optimizer fit per chip without FSDP — avoids the
    contracting-dim activation all-reduces FSDP induces (§Perf #1/#2)."""
    r = dict(DEFAULT_RULES)
    r["embed"] = ()
    return r


def serve_rules() -> dict:
    """Serving-engine rules (mesh-sharded Engine): tensor-parallel param
    placement — weights replicated along 'data', sharded along 'model'
    where divisible — plus the paged pool's 'pages' axis sharded along
    'model'.  Decode never wants FSDP: an embed->data shard would
    all-gather the weights on every step for zero memory benefit at
    serving batch sizes (same measurement as tp_rules)."""
    return tp_rules()


def decode_rules() -> dict:
    """Row-parallel weight layout for mega-arch DECODE (§Perf #3).

    With FSDP rules, every decode step all-gathers the layer weights
    (30 GB/step on nemotron-340b) because GSPMD prefers gathering over
    partial sums when the contracting dim is 'data'-sharded.  Storing
    weights [embed -> model, heads/ff/vocab -> data] keeps them 2-D
    sharded (fits HBM) while making the contraction dim 'model'-sharded —
    the partial-sum all-reduce is then over tiny [B, 1, *] decode
    activations instead of the weights.
    """
    r = dict(DEFAULT_RULES)
    r.update(embed=("model",), heads=("data",), kv_heads=("data",),
             ff=("data",), vocab=("data",), experts=("data",))
    return r


def spec_for(shape: Tuple[int, ...], axes: Tuple[Optional[str], ...],
             mesh, rules=None) -> P:
    """Map logical axes to a PartitionSpec with divisibility fallback."""
    rules = rules or DEFAULT_RULES
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    used = set()
    entries = []
    for dim, ax in zip(shape, axes):
        if ax is None or ax not in rules:
            entries.append(None)
            continue
        picked = []
        prod = 1
        for m in rules[ax]:
            if m not in sizes or m in used:
                continue
            if dim % (prod * sizes[m]) == 0:
                picked.append(m)
                prod *= sizes[m]
        if not picked:
            entries.append(None)
        else:
            used.update(picked)
            entries.append(tuple(picked) if len(picked) > 1 else picked[0])
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)


def _active_mesh():
    """The mesh of the enclosing ``with mesh:`` block, or None."""
    from jax._src.mesh import thread_resources
    mesh = thread_resources.env.physical_mesh
    return None if mesh.empty else mesh


def shard_activation(x: jax.Array, axes: Tuple[Optional[str], ...],
                     rules=None) -> jax.Array:
    """with_sharding_constraint honoring the logical rules.

    No-op when no mesh context is active (CPU tests) or when nothing in
    the spec is shardable.
    """
    mesh = _active_mesh()
    if mesh is None:
        return x
    spec = spec_for(x.shape, axes, mesh, rules)
    if not any(spec):
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
