"""Autotuned block-size table for the Pallas kernels.

The kernels take block parameters (``bq``/``bk`` row/column tiles,
``pages_per_block`` for the page-table walkers) that trade VMEM
residency against grid overhead, and the right values depend on the
accelerator generation and the problem shape.  Historically every
wrapper in ops.py hardcoded ``bq=128, bk=128``; this module replaces
those constants with a COMMITTED per-(backend, kernel, shape-bucket)
table, ``tuning_table.json``, consulted at trace time (block params are
static argnames, so a lookup costs nothing at runtime).

Table layout::

    { kernel: { backend: { shape_key: {"params": {...},
                                       "us": measured,
                                       "model_us": roofline estimate} } } }

``backend`` is the JAX device kind (``cpu``, ``tpu_v5e``, ...);
``shape_key`` buckets each dimension to the next power of two so one
entry covers a band of nearby shapes.  ``lookup`` falls back
backend -> ``"any"`` -> per-kernel defaults, so a missing table (or an
unswept shape) degrades to exactly the old hardcoded behaviour.

Regenerate with ``python benchmarks/kernels_micro.py --tune`` (see
docs/SERVING.md): the sweep times each candidate with the live backend
and records the winner alongside a roofline estimate
(:func:`extend_cost_model_us`) built from the chip's published peaks
(launch/mesh.CHIP_PEAKS, keyed by device kind — a device without peaks,
such as the CPU, cannot be tuned).
"""
from __future__ import annotations

import json
import os
from typing import Dict, Optional

import jax

TABLE_PATH = os.path.join(os.path.dirname(__file__), "tuning_table.json")

# the pre-tuning-table hardcoded values, kept as the universal fallback
DEFAULTS: Dict[str, Dict] = {
    "flash": {"bq": 128, "bk": 128},
    "decode": {"bk": 128},
    "paged_extend": {"bq": 128, "pages_per_block": 1},
}

_cache: Optional[Dict] = None


def backend_key() -> str:
    """Device-kind key, e.g. ``cpu`` / ``tpu_v5_lite``."""
    d = jax.devices()[0]
    kind = getattr(d, "device_kind", "") or d.platform
    return "".join(c if c.isalnum() else "_" for c in kind.lower())


def _bucket(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def shape_key(**dims) -> str:
    """Stable pow2-bucketed key, e.g. ``ctx4096_hd64_r64``."""
    return "_".join(f"{k}{_bucket(int(v))}" for k, v in sorted(dims.items()))


def load_table(refresh: bool = False) -> Dict:
    global _cache
    if _cache is None or refresh:
        if os.path.exists(TABLE_PATH):
            with open(TABLE_PATH) as f:
                _cache = json.load(f)
        else:
            _cache = {}
    return _cache


def lookup(kernel: str, **dims) -> Dict:
    """Best-known block params for ``kernel`` at this shape on this
    backend; always returns a full param dict (defaults fill gaps)."""
    table = load_table().get(kernel, {})
    per_be = table.get(backend_key(), table.get("any", {}))
    entry = per_be.get(shape_key(**dims), {})
    out = dict(DEFAULTS.get(kernel, {}))
    out.update(entry.get("params", {}))
    return out


def record(kernel: str, key: str, params: Dict, *, us: float,
           model_us: float, backend: Optional[str] = None) -> None:
    """Write one sweep winner into the committed table (and the cache)."""
    table = load_table()
    be = backend or backend_key()
    table.setdefault(kernel, {}).setdefault(be, {})[key] = {
        "params": params, "us": round(us, 1), "model_us": round(model_us, 1),
    }
    with open(TABLE_PATH, "w") as f:
        json.dump(table, f, indent=1, sort_keys=True)
        f.write("\n")


def extend_cost_model_us(*, B: int, Sx: int, K: int, G: int, hd: int,
                         ctx: int, kind: str, quant: bool = False) -> float:
    """Two-term roofline for one paged-extend call (page-read-once) on a
    chip of device kind ``kind``: bytes = each mapped KV byte ONCE +
    q/out, flops = QK^T + PV over the causal extent.  This is the floor
    the kernel chases and the sanity bound the sweep records next to
    measured times."""
    from repro.launch.mesh import chip_peaks
    peaks = chip_peaks(kind)
    kv_bytes = 2 * B * ctx * K * hd * (1 if quant else 4)
    if quant:
        kv_bytes += 3 * B * ctx * K * 4                    # scale sidecars
    io_bytes = 2 * B * Sx * K * G * hd * 4                 # q + out
    flops = 2 * 2 * B * Sx * K * G * hd * ctx              # QK^T + PV
    return max(flops / peaks["flops_bf16"],
               (kv_bytes + io_bytes) / peaks["hbm_bw"]) * 1e6
