"""Device meshes and per-chip peak numbers.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state — the dry-run sets
``xla_force_host_platform_device_count`` before first jax init, and tests
must keep seeing 1 device.
"""
from __future__ import annotations

import re
from typing import Dict

import jax
import numpy as np
from jax.sharding import AxisType, Mesh

# Published per-chip peaks, keyed by ``jax.Device.device_kind``.  Source:
# Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 393 TOP/s int8,
# 16 GB HBM at 819 GB/s, 1,600 Gbit/s inter-chip interconnect, here split
# over the 4 ICI links of a chip).  A kind missing here is an error
# (chip_peaks), never a silent v5e default.
CHIP_PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,          # FLOP/s
        "ops_int8": 393e12,            # OP/s
        "hbm_bw": 819e9,               # B/s
        "hbm_bytes": 16e9,             # B
        "ici_bw": 50e9,                # B/s per link
    },
}

# The chip the dry-run and roofline analyses are sized for.
TARGET_KIND = "TPU v5 lite"


def chip_peaks(kind: str) -> Dict[str, float]:
    """Peaks of the chip ``kind`` (a ``device_kind`` string)."""
    try:
        return CHIP_PEAKS[kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind {kind!r}; "
                         f"known: {sorted(CHIP_PEAKS)}") from None


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh():
    """1-device mesh for CPU tests/examples (same axis names)."""
    return _make_mesh((1, 1), ("data", "model"))


def make_device_mesh(device) -> Mesh:
    """1x1 ``("data", "model")`` mesh over one given device: pins an
    Engine replica to that device (``Engine(mesh=...)``)."""
    return Mesh(np.array([[device]]), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)


def make_serve_mesh(spec: str):
    """Serving mesh from a ``"DxM"`` string (e.g. ``"1x2"``): axes
    ``("data", "model")`` — replica groups x tensor-parallel shards.

    Validates against the visible device count: on the CPU, jax must
    already have been initialised with enough devices, which means
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` was exported
    BEFORE the first jax import (launch/serve.py does this for --mesh
    under ``JAX_PLATFORMS=cpu``).
    """
    m = re.fullmatch(r"(\d+)x(\d+)", spec.strip().lower())
    if not m:
        raise ValueError(f"mesh spec {spec!r} is not of the form 'DxM' "
                         f"(e.g. '1x2')")
    d, t = int(m.group(1)), int(m.group(2))
    if d < 1 or t < 1:
        raise ValueError(f"mesh spec {spec!r} has a non-positive axis")
    need, have = d * t, jax.device_count()
    if need > have:
        raise ValueError(
            f"mesh {spec} needs {need} devices but jax sees {have} — on "
            f"the CPU set XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{need} before the first jax import, or use fewer shards")
    return _make_mesh((d, t), ("data", "model"))


def mesh_chips(mesh) -> int:
    n = 1
    for s in mesh.devices.shape:
        n *= s
    return n
