"""§Roofline: three-term roofline per (arch x shape) from dry-run artifacts.

    compute term    = HLO_FLOPs / (chips x 197 TFLOP/s)
    memory term     = HLO_bytes / (chips x 819 GB/s)
    collective term = collective_bytes / (chips x 50 GB/s/link)

All numbers in the artifacts are already PER-PARTITION (post-SPMD HLO,
trip-count-corrected by launch/hlocost.py), so terms divide by per-chip
peaks directly.  MODEL_FLOPS uses 6*N*D (train) / 2*N*D (inference) with
N = active params.
"""
from __future__ import annotations

import glob
import json
import os
from typing import Dict, List

from repro.configs.base import SHAPES
from repro.launch.mesh import TARGET_KIND, chip_peaks

ART = os.path.join(os.path.dirname(__file__), "..", "artifacts", "dryrun")


def load_artifacts(mesh: str = "16_16") -> List[Dict]:
    out = []
    for path in sorted(glob.glob(os.path.join(ART, f"*__{mesh}.json"))):
        with open(path) as f:
            out.append(json.load(f))
    return out


def model_flops(rec: Dict) -> float:
    """Analytic useful FLOPs for the whole step, per device."""
    shape = SHAPES[rec["shape"]]
    n_active = rec.get("params_active", 0)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        total = 6.0 * n_active * tokens
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        total = 2.0 * n_active * tokens
    else:  # decode: one token per sequence
        total = 2.0 * n_active * shape.global_batch
    return total / max(rec["chips"], 1)


def roofline_row(rec: Dict) -> Dict:
    coll_bytes = sum(v for k, v in rec["collectives"].items()
                     if not k.endswith("_count"))
    peaks = chip_peaks(TARGET_KIND)      # the chip the dry-run targets
    compute_s = rec["flops"] / peaks["flops_bf16"]
    memory_s = rec["bytes_accessed"] / peaks["hbm_bw"]
    collective_s = coll_bytes / peaks["ici_bw"]
    dom = max((("compute", compute_s), ("memory", memory_s),
               ("collective", collective_s)), key=lambda t: t[1])[0]
    mf = model_flops(rec)
    return {
        "arch": rec["arch"], "shape": rec["shape"], "mesh": rec["mesh"],
        "compute_s": compute_s, "memory_s": memory_s,
        "collective_s": collective_s, "bottleneck": dom,
        "model_flops_per_dev": mf,
        "useful_ratio": mf / max(rec["flops"], 1.0),
        "step_s": max(compute_s, memory_s, collective_s),
    }


def run(verbose: bool = True, mesh: str = "16_16"):
    recs = load_artifacts(mesh)
    rows = []
    if verbose and recs:
        print(f"\n== roofline ({mesh}) — terms in seconds/step ==")
        print(f"{'arch':22s}{'shape':13s}{'compute':>10s}{'memory':>10s}"
              f"{'collect':>10s} {'bottleneck':11s}{'useful':>7s}")
    for rec in recs:
        r = roofline_row(rec)
        if verbose:
            print(f"{r['arch']:22s}{r['shape']:13s}{r['compute_s']:10.4f}"
                  f"{r['memory_s']:10.4f}{r['collective_s']:10.4f} "
                  f"{r['bottleneck']:11s}{r['useful_ratio']:7.2f}")
        rows.append((f"roofline_{r['arch']}_{r['shape']}_{mesh}", 0.0,
                     f"{r['bottleneck']};step={r['step_s']:.4f}s;"
                     f"useful={r['useful_ratio']:.2f}"))
    if not recs and verbose:
        print("roofline: no dry-run artifacts found — run "
              "`python -m repro.launch.dryrun --save` first")
    return rows


if __name__ == "__main__":
    for r in run():
        print(",".join(map(str, r)))
