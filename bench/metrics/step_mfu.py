"""Model step against the chip's peak: the operations the traced steps'
valid tokens need (bench/flops.step_flops: projections, MLP, attention
over each token's context, the head for each sampled row), over the
steps' wall time on the host times the chip's bf16 peak."""
from bench import flops


def read(run):
    rows = {s.index: s.rows for s in run.steps}
    traced = run.trace.steps()
    wall = sum(s["host_ns"] for s in traced) * 1e-9
    if not traced or wall <= 0:
        return None
    work = sum(flops.step_flops(run.dims, rows[s["index"]]) for s in traced)
    return 100.0 * work / (wall * run.peaks["flops_bf16"])
