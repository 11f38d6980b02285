"""Kernel (kernels/paged_extend.py, the Pallas call named
``paged_attention``): the least time the chip could take for the calls
in the traced steps, max(FLOPs / peak FLOP/s, bytes / peak bandwidth)
per call, over the kernel's summed device time in those steps.  One call
per layer per step; FLOPs and bytes from each step's rows
(bench/flops).  Which bound applies is written to the run's notes."""
from bench import flops

KERNEL = "paged_attention"


def read(run):
    rows = {s.index: s.rows for s in run.steps}
    L = run.dims["L"]
    t_min = t_flop = t_byte = kernel_ns = 0.0
    for s in run.trace.steps():
        r = rows[s["index"]]
        f = flops.paged_attention_flops(run.dims, r) / run.peaks["flops_bf16"]
        b = flops.paged_attention_bytes(run.dims, r, run.page_size,
                                        run.kv_itemsize) / run.peaks["hbm_bw"]
        t_min += L * max(f, b)
        t_flop += L * f
        t_byte += L * b
        kernel_ns += run.trace.op_time_in(KERNEL, s["start_ns"],
                                          s["start_ns"] + s["host_ns"])
    if kernel_ns <= 0:
        return None
    run.notes.append(f"{KERNEL}: {kernel_ns * 1e-6:.3f} ms of kernel time; "
                     f"bound by {'bytes' if t_byte >= t_flop else 'FLOPs'} "
                     f"(byte time {t_byte * 1e3:.3f} ms, FLOP time "
                     f"{t_flop * 1e3:.3f} ms)")
    return 100.0 * t_min / (kernel_ns * 1e-9)
