"""Readings that set the limit of ``correct``: for each seed, the widest
served-token gap of the program and of the control (the reference one
step below the configuration's precision, in the program's place), on
the same sampled requests, all in one process.

    python3 bench/readings.py --workload <name> --seconds <s> --seeds 1 2 3

Prints one JSON line per seed.  The benchmark's own runs never run the
control.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:] = [ROOT, os.path.join(ROOT, "src")] + sys.path[1:]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--no-control", action="store_true")
    args = ap.parse_args()
    from bench import harness
    for seed in args.seeds:
        t0 = time.perf_counter()
        b = harness.build(harness.load_cell(args.workload), seed,
                          args.seconds, t_start=t0)
        win = b.window()
        win.setup_s = win.run() - t0
        res = harness.measure(b, win)
        correct, checks, sample = harness.check(b, win)
        ctl = None
        if not args.no_control:
            t1 = time.perf_counter()
            ctl = harness.check_outputs(
                b.conf, seed, sample,
                control=harness.family_ref(b.conf).CONTROL[b.conf["dtype"]])
            print(f"control: {time.perf_counter() - t1:.2f}s",
                  file=sys.stderr, flush=True)
        print(json.dumps({
            "seed": seed, "correct": correct,
            "program_gap_sd": checks["served_gap_sd"]["value"],
            "control_gap_sd": max(ctl) if ctl else None,
            "control_gaps": ctl,
            "sample_tokens": [len(r.req.output) for r in sample],
            "sample_rounds": [r.spec.round for r in sample],
            "metrics": {k: v["value"] for k, v in res["metrics"].items()},
            "memory_peak_bytes": res["device"]["memory_peak_bytes"],
            "wall_s": time.perf_counter() - t0}), flush=True)


if __name__ == "__main__":
    main()
