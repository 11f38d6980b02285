"""Find an open-loop cell's knee, and how long its ramp has to be: serve
the cell's mix at each of several rates, one after another in one
process, and print what each rate did.

    python3 bench/sweep.py --workload <name> --seconds <s> --rates 0.2 0.3 \
        [--ramp-s 120] [--timeline out.jsonl]

A rate is sustained when the queue does not grow through the window:
requests wait about as long for a slot at the window's end as at its
start, and output tokens/s follows the offered load.  The timeline (one
JSON line per rate and 5-second bucket from the ramp's start) shows when
slot occupancy and the time per step go flat, which is how long the
ramp has to be.
"""
import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:] = [ROOT, os.path.join(ROOT, "src")] + sys.path[1:]
BUCKET_S = 5.0


def offered_tok_s(traffic) -> float:
    """Output tokens per second the mix offers at its rate."""
    per_unit = np.mean([sum(u["out"][:u["rounds"]]) for u in traffic.units])
    return float(traffic.mix["rate_per_s"]) * per_unit


def timeline(win, ramp_s: float):
    """Per bucket of the steps that began in it: steps, ms per step,
    occupied slots, decoding rows, queued requests, output tokens/s and
    the share of the rows that belong to a later round."""
    out = []
    t = -ramp_s
    while t < win.seconds:
        st = [s for s in win.steps if t <= s.t0 < t + BUCKET_S]
        toks = sum(1 for r in win.records for x in r.tok_t
                   if t <= x < t + BUCKET_S)
        rows = sum(len(s.rows) for s in st)
        out.append({
            "t": t, "steps": len(st),
            "step_ms": (1e3 * sum(s.t1 - s.t0 for s in st) / len(st)
                        if st else None),
            "slots": np.mean([s.slots for s in st]) if st else 0.0,
            "decoding": (np.mean([sum(1 for n, _, e in s.rows if n == 1
                                      and e) for s in st]) if st else 0.0),
            "queued": np.mean([s.queued for s in st]) if st else 0.0,
            "tok_s": toks / BUCKET_S,
            "later_share": (sum(s.later for s in st) / rows if rows
                            else 0.0)})
        t += BUCKET_S
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--ramp-s", type=float, default=None)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--timeline", default=None)
    args = ap.parse_args()
    from bench import harness
    for rate in args.rates:
        t0 = time.perf_counter()
        spec = harness.load_cell(args.workload)
        spec["mix"]["rate_per_s"] = rate
        if args.ramp_s is not None:
            spec["mix"]["ramp_s"] = args.ramp_s
        b = harness.build(spec, args.seed, args.seconds, t_start=t0)
        win = b.window()
        win.setup_s = win.run() - t0
        e2e = harness.end_to_end(win)
        T = args.seconds
        waits = [r.admit_t - r.spec.due for r in win.records
                 if r.admit_t is not None and 0.0 <= r.admit_t < T]
        inside = [s for s in win.steps if 0.0 <= s.t0 < T]
        later = sum(1 for r in win.records if r.spec.round > 1
                    for x in r.tok_t if 0.0 <= x < T)
        print(json.dumps({
            "rate_per_s": rate, "setup_s": win.setup_s,
            "offered_tok_s": offered_tok_s(b.traffic),
            "output_tok_s": e2e["output_tok_s"],
            "itl_p50_ms": e2e["itl_p50_ms"], "itl_p95_ms": e2e["itl_p95_ms"],
            "ttft_p90_ms": e2e["ttft_p90_ms"],
            "queued_first_last": [inside[0].queued, inside[-1].queued]
            if inside else None,
            "wait_p50_p90_ms": [harness.percentile(waits, 50),
                                harness.percentile(waits, 90)],
            "later_round_token_share": later / max(1, e2e["output_tok_s"]
                                                   * T),
            "due_in_window": sum(1 for r in win.records
                                 if 0.0 <= r.spec.due < T)}), flush=True)
        if args.timeline:
            with open(args.timeline, "a") as f:
                for row in timeline(win, b.traffic.ramp_s):
                    f.write(json.dumps(dict(row, rate_per_s=rate)) + "\n")
        b.free(win)


if __name__ == "__main__":
    main()
