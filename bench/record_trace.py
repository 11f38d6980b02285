"""Record the reduced trace of a short traced run of a cell, for the
reduction's test (``testdata/``), and print the trace's layout.

    python3 bench/record_trace.py --workload <name> --out <file.json.gz>
"""
import argparse
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:] = [ROOT, os.path.join(ROOT, "src")] + sys.path[1:]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--seed", type=int, default=5)
    args = ap.parse_args()
    import jax

    from bench import harness, tracefile
    b = harness.build(harness.load_cell(args.workload), args.seed,
                      args.seconds, t_start=time.perf_counter())
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
    win = b.window(trace_dir)
    win.run()
    events = tracefile.extract(trace_dir)
    shutil.rmtree(trace_dir, ignore_errors=True)
    conf = b.conf
    tracefile.save(dict(events, steps=[
        [s.index, s.kind, s.rows] for s in win.steps], meta={
        "dims": harness.family_ref(conf).dims(conf),
        "page_size": conf["serve"]["page_size"],
        "kv_itemsize": jax.numpy.dtype(conf["dtype"]).itemsize,
        "kind": b.device["kind"]}), args.out)
    ev = tracefile.load(args.out)
    for dev, ops in ev["device_ops"].items():
        names = sorted({o[0] for o in ops})
        print(f"{dev}: {len(ops)} ops, {len(names)} names, e.g. "
              f"{names[:40]}")
    print(f"host spans: {len(ev['host_spans'])}")


if __name__ == "__main__":
    main()
