"""Run one benchmark cell once, on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Loads the cell's model with weights drawn from the seed, warms up every
step shape and the traffic's ramp (all of it set-up), serves the cell's
traffic for ``--seconds`` on the wall clock, checks what it served
against the plain reference, and prints one JSON object as the last line
of standard output: ``correct``, ``attempted``, ``failed``, ``metrics``
(end-to-end with ``--trace 0``, per-layer with ``--trace 1``), ``device``
and, when traced, ``breakdown``; last, under ``checks``, each number
compared beside its limit.  Exits non-zero, printing no result, where
JAX finds no TPU or fewer chips than the cell asks for.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the checkout's root (the bench package) and the program's src/; the
# script's own directory would shadow standard modules
sys.path[:] = [ROOT, os.path.join(ROOT, "src")] + [
    p for p in sys.path if os.path.abspath(p or ".") != os.path.dirname(
        os.path.abspath(__file__))]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from bench.harness import run_cell
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace), t_start=T_START)
    sys.stdout.flush()
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
