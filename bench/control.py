#!/usr/bin/env python3
"""Run a cell with its control, or with a fault planted, in the
program's place, on several seeds in one process, and print each run's
result line.

    python3 bench/control.py --workload rhg-d16.stream-s20 \\
        --seeds 11,12,13 --seconds 20 [--fault half_left_out]

The control is the cell's plain reference with what would tempt a later
change: for G(n, m) a broken guarantee (repeated edge indices are not
redrawn), for the RHG Eq. 9 in float32.  A fault (``bench/faults.py``)
breaks the program's own chunks where they are produced.  Each run must
come out ``"correct": false``; the smallest reading the control gives of
each compared number is that number's upper reading.  The benchmark's
own runs never run either.
"""
import argparse
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--fault", default=None,
                    help="plant this fault of bench/faults.py in the "
                         "program instead of running the control")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from bench.harness.env import prepare

    prepare()
    import repro  # noqa: F401
    from repro import api

    from bench.faults import broken_stream
    from bench.harness.runner import run

    if args.fault:
        api.iter_edge_chunks = broken_stream(args.fault)
    for seed in (int(s) for s in args.seeds.split(",")):
        run(args.workload, seed, args.seconds, False,
            t_start=time.perf_counter(), control=not args.fault)
    return 0


if __name__ == "__main__":
    sys.exit(main())
