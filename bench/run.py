#!/usr/bin/env python3
"""Run one cell of the benchmark on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (configuration, traffic mix, metrics) comes from
``BENCHMARK.json`` at the root of the checkout and the files under
``bench/`` it names.  The run refuses any platform but a TPU, and fewer
chips than the cell asks for, with a non-zero exit and no result.  It
warms up the cell, measures for ``--seconds``, compares what the window
produced with the plain reference, and prints the result as one JSON
object on the last line of standard output; the numbers compared, each
beside its limit, are the last lines of standard error.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the traced run's .xplane.pb into this directory")
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT))
    from bench.harness.env import prepare

    prepare()
    try:
        import repro  # noqa: F401  (x64 and the cache, before any compile)
        from bench.harness.device import NoChip
        from bench.harness.runner import run
    except ImportError as e:
        print(f"bench: cannot import the program under test: {e}",
              file=sys.stderr)
        return 2
    try:
        run(args.workload, args.seed, args.seconds, bool(args.trace),
            t_start=T_START, keep_trace=args.keep_trace)
    except NoChip as e:
        print(f"bench: {e}; nothing was measured", file=sys.stderr)
        return 3
    return 0


if __name__ == "__main__":
    sys.exit(main())
