#!/usr/bin/env python3
"""Charge a stream cell's device idle time to the program's own spans,
and measure what ``repro.obs`` costs while it is on.

    python3 bench/attribute.py --workload rhg-d16.stream-s20 \\
        --seeds 11,12,13 --seconds 50 [--cost] [--keep-trace DIR]

Per seed, after the cell's set-up: two profiled half-windows, each
starting a new pass, ``repro.obs`` off then on.  With ``--cost``, four
whole untraced windows of ``--seconds``, ``repro.obs`` off, on, on, off,
and each one's ``edges_per_s``, come first, and the profiled halves are
four too, in the same order.  Each profiled half reads the cell's
per-layer metrics as the runner does (``device_idle.stream``,
``wave_hbm_roofline``, ...), and each with it on also the attribution of
``bench/harness/attribution.py``: the idle seconds by the innermost
span open (program or benchmark), the shares of the traced stretch idle
under a ``plan/*`` span, in the per-wave host spans, and in
``bench/next_chunk`` alone, and how far the labels' sum is from
``device_idle.stream``.  One JSON line per seed on standard output,
each reading a list in run order, then ``correct`` as the runner
decides it, the passes compared (``attempted``) and each number compared
beside its limit.  A half starts a pass and the next one closes it, so
``correct`` is false wherever no half streams a whole pass: every
half has to outlast a pass of the instance, as at the cell's size.
``--keep-trace`` copies the last profiled half with ``repro.obs`` on
there.  The benchmark's own runs never run this.
"""
import argparse
import contextlib
import gc
import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
TRACE_DIR = ROOT / ".bench_out" / "attribute"


def _profiled(drv, seconds, traced):
    """One profiled half-window; returns (window id, repro.obs spans,
    trace path)."""
    import jax
    from repro import obs

    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    win, spans = len(drv.windows), []
    jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    try:
        if traced:
            with obs.capture() as tr:
                drv.window(seconds, fresh=True)
            spans = tr.spans()
        else:
            drv.window(seconds, fresh=True)
    finally:
        jax.profiler.stop_trace()
    from bench.harness.xplane import find_xplane

    return win, spans, find_xplane(str(TRACE_DIR))


def measure(name, seed, seconds, cost, traffic=None, keep_trace=None):
    from repro import obs

    from bench.harness import device as dev
    from bench.harness.attribution import idle_by_span
    from bench.harness.cell import find_cell, load_benchmark
    from bench.harness.peaks import peaks_for
    from bench.harness.runner import Readings
    from bench.harness.xplane import reduce_trace

    cell = find_cell(load_benchmark(), name)
    cell.traffic.update(traffic or {})
    devices = dev.require_chips(cell.chips)
    peaks = peaks_for(devices[0].device_kind)
    readers = cell.readers()
    drv = cell.driver().Driver(cell.config, cell.traffic, seed,
                               cell.reference(), seconds=seconds)
    drv.setup()
    gc.collect()
    gc.freeze()
    out = {"cell": name, "seed": seed}
    # off, on, on, off: a drift over the run weighs on both sides alike
    order = (False, True, True, False) if cost else (False, True)
    for on in order if cost else ():
        win = len(drv.windows)
        with obs.capture() if on else contextlib.nullcontext():
            drv.window(seconds)
        c = drv.counters(win)
        out.setdefault(f"edges_per_s.obs_{_side(on)}", []).append(
            c["edges"] / c["seconds"])
    for on in order:
        t0 = time.perf_counter()
        win, spans, path = _profiled(drv, seconds / 2, on)
        summary = reduce_trace(path)
        c = drv.counters(win)
        rd = Readings(name, spans, summary, c, c, 0, peaks)
        got = {m: read(rd) for m, read in readers.items()}
        if summary is not None:
            got["busy_s"], got["window_s"] = summary.busy_s, summary.window_s
        at = idle_by_span(path) if on else None
        if at is not None:
            got["idle_labels"] = at.labels()
            got["idle_plan.stream"] = at.plan_share()
            got["idle_wave_host.stream"] = at.wave_host_share()
            got["idle_unspanned.stream"] = at.unspanned_share()
            got["labels_minus_device_idle_pp"] = (
                100.0 * at.idle_s / at.window_s
                - (got["device_idle.stream"] or 0.0))
        if on:
            got["spans"] = len(spans)
            if keep_trace:
                Path(keep_trace).mkdir(parents=True, exist_ok=True)
                shutil.copy(path, keep_trace)
        got["reduce_s"] = time.perf_counter() - t0 - seconds / 2
        out.setdefault(f"obs_{_side(on)}", []).append(got)
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    drv.close()
    ck = drv.check()
    out["correct"] = bool(ck["attempted"]) and all(
        v <= lim for v, lim in ck["checks"].values())
    out["attempted"], out["checks"] = int(ck["attempted"]), ck["checks"]
    gc.unfreeze()
    return out


def _side(on: bool) -> str:
    return "on" if on else "off"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=50.0)
    ap.add_argument("--cost", action="store_true",
                    help="measure what repro.obs costs: whole untraced "
                         "windows and profiled halves, off, on, on, off")
    ap.add_argument("--traffic", default=None,
                    help="JSON object of traffic keys to override (a "
                         "smaller instance)")
    ap.add_argument("--keep-trace", default=None)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from bench.harness.env import prepare

    prepare()
    import repro  # noqa: F401

    traffic = json.loads(args.traffic) if args.traffic else None
    for seed in (int(s) for s in args.seeds.split(",")):
        res = measure(args.workload, seed, args.seconds, args.cost,
                      traffic, args.keep_trace)
        print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
