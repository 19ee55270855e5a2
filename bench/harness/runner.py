"""One run of one cell: set-up, the measured window, the reference
comparison and the result line.

With ``--trace 0`` the window is measured whole and the cell's
end-to-end metrics are reported.  With ``--trace 1`` it is split into
two halves, each starting where the driver says (a stream begins a new
pass):

* first half: the JAX profiler is on and ``repro.obs`` is off, so the
  device readings (busy and idle time, program device time) are those
  of the untraced program -- ``repro.obs`` blocks on every wave;
* second half: ``repro.obs`` is on and the profiler off; span readings
  come from this half.

The per-layer metrics are then read from both, by the readers in
``bench/metrics/``.
"""
from __future__ import annotations

import gc
import json
import shutil
import sys
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Optional

from .cell import ROOT, Cell, find_cell, load_benchmark

TRACE_DIR = ROOT / ".bench_out" / "trace"


@dataclass
class Readings:
    """What a per-layer reader reads."""
    cell: str
    spans: list                     # repro.obs span records, second half
    trace: object                   # xplane.TraceSummary of the first half, or None
    device: dict                    # counters of the first half (driver's)
    host: dict                      # counters of the second half (driver's)
    compiles: int                   # backend compiles in the whole window
    peaks: dict


class _GcPauses:
    """Seconds the garbage collector paused the process, from now on."""

    def __init__(self):
        self.seconds, self.count, self._t = 0.0, 0, None
        gc.callbacks.append(self._cb)

    def _cb(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.seconds += time.perf_counter() - self._t
            self.count += info.get("generation") == 2

    def stop(self):
        gc.callbacks.remove(self._cb)


def _fmt(v):
    return f"{v:.6g}" if isinstance(v, float) else str(v)


def run(name: str, seed: int, seconds: float, trace: bool, *, t_start: float,
        require_tpu: bool = True, traffic: Optional[dict] = None,
        control: bool = False, keep_trace: Optional[str] = None,
        out=None, err=None) -> dict:
    """Run cell ``name``; print the result line; return it.

    ``traffic`` overrides keys of the cell's traffic file (tests run a
    cell at a size a CPU holds).  ``control`` puts the driver's control
    in the program's place."""
    out = out or sys.stdout
    err = err or sys.stderr
    import jax

    from repro import obs

    from . import device as dev
    from .clock import CompileClock
    from .peaks import peaks_for

    bench = load_benchmark()
    cell: Cell = find_cell(bench, name)
    if traffic:
        cell.traffic.update(traffic)
    devices = (dev.require_chips(cell.chips) if require_tpu
               else jax.devices()[: cell.chips])
    peaks = peaks_for(devices[0].device_kind) if require_tpu else {}
    clock = CompileClock()
    drv = cell.driver().Driver(cell.config, cell.traffic, seed,
                               cell.reference(), control=control,
                               seconds=seconds)
    drv.setup()
    # Set-up ends in a steady state: what compiling and planning left
    # for the collector is collected now and frozen out of every later
    # collection, so that no pass over it falls inside the window.
    gc.collect()
    gc.freeze()
    pauses = _GcPauses()
    setup_s = time.perf_counter() - t_start
    compiles0 = clock.count
    summary = None
    if not trace:
        drv.window(seconds)
        spans = []
    else:
        from . import xplane

        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1   # the benchmark's own spans; fewer events to read
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
        try:
            drv.window(seconds / 2, fresh=True)
        finally:
            jax.profiler.stop_trace()
        with obs.capture() as tr:
            drv.window(seconds / 2, fresh=True)
        spans = tr.spans()
        path = xplane.find_xplane(str(TRACE_DIR))
        if keep_trace:
            Path(keep_trace).mkdir(parents=True, exist_ok=True)
            shutil.copy(path, keep_trace)
        summary = xplane.reduce_trace(path)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    compiles = clock.count - compiles0
    pauses.stop()
    mem = dev.memory_peak_bytes(devices)
    drv.close()
    ck = drv.check()

    checks = ck["checks"]
    correct = bool(ck["attempted"]) and all(v <= lim for v, lim in checks.values())
    stamp = dev.stamp(devices)
    stamp["memory_peak_bytes"] = mem
    if trace:
        metrics = {}
        rd = Readings(name, spans, summary, drv.counters(0), drv.counters(1),
                      compiles, peaks)
        for mname, read in cell.readers().items():
            v = read(rd)
            if v is not None:
                unit = next(m["unit"] for m in cell.per_layer if m["name"] == mname)
                metrics[mname] = {"value": float(v), "unit": unit}
        if summary is not None:
            stamp["busy_s"] = summary.busy_s
            stamp["window_s"] = summary.window_s
    else:
        e2e = drv.end_to_end()
        e2e["setup_s"] = setup_s
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        metrics = {k: {"value": float(e2e[k]), "unit": units[k]}
                   for k in units if k in e2e}
    result = {"correct": correct, "attempted": int(ck["attempted"]),
              "failed": int(ck["failed"]), "metrics": metrics,
              "device": stamp}
    if summary is not None:
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in summary.ops[:10]],
            "idle_gaps": [[n, s] for n, s in summary.gaps[:10]]}
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}

    print(f"cell {name} seed {seed}: setup {setup_s:.3f}s, window "
          f"{seconds}s, backend compiles in the window: {compiles}, "
          f"compile seconds in all {clock.seconds:.3f}; {pauses.count} "
          f"collections of the oldest generation in the window, "
          f"{pauses.seconds:.3f}s of collector pauses in all", file=err)
    for k, v in metrics.items():
        print(f"metric {k} = {_fmt(v['value'])} {v['unit']}", file=err)
    if summary is not None:
        print(f"trace: busy {summary.busy_s:.6f}s of {summary.window_s:.6f}s; "
              f"programs {json.dumps(summary.programs)}", file=err)
        print(f"trace: longest idle gaps {summary.longest_gaps}", file=err)
    print(ck["info"], file=err)
    for k, (v, lim) in checks.items():
        print(f"check {k}: {v} (limit {lim})", file=err)
    print(f"correct: {str(correct).lower()}", file=err)
    err.flush()
    print(json.dumps(result), file=out)
    out.flush()
    return result
