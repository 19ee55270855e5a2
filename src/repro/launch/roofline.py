"""Roofline model: achieved-vs-peak fractions for traced programs.

Two layers:

* the analytic model — :class:`Peaks`, :func:`roofline_seconds` and
  :func:`achieved_fraction` turn the static FLOP/byte estimates of
  :class:`repro.launch.hlocost.HloCost` into a time floor
  ``max(flops/peak_flops, bytes/peak_bw)`` and compare it against
  a measured time.  :func:`program_summary` does this for one lowered
  program.

* the legacy table CLI — aggregate dry-run JSONs into the
  EXPERIMENTS.md roofline table:

    PYTHONPATH=src python -m repro.launch.roofline [--dir results/dryrun]
"""
from __future__ import annotations

import argparse
import glob
import json
import os
from dataclasses import dataclass
from typing import Dict, Optional

from .hlocost import HloCost

# --------------------------------------------------------------------------
# the analytic model
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class Peaks:
    """Published peak rates of one chip."""
    flops_per_s: float                       # bf16 matrix FLOP/s
    bytes_per_s: float                       # HBM bytes/s
    int8_ops_per_s: Optional[float] = None   # int8 matrix ops/s


# per-chip peaks keyed by ``jax.Device.device_kind``.  TPU v5e ("TPU v5
# lite" to JAX): Google Cloud documentation, "TPU v5e" — 197 TFLOP/s
# bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s.
DEVICE_PEAKS: Dict[str, Peaks] = {
    "TPU v5 lite": Peaks(flops_per_s=197e12, bytes_per_s=819e9,
                         int8_ops_per_s=393e12),
}


def peaks_for(device_kind: str) -> Peaks:
    """The published peaks of ``device_kind``; a kind that is not in
    :data:`DEVICE_PEAKS` is an error, never a default."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(DEVICE_PEAKS)}") from None


def default_peaks() -> Peaks:
    """Peaks of the default device (:func:`peaks_for` its kind)."""
    import jax

    return peaks_for(jax.devices()[0].device_kind)


def roofline_seconds(flops: float, nbytes: float,
                     peaks: Optional[Peaks] = None) -> float:
    """The roofline time floor: max of compute and memory terms."""
    peaks = peaks if peaks is not None else default_peaks()
    return max(flops / peaks.flops_per_s, nbytes / peaks.bytes_per_s)


def achieved_fraction(flops: float, nbytes: float, measured_s: float,
                      peaks: Optional[Peaks] = None) -> Optional[float]:
    """roofline_floor / measured — 1.0 means running at the roofline;
    None when the measurement is missing or degenerate."""
    if not measured_s or measured_s <= 0:
        return None
    return roofline_seconds(flops, nbytes, peaks) / measured_s


def program_summary(lowered, measured_s: Optional[float] = None,
                    peaks: Optional[Peaks] = None) -> dict:
    """FLOP/byte estimate + roofline verdict for one lowered program.

    ``lowered`` is a ``jax.stages.Lowered``/``Compiled`` (or an
    :class:`HloCost` already built from one).  ``measured_s`` is the
    program's device time (from a profiler trace; ``repro.obs`` spans
    time host work only) to compare against the floor."""
    cost = lowered if isinstance(lowered, HloCost) else HloCost.from_lowered(lowered)
    peaks = peaks if peaks is not None else default_peaks()
    floor = roofline_seconds(cost.flops, cost.bytes, peaks)
    compute_s = cost.flops / peaks.flops_per_s
    memory_s = cost.bytes / peaks.bytes_per_s
    return {
        "flops": cost.flops,
        "bytes": cost.bytes,
        "roofline_s": floor,
        "bound": "compute" if compute_s >= memory_s else "memory",
        "measured_s": measured_s,
        "achieved_fraction": achieved_fraction(
            cost.flops, cost.bytes, measured_s or 0.0, peaks),
    }


# --------------------------------------------------------------------------
# the legacy dry-run table CLI
# --------------------------------------------------------------------------

ARCH_ORDER = [
    "deepseek_v2_lite_16b", "mixtral_8x7b", "qwen2_vl_72b", "smollm_360m",
    "granite_20b", "gemma3_27b", "qwen3_0p6b", "jamba_v0_1_52b",
    "hubert_xlarge", "mamba2_2p7b", "kagen_er_gnm",
]
SHAPE_ORDER = ["train_4k", "prefill_32k", "decode_32k", "long_500k", "gen"]

HBM_PER_CHIP = 16 * 2**30  # v5e


def fmt_s(x):
    if x is None:
        return "-"
    if x >= 1:
        return f"{x:.2f}"
    if x >= 1e-3:
        return f"{x*1e3:.1f}m"
    return f"{x*1e6:.0f}u"


def load(dirname):
    rows = {}
    for f in glob.glob(os.path.join(dirname, "*.json")):
        with open(f) as fh:
            d = json.load(fh)
        key = (d.get("arch"), d.get("shape"), bool(d.get("multi_pod")))
        rows[key] = d
    return rows


def make_table(rows, multi_pod=False):
    out = []
    hdr = ("| arch | shape | compute_s | memory_s | collective_s | dominant | "
           "peak GB/chip | fits | useful-flops ratio | bottleneck note |")
    sep = "|" + "---|" * 10
    out.append(hdr)
    out.append(sep)
    for arch in ARCH_ORDER:
        for shape in SHAPE_ORDER:
            d = rows.get((arch, shape, multi_pod))
            if d is None:
                d = rows.get((arch, f"n2^30_m2^34", multi_pod)) if shape == "gen" and arch == "kagen_er_gnm" else None
            if d is None:
                continue
            if d["status"] == "skipped":
                out.append(f"| {arch} | {shape} | - | - | - | skipped | - | - | - | {d['reason']} |")
                continue
            if d["status"] != "ok":
                out.append(f"| {arch} | {shape} | - | - | - | ERROR | - | - | - | {d.get('stderr','')[:40]} |")
                continue
            r = d["roofline"]
            peak = d.get("memory", {}).get("peak_per_device")
            peak_gb = f"{peak/2**30:.1f}" if peak else "-"
            fits = "yes" if (peak or 0) <= HBM_PER_CHIP else "NO"
            ratio = d.get("useful_flops_ratio")
            ratio_s = f"{ratio:.2f}" if ratio else "-"
            note = _note(d)
            out.append(
                f"| {arch} | {shape} | {fmt_s(r['compute_s'])} | {fmt_s(r['memory_s'])} "
                f"| {fmt_s(r['collective_s'])} | {d['dominant'].replace('_s','')} "
                f"| {peak_gb} | {fits} | {ratio_s} | {note} |"
            )
    return "\n".join(out)


def _note(d):
    dom = d["dominant"]
    r = d["roofline"]
    colls = d.get("collectives", {})
    if d.get("zero_collectives"):
        return "communication-free by construction (asserted)"
    if dom == "collective_s":
        big = max(colls.items(), key=lambda kv: kv[1]["bytes"])[0] if colls else "?"
        return f"dominated by {big}; cut via RS/AG + bf16 gathers"
    if dom == "memory_s":
        return "bytes-proxy bound; fuse/avoid materialized intermediates"
    return "compute-bound: near roofline if overlap hides comm"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dir", default="results/dryrun")
    ap.add_argument("--multi-pod", action="store_true")
    args = ap.parse_args()
    rows = load(args.dir)
    print(make_table(rows, args.multi_pod))


if __name__ == "__main__":
    main()
