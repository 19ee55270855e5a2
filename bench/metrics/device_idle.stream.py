"""Share of the profiler half of the traced window in which no
operation of the program under test ran on the device: 1 - (union of
its op intervals) / (traced stretch), averaged over the chips used.
The benchmark's own consumer programs (``jit_bench_*``) count as idle,
so that the yardstick's work never passes for the program's."""


def read(rd):
    t = rd.trace
    return (None if t is None or t.window_s <= 0
            else 100.0 * (1.0 - t.program_busy_s / t.window_s))
