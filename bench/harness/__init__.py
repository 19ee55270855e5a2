"""The benchmark's harness: loading a cell from ``BENCHMARK.json``, the
device checks, the compile counter, the on-device chunk digest, the
profiler-trace reduction, the table of peaks and the run itself."""
