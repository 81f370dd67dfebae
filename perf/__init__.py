"""The benchmark (BENCHMARK.json names this directory in ``paths``).

The yardstick lives here, where a PR that changes the program cannot
change it: the command (``run.py``), traffic generation (``traffic/``),
the reduction from the profiler's trace to metrics (``trace_reduce.py``,
``layer_metrics/``), the table of peaks (``peaks.py``), the functions
that compute required operations and bytes (``flops.py``), and each
model family's plain reference and parity check (``families/``).

Everything that belongs to one configuration, cell, traffic mix or
per-layer metric is a file of its own, found by the name BENCHMARK.json
gives it; ``run.py`` holds none of those names.  Importing this package
touches no JAX backend.
"""
