"""chipbench: the benchmark of the TPU Spark acceleration layer.

One command runs one cell once (``python3 -m chipbench --workload <cell>
--seed <n> --seconds <s> --trace <0|1>``); ``BENCHMARK.json`` at the root of
the checkout names the cells.  Everything that decides a number lives here:
data and traffic generation, the plain references, the window loop, the
reduction from spans, counters and the profiler's trace to metrics, the
table of peaks and the byte counts of the rooflines.  From the program it
takes only the entry points under test, ``utils/metrics.py`` and the
fallback tallies named in ``guards.py``.
"""
