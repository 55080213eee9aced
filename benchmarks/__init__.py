"""Seeded data generators and pandas twins that the tests, ``chip_smoke.py``
and ``tools/tpu_check.py`` share.  The benchmark is not here: ``BENCHMARK.json``
and ``chipbench/`` (``python3 -m chipbench --workload <cell>``; PERF.md §2).
"""
