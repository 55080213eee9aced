"""The ``compile_ledger`` reader: sums over the ``xla:<fun>`` entries of the
program's compile ledger, on a synthetic store and on the program's own.

``JAX_PLATFORMS=cpu python3 -m pytest chipbench/tests -q``; tier-1 runs it
through ``tests/test_instrument_compile_ledger_reader.py``.
"""

import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench.readers import compile_ledger  # noqa: E402

# the four set-up metrics the reader exists for, as their metric files would
# give its params (PERF.md §7: they wait on a benchmark PR)
PARAMS = {
    "compile_backend_s": {"add": ["compile_ms"], "scale": 0.001},
    "compile_lower_s": {"add": ["trace_ms", "lower_ms"], "scale": 0.001},
    "compile_programs": {"add": ["compiles"]},
    "compile_cache_misses": {"add": ["compiles"], "subtract": ["cache_hits"]},
}


def _ctx(ledger: dict) -> dict:
    return {"program_metrics": types.SimpleNamespace(
        ledger_snapshot=lambda: {k: dict(v) for k, v in ledger.items()})}


LEDGER = {
    "xla:f": {"traces": 1, "trace_ms": 2.0, "lowers": 1, "lower_ms": 3.0,
              "compiles": 1, "compile_ms": 500.0, "cache_hits": 0},
    "xla:g": {"traces": 4, "trace_ms": 8.0, "lowers": 4, "lower_ms": 12.0,
              "compiles": 4, "compile_ms": 40.0, "cache_hits": 4},
    # a plan's entry: its own trace_ms and cache_hits are not the compiler's
    "3f9a0c": {"captures": 1, "capture_ms": 900.0, "traces": 1,
               "trace_ms": 700.0, "cache_hits": 12, "runs": 30},
}


@pytest.mark.parametrize("name,want", [
    ("compile_backend_s", 0.54), ("compile_lower_s", 0.025),
    ("compile_programs", 5), ("compile_cache_misses", 1)])
def test_sums_the_xla_entries_and_leaves_the_plans_out(name, want):
    assert compile_ledger.read(_ctx(LEDGER), PARAMS[name]) == \
        pytest.approx(want)


def test_a_warm_cache_reads_zero_misses_not_nothing():
    warm = {"xla:g": LEDGER["xla:g"]}
    assert compile_ledger.read(_ctx(warm),
                               PARAMS["compile_cache_misses"]) == 0


@pytest.mark.parametrize("name", sorted(PARAMS))
def test_nothing_without_an_xla_entry(name):
    plans_only = {"3f9a0c": LEDGER["3f9a0c"]}
    assert compile_ledger.read(_ctx(plans_only), PARAMS[name]) is None
    assert compile_ledger.read(_ctx({}), PARAMS[name]) is None


def test_reads_what_the_program_recorded():
    import jax
    import numpy as np
    from spark_rapids_jni_tpu.utils import metrics
    metrics.set_enabled(True)
    metrics.reset()
    try:
        def probe_reader_fn(x):
            return x * 7.0 - 1.0
        jax.jit(probe_reader_fn)(np.arange(4, dtype=np.float32))
        ctx = {"program_metrics": metrics}
        got = {n: compile_ledger.read(ctx, p) for n, p in PARAMS.items()}
    finally:
        metrics.reset()
        metrics.set_enabled(None)
    assert got["compile_programs"] >= 1
    assert 0 <= got["compile_cache_misses"] <= got["compile_programs"]
    assert got["compile_backend_s"] > 0 and got["compile_lower_s"] > 0
