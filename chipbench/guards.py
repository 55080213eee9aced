"""What must not move in a run: the program's fallback tallies (over the
whole run) and its compile counters (inside the window).  The one place
where chipbench reads program internals other than ``utils/metrics.py``."""

from __future__ import annotations

WINDOW_COUNTERS = ("compiled.capture", "exec.plan_cache.miss")


def fallbacks() -> dict[str, float]:
    """Tallies of every way a call can be served by something other than
    the device path it was sent to."""
    from spark_rapids_jni_tpu.rowconv import xpack, xpallas
    from spark_rapids_jni_tpu.utils import metrics
    return {
        "parquet.host_fallback_cols":
            metrics.counter_value("parquet.host_fallback_cols"),
        "xpack.fallbacks": float(sum(xpack.fallback_counts.values())),
        "xpallas.fallbacks": float(xpallas._counts["fallbacks"]),
    }


def compiles() -> dict[str, float]:
    from spark_rapids_jni_tpu.utils import metrics
    return {k: metrics.counter_value(k) for k in WINDOW_COUNTERS}


def moved(before: dict, after: dict) -> float:
    return float(sum(abs(after[k] - before[k]) for k in before))
