"""What must not move in a run: the program's fallback tallies (over the
whole run) and its compile counters (inside the window).  The one place
where chipbench reads program internals other than ``utils/metrics.py``."""

from __future__ import annotations

WINDOW_COUNTERS = ("compiled.capture", "exec.plan_cache.miss")
XLA_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_xla_compiles = []          # one entry a program; the listener once a process


def watch_xla_compiles():
    """Count, from here on, every program that JAX hands to the backend's
    compiler or fetches from its persistent cache."""
    import jax.monitoring

    def listen(event, seconds, **_):
        if event == XLA_COMPILE_EVENT:
            _xla_compiles[0] += 1
    if not _xla_compiles:
        _xla_compiles.append(0)
        jax.monitoring.register_event_duration_secs_listener(listen)


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
    return {"jax.backend_compile": float(sum(_xla_compiles)),
            **{k: metrics.counter_value(k) for k in WINDOW_COUNTERS}}


def moved(before: dict, after: dict) -> float:
    return float(sum(abs(after[k] - before[k]) for k in before))
