"""Tracing / profiling hooks.

The reference instruments every public entry with NVTX ranges
(``CUDF_FUNC_RANGE()`` at ``NativeParquetJni.cpp:136,392,469,524,553,578,668``)
and exposes a Java-side toggle (``pom.xml:86,490``).  The TPU-native
equivalents are ``jax.named_scope`` (shows up in XLA HLO + xprof) and
``jax.profiler`` trace annotations; both degrade to no-ops off-device.

The knob (``SPARK_RAPIDS_TPU_TRACE``) is read at import AND re-checkable at
runtime: :func:`set_enabled` flips it (parity with
``structured_log.configure`` — tests and the hot knob need the toggle
without a process restart).

A ``@traced`` entry opens ONE range per call.  With metrics on it is a
``utils.metrics`` span, which carries the profiler annotation itself; with
metrics off (the default) it is :func:`func_range`.  Either way the
profiler sees ``srjt:<name>`` (``metrics.PREFIX``), so whoever reads a
profile needs one rule.  With ``utils.structured_log`` on, each call also
emits one event record with its wall-time duration (the RMM-logging/spdlog
analog).
"""

from __future__ import annotations

import contextlib
import functools
import os
import time
from typing import Optional

import jax

from . import metrics


def _read_env() -> bool:
    return os.environ.get("SPARK_RAPIDS_TPU_TRACE", "1") not in ("0", "false")


_ENABLED = _read_env()


def enabled() -> bool:
    return _ENABLED


def set_enabled(on: Optional[bool] = None) -> None:
    """Toggle tracing at runtime; ``None`` re-reads the env knob."""
    global _ENABLED
    _ENABLED = _read_env() if on is None else bool(on)


@contextlib.contextmanager
def func_range(name: str):
    """NVTX-range analog: a named scope visible in HLO, and the annotation
    ``srjt:<name>`` in xprof traces."""
    if not _ENABLED:
        yield
        return
    with jax.named_scope(name), \
            jax.profiler.TraceAnnotation(metrics.PREFIX + name):
        yield


def traced(name: str | None = None):
    """Decorator form of :func:`func_range` (CUDF_FUNC_RANGE analog).

    Also feeds the structured-log knob (``SPARK_RAPIDS_TPU_LOG``,
    ``utils.structured_log``): when enabled, each call emits one event
    record with wall-time duration — the RMM-logging/spdlog analog.
    With metrics on (``SPARK_RAPIDS_TPU_METRICS``, ``utils.metrics``),
    the call's range is one span in the current span tree, and the span
    opens the annotation; the HLO scope stays as :func:`func_range` has
    it, so a compiled program is the same whichever recorder is on."""

    def wrap(fn):
        scope = name or fn.__qualname__

        @functools.wraps(fn)
        def inner(*args, **kwargs):
            from . import structured_log as slog
            rec = metrics.recording()
            log = slog.enabled()
            if not (rec or log):
                with func_range(scope):
                    return fn(*args, **kwargs)
            t0 = time.perf_counter()
            if rec:
                hlo = (jax.named_scope(scope) if _ENABLED
                       else contextlib.nullcontext())
                with metrics.span(scope), hlo:
                    out = fn(*args, **kwargs)
            else:
                with func_range(scope):
                    out = fn(*args, **kwargs)
            if log:
                slog.event(scope, duration_s=time.perf_counter() - t0)
            return out

        return inner

    return wrap
