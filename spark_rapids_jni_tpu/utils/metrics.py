"""Process-wide metrics registry + hierarchical span recorder.

The reference instruments every public entry with NVTX ranges and threads
an RMM logging level through the build (SURVEY §5.5); this module is the
query-level half of that story the TPU rebuild was missing: counters
(join-engine choice, build-index cache hits, tape lengths, pages decoded,
bytes shuffled), gauges (HBM live-byte watermarks), histograms (expansion
pair totals), and a per-query SPAN TREE: a parent/child stage hierarchy
exportable as Chrome-trace JSON (``chrome://tracing`` / Perfetto-loadable)
and as a structured summary dict.  Every span also opens a
``jax.profiler.TraceAnnotation`` named ``srjt:<name>``, so a profile
holds the same spans on the host plane, on the clock of the device
plane's ``XLA Ops`` (``utils.tracing.traced`` entries are spans too).

Knobs
-----
  SPARK_RAPIDS_TPU_METRICS=0|1        (default off)
  SPARK_RAPIDS_TPU_METRICS_TRACE=<p>  default export path for
                                      :func:`export_chrome_trace`

Discipline
----------
* **Zero overhead when disabled.**  Every public entry is gated on ONE
  module-level bool; :func:`span` returns a shared ``nullcontext`` without
  allocating, counters return before touching any dict.
* **Record around dispatch, never inside compiled bodies.**  All recording
  is Python-side (eager orchestration, capture runs, dispatch wrappers).
  Sites that re-trace under ``jax.jit`` replay (``utils.syncs`` replay
  mode) are skipped automatically — a replay trace would otherwise
  double-count the capture run's events and measure trace time instead of
  run time.  The deliberate exceptions are
  ``count(..., in_trace=True)`` (e.g. ``compiled.recompile``) and the
  compile listeners (``jax.*`` spans, ``xla:`` ledger entries), which
  record trace-time occurrences on purpose.
* No device syncs: values passed in must already be host ints/floats
  (the op library's sizes all flow through ``syncs.scalar`` anyway).
"""

from __future__ import annotations

import collections
import contextlib
import itertools
import json
import os
import re
import threading
import time

from jax.profiler import TraceAnnotation

from ..analysis import sanitize
from . import knobs
from typing import Any, Optional

_enabled: bool = os.environ.get(
    "SPARK_RAPIDS_TPU_METRICS", "0").lower() not in ("0", "off", "false", "")

_lock = sanitize.tracked_lock("utils.metrics")
_counters: dict[str, float] = {}
_gauges: dict[str, float] = {}
_hists: dict[str, dict] = {}        # name -> {count,total,min,max,buckets}
# bounded (ts, value) sample tails per histogram, feeding the
# rolling-window percentile path (the SLO watchdog's quantiles); the
# log2 buckets above stay the process-lifetime story
_WINDOW_N = max(knobs.get("SRJT_METRICS_WINDOW_N"), 16)
_samples: dict[str, "collections.deque[tuple[float, float]]"] = {}

_EPOCH = time.perf_counter()        # trace time base (ts exported rel. us)

# every span's name on the profiler (``tracing.func_range`` too): one rule
# for whoever reads a profile
PREFIX = "srjt:"
_ROOTS_MAX = 4096                   # completed root trees kept (newest)

_tls = threading.local()            # per-thread open-span stack
# completed root spans (all threads)
_roots: "collections.deque[Span]" = collections.deque(maxlen=_ROOTS_MAX)
_rids = itertools.count(1)          # request ids of roots not given one

# compile-cost ledger: plan fingerprint → summed cost fields (capture_ms,
# trace_ms, traces, first_dispatch_ms, runs, cache_hits, ...) — the
# per-plan attribution of where compilation wall time went
# (``models/compiled.py`` and ``exec/plan_cache.py`` feed it); beside the
# plans, ``xla:<fun>`` → traces, trace_ms, lowers, lower_ms, compiles,
# compile_ms, cache_hits for every program JAX compiles (the listeners below)
_ledger: dict[str, dict[str, float]] = {}

# installed by ``plan/profile.py`` when that module loads; ops-layer
# sites report into the active node profile through :func:`profile_op`
# without importing plan/ (no cycle, no cost when profiling never loads)
_profile_op_hook = None


def enabled() -> bool:
    return _enabled


def set_enabled(on: Optional[bool] = None) -> None:
    """Toggle metrics at runtime; ``None`` re-reads the env knob."""
    global _enabled
    if on is None:
        _enabled = os.environ.get(
            "SPARK_RAPIDS_TPU_METRICS",
            "0").lower() not in ("0", "off", "false", "")
    else:
        _enabled = bool(on)


def recording() -> bool:
    """True when events should be recorded NOW: metrics on, and not inside
    a ``syncs.replay`` re-trace (which re-runs the already-recorded plan
    Python under ``jax.jit``)."""
    if not _enabled:
        return False
    from . import syncs
    return syncs.mode() != "replay"


def reset() -> None:
    """Drop all counters, gauges, histograms, completed spans, and the
    compile-cost ledger."""
    with _lock:
        _counters.clear()
        _gauges.clear()
        _hists.clear()
        _samples.clear()
        _roots.clear()
        _ledger.clear()


def profile_op(name: str, **fields) -> None:
    """Report one op-level event (host-visible fields only — already
    resolved ints/strings, never device values) into the active plan-node
    profile.  A no-op until ``plan/profile.py`` is loaded AND a profile is
    active; ops-layer sites call this instead of importing plan/."""
    hook = _profile_op_hook
    if hook is not None:
        hook(name, **fields)


_profile_stage_hook = None      # plan/profile.stage once that module loads


def profile_stage(name: str, **fields):
    """Context manager opening a synthetic stage record (ml/ feature pack,
    train, predict) under the active plan-node profile — the non-plan-node
    twin of :func:`profile_op`, same no-import-cycle indirection.  Yields
    the open record (or None when no profile is active) so the stage can
    set output facts like ``out_rows``."""
    hook = _profile_stage_hook
    if hook is None:
        return contextlib.nullcontext()
    return hook(name, **fields)


# --- compile-cost ledger -----------------------------------------------------


def ledger_add(plan: str, *, in_trace: bool = False, **fields) -> None:
    """Accumulate numeric cost ``fields`` (ms, counts) under ``plan`` —
    a plan fingerprint or query name.  Same gating discipline as
    :func:`count`: no-op when disabled; ``in_trace=True`` records even
    under a replay trace (trace time is MEASURED at trace time)."""
    if not _enabled:
        return
    if not in_trace and not recording():
        return
    with _lock:
        e = _ledger.setdefault(plan, {})
        for k, v in fields.items():
            e[k] = e.get(k, 0) + v


def ledger_snapshot() -> dict[str, dict[str, float]]:
    """The compile-cost ledger as plain dicts (deep-copied):
    plan → {capture_ms, trace_ms, traces, first_dispatch_ms, runs,
    cache_hits, ...}.  ``traces`` counts jit (re)traces of the plan body;
    ``traces - 1`` of them are recompiles."""
    with _lock:
        return {k: dict(v) for k, v in _ledger.items()}


# --- counters / gauges / histograms ----------------------------------------


def count(name: str, value: float = 1, *, in_trace: bool = False) -> None:
    """Add ``value`` to counter ``name`` (no-op when disabled or replaying;
    ``in_trace=True`` records even under a replay trace — for events whose
    occurrence IS the trace, e.g. recompiles)."""
    if not _enabled:
        return
    if not in_trace and not recording():
        return
    with _lock:
        _counters[name] = _counters.get(name, 0) + value


def counter_value(name: str, default: float = 0) -> float:
    """Read counter ``name`` (``default`` when never incremented)."""
    with _lock:
        return _counters.get(name, default)


def gauge(name: str, value: float) -> None:
    """Set gauge ``name`` to ``value``."""
    if not recording():
        return
    with _lock:
        _gauges[name] = value


def gauge_max(name: str, value: float) -> None:
    """High-water gauge: keep the max of all samples (HBM watermarks)."""
    if not recording():
        return
    with _lock:
        if value > _gauges.get(name, float("-inf")):
            _gauges[name] = value


def observe(name: str, value: float) -> None:
    """Record one histogram observation (count/total/min/max + log2
    buckets — enough for skew questions without storing samples)."""
    if not recording():
        return
    with _lock:
        h = _hists.get(name)
        if h is None:
            h = _hists[name] = {"count": 0, "total": 0, "min": value,
                                "max": value, "buckets": {}}
        h["count"] += 1
        h["total"] += value
        h["min"] = min(h["min"], value)
        h["max"] = max(h["max"], value)
        b = f"<=2^{max(int(value), 0).bit_length()}"
        h["buckets"][b] = h["buckets"].get(b, 0) + 1
        s = _samples.get(name)
        if s is None:
            s = _samples[name] = collections.deque(maxlen=_WINDOW_N)
        s.append((time.monotonic(), value))


def percentile(name: str, q: float,
               window_s: Optional[float] = None) -> Optional[float]:
    """The ``q``-th percentile (0..100) of histogram ``name``.

    ``window_s=None`` (default) estimates over the PROCESS LIFETIME from
    the log2 buckets: the answer is the upper edge of the bucket holding
    the quantile, clamped to the observed min/max — coarse (≤2× off) but
    storage-free; serving latency tails need the magnitude, not the
    digit.

    ``window_s`` computes an EXACT quantile (nearest-rank) over the
    retained sample tail restricted to the last ``window_s`` seconds —
    the rolling view the SLO watchdog alarms on.  The tail is bounded
    (``SRJT_METRICS_WINDOW_N``, default 1024 newest observations), so a
    long window over a hot histogram sees the newest N, never unbounded
    storage.  Returns None when no observation falls in the window
    (including the empty-histogram case); a single in-window sample is
    its own percentile at every q."""
    q = min(max(q, 0.0), 100.0)
    if window_s is not None:
        cutoff = time.monotonic() - max(float(window_s), 0.0)
        with _lock:
            s = _samples.get(name)
            vals = [v for ts, v in s if ts >= cutoff] if s else []
        if not vals:
            return None
        vals.sort()
        rank = max(int(-(-len(vals) * q // 100)), 1)   # ceil, 1-based
        return float(vals[min(rank, len(vals)) - 1])
    with _lock:
        h = _hists.get(name)
        if h is None or not h["count"]:
            return None
        lo, hi, total = h["min"], h["max"], h["count"]
        edges = sorted((int(k.rsplit("^", 1)[1]), c)
                       for k, c in h["buckets"].items())
    target = total * q / 100.0
    cum = 0
    for exp, c in edges:
        cum += c
        if cum >= target:
            return float(min(max(float(1 << exp), lo), hi))
    return float(hi)


# --- span recorder ----------------------------------------------------------


class Span:
    """One timed range; completed children hang off ``children``.

    ``parent`` is the span this one hangs under: the innermost open span
    of the entering thread, or the one passed in — the way a span opened
    on another thread joins the call that caused it.  ``rid`` is the
    request id every span of one call shares: a root draws a fresh one
    unless given one, everything under it inherits."""

    __slots__ = ("name", "attrs", "t0", "dur", "tid", "children", "parent",
                 "rid", "_note")

    def __init__(self, name: str, attrs: dict,
                 parent: Optional["Span"] = None, rid=None):
        self.name = name
        self.attrs = attrs
        self.t0 = 0.0           # seconds since _EPOCH, set on __enter__
        self.dur = 0.0          # seconds
        self.tid = 0
        self.children: list[Span] = []
        self.parent = parent
        self.rid = rid
        self._note = None

    def annotate(self, **attrs) -> None:
        self.attrs.update(attrs)

    def __enter__(self) -> "Span":
        stack = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        if self.parent is None and stack:
            self.parent = stack[-1]
        if self.rid is None:
            self.rid = (self.parent.rid if self.parent is not None
                        else next(_rids))
        stack.append(self)
        self.tid = threading.get_ident()
        self._note = TraceAnnotation(PREFIX + self.name, rid=self.rid)
        self._note.__enter__()
        self.t0 = time.perf_counter() - _EPOCH
        return self

    def __exit__(self, *exc) -> None:
        self.dur = (time.perf_counter() - _EPOCH) - self.t0
        self._note.__exit__(*exc)
        self._note = None
        _tls.stack.pop()
        self._attach()

    def _attach(self) -> None:
        if self.parent is None:
            with _lock:
                _roots.append(self)
        else:
            # also from another thread than the parent's: one list.append
            # is atomic under the interpreter lock
            self.parent.children.append(self)

    def as_dict(self) -> dict:
        d = {"name": self.name, "start_ms": round(self.t0 * 1e3, 3),
             "dur_ms": round(self.dur * 1e3, 3), "tid": self.tid,
             "rid": self.rid}
        if self.attrs:
            d["attrs"] = dict(self.attrs)
        if self.children:
            d["children"] = [c.as_dict() for c in self.children]
        return d


_NOOP = contextlib.nullcontext()


def span(name: str, *, parent: Optional[Span] = None, rid=None, **attrs):
    """Context manager recording a span under the current thread's open
    span, under ``parent`` where one is passed (a span opened on another
    thread than its cause), or as a new root with request id ``rid``.
    Returns a shared no-op context when disabled or under a replay trace —
    zero allocation on the hot path."""
    if not recording():
        return _NOOP
    return Span(name, attrs, parent, rid)


def current_span() -> Optional[Span]:
    stack = getattr(_tls, "stack", None)
    return stack[-1] if stack else None


def annotate(**attrs) -> None:
    """Attach attributes to the innermost open span (no-op without one)."""
    if not recording():
        return
    sp = current_span()
    if sp is not None:
        sp.attrs.update(attrs)


@contextlib.contextmanager
def query_span(name: str, **attrs):
    """Root span for one query execution, with HBM watermark samples
    taken before and after (around dispatch — never inside it)."""
    if not recording():
        yield None
        return
    pre = sample_hbm("pre")
    with span(f"query:{name}", **attrs) as sp:
        yield sp
    post = sample_hbm("post")
    if pre is not None and post is not None:
        sp.annotate(hbm_pre_bytes=pre, hbm_post_bytes=post)


# --- the compile path: JAX's own compile events ------------------------------
#
# JAX reports each of three phases twice, with the program's ``fun_name``: a
# start event (``record_scalar``, value = wall-clock start) and a time-span
# event (``record_event_time_span``) at its end.
#   * ``jaxpr_trace_duration``: Python tracing of a function to a jaxpr;
#   * ``jaxpr_to_mlir_module_duration``: lowering the jaxpr to MLIR;
#   * ``backend_compile_duration``: ``compile_or_get_cached``, the XLA
#     compile or the persistent cache's fetch; a fetch fires
#     ``/jax/compilation_cache/cache_hits`` inside it, on the same thread.
# Each outermost phase becomes a span (``jax.trace`` / ``jax.lower`` /
# ``jax.compile``) under the thread's open span, and adds to the ledger entry
# ``xla:<fun>``.  Phases nest (the trace of ``f`` holds the traces of the jnp
# functions it calls): a phase inside one of its own kind on the same thread
# is neither a span nor a count.  Recorded while metrics are on, replay
# included, since a plan compiles while ``syncs.replay`` traces it.
#
# Memory: span attrs and ledger fields hold only str/bool/int/float, never a
# jaxpr, module, executable or array.  ``chipbench`` keeps metrics on in every
# run and the span store keeps 4096 root trees for the life of the process,
# so whatever these records referenced would live through every window; the
# ledger is one entry a program name, bounded by the programs, not the calls.

_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration":
        ("jax.trace", "traces", "trace_ms"),
    "/jax/core/compile/jaxpr_to_mlir_module_duration":
        ("jax.lower", "lowers", "lower_ms"),
    "/jax/core/compile/backend_compile_duration":
        ("jax.compile", "compiles", "compile_ms"),
}
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_FAULTS = "metrics.compile_listener.faults"   # a listener that failed
_JIT_WRAP = re.compile(r"^(?:jit|pjit|pmap|xla_pmap)\((.*)\)$")
_listening = False


def _program_name(fun_name) -> str:
    """``jit(f)`` (how the lowering and compile name it) → ``f`` (how the
    trace names it)."""
    name = str(fun_name)
    m = _JIT_WRAP.match(name)
    while m is not None:
        name = m.group(1)
        m = _JIT_WRAP.match(name)
    return name


def _phases_open() -> dict:
    """This thread's open phases: event → [depth, span or None]."""
    open_ = getattr(_tls, "phases", None)
    if open_ is None:
        open_ = _tls.phases = {}
    return open_


def _on_phase_start(event: str, value, **kw) -> None:
    phase = _PHASES.get(event)
    if phase is None:
        return
    try:
        open_ = _phases_open()
        frame = open_.get(event)
        if frame is not None:
            frame[0] += 1
            return
        sp = None
        if _enabled:
            attrs = {"fun": _program_name(kw.get("fun_name", ""))}
            if phase[0] == "jax.compile":
                attrs["cache_hit"] = False
            sp = Span(phase[0], attrs)
            sp.__enter__()
        open_[event] = [1, sp]
    except Exception:       # noqa: BLE001 — never raise into JAX
        count(_FAULTS, in_trace=True)


def _on_phase_end(event: str, start: float, end: float, **kw) -> None:
    phase = _PHASES.get(event)
    if phase is None:
        return
    try:
        open_ = _phases_open()
        frame = open_.get(event)
        if frame is not None:
            frame[0] -= 1
            if frame[0] > 0:
                return
            del open_[event]
            sp = frame[1]
            if sp is None:
                return
            sp.__exit__(None, None, None)
        else:
            # no start event seen: the whole span, moved onto our clock
            if not _enabled:
                return
            attrs = {"fun": _program_name(kw.get("fun_name", ""))}
            if phase[0] == "jax.compile":
                attrs["cache_hit"] = bool(getattr(_tls, "cache_hit", False))
            parent = current_span()
            sp = Span(phase[0], attrs, parent,
                      parent.rid if parent is not None else next(_rids))
            sp.tid = threading.get_ident()
            sp.dur = float(end) - float(start)
            sp.t0 = (time.perf_counter() - _EPOCH) - (time.time()
                                                      - float(start))
            sp._attach()
        _tls.cache_hit = False
        fields = {phase[1]: 1, phase[2]: (float(end) - float(start)) * 1e3}
        if phase[0] == "jax.compile":
            fields["cache_hits"] = int(sp.attrs["cache_hit"])
        ledger_add("xla:" + sp.attrs["fun"], in_trace=True, **fields)
    except Exception:       # noqa: BLE001 — never raise into JAX
        count(_FAULTS, in_trace=True)


def _on_event(event: str, **kw) -> None:
    if event != _CACHE_HIT_EVENT:
        return
    try:
        frame = _phases_open().get(
            "/jax/core/compile/backend_compile_duration")
        if frame is None:
            _tls.cache_hit = True       # for a build with no start event
        elif frame[1] is not None:
            frame[1].attrs["cache_hit"] = True
    except Exception:       # noqa: BLE001 — never raise into JAX
        count(_FAULTS, in_trace=True)


def install_compile_listeners() -> None:
    """Register the compile listeners with ``jax.monitoring``; once a
    process, however often called."""
    global _listening
    if _listening:
        return
    _listening = True
    import jax.monitoring
    jax.monitoring.register_scalar_listener(_on_phase_start)
    jax.monitoring.register_event_time_span_listener(_on_phase_end)
    jax.monitoring.register_event_listener(_on_event)


# --- HBM accounting ---------------------------------------------------------


def sample_hbm(tag: str = "sample") -> Optional[int]:
    """Sample live device memory: sum of ``jax.live_arrays()`` byte sizes
    plus per-device allocator stats where the backend exposes them.  On
    backends with no ``memory_stats()`` (CPU, some PJRT builds) the
    per-device gauges fall back to an estimate from ``jax.live_arrays()``
    grouped by placement (sharded arrays split evenly across their
    devices).  Updates ``hbm.live_bytes`` and the ``hbm.live_bytes.peak``
    high-water gauge; returns the live-byte total (None when disabled)."""
    if not recording():
        return None
    import jax
    arrays = []
    try:
        arrays = list(jax.live_arrays())
    except Exception:
        pass
    live = sum(int(getattr(a, "nbytes", 0) or 0) for a in arrays)
    gauge("hbm.live_bytes", live)
    gauge_max("hbm.live_bytes.peak", live)
    any_stats = False
    try:
        for i, d in enumerate(jax.local_devices()):
            stats = getattr(d, "memory_stats", None)
            stats = stats() if callable(stats) else None
            if not stats:
                continue
            in_use = stats.get("bytes_in_use")
            if in_use is not None:
                any_stats = True
                gauge(f"hbm.device{i}.bytes_in_use", int(in_use))
                gauge_max(f"hbm.device{i}.peak_bytes_in_use",
                          int(stats.get("peak_bytes_in_use", in_use)))
    except Exception:
        pass
    if not any_stats:
        # allocator-stats fallback: estimate per-device occupancy from the
        # live-array census so the gauges exist on every backend
        try:
            devs = jax.local_devices()
            index = {d: i for i, d in enumerate(devs)}
            per = [0] * len(devs)
            for a in arrays:
                try:
                    placement = list(a.devices())
                except Exception:
                    continue
                n = int(getattr(a, "nbytes", 0) or 0)
                if not placement or not n:
                    continue
                share = n // len(placement)   # sharded: even split
                for d in placement:
                    i = index.get(d)
                    if i is not None:
                        per[i] += share
            for i, v in enumerate(per):
                gauge(f"hbm.device{i}.bytes_in_use", v)
                gauge_max(f"hbm.device{i}.peak_bytes_in_use", v)
        except Exception:
            pass
    return live


# --- export -----------------------------------------------------------------


def snapshot() -> dict:
    """Counters/gauges/histograms/ledger as plain dicts (deep-copied)."""
    with _lock:
        return {"counters": dict(_counters), "gauges": dict(_gauges),
                "histograms": {k: {**v, "buckets": dict(v["buckets"])}
                               for k, v in _hists.items()},
                "ledger": {k: dict(v) for k, v in _ledger.items()}}


def span_roots(window_s: Optional[float] = None) -> list[dict]:
    """Completed root span trees (dict form), in completion order: the
    newest ``_ROOTS_MAX`` of them, or of those the ones that began in the
    last ``window_s`` seconds."""
    with _lock:
        roots = list(_roots)
    if window_s is not None:
        cutoff = (time.perf_counter() - _EPOCH) - max(float(window_s), 0.0)
        roots = [s for s in roots if s.t0 >= cutoff]
    return [s.as_dict() for s in roots]


def _walk(spans, fn):
    for s in spans:
        fn(s)
        _walk(s.children, fn)


def stage_breakdown() -> dict[str, dict]:
    """Aggregate all completed spans by name: call count, total/max ms."""
    agg: dict[str, dict] = {}

    def add(s: Span):
        e = agg.setdefault(s.name, {"count": 0, "total_ms": 0.0,
                                    "max_ms": 0.0})
        e["count"] += 1
        e["total_ms"] += s.dur * 1e3
        e["max_ms"] = max(e["max_ms"], s.dur * 1e3)

    with _lock:
        _walk(list(_roots), add)
    for e in agg.values():
        e["total_ms"] = round(e["total_ms"], 3)
        e["max_ms"] = round(e["max_ms"], 3)
    return agg


def summary() -> dict:
    """One structured dict: counters, gauges, histograms, span aggregate."""
    return {**snapshot(), "spans": stage_breakdown()}


def chrome_trace() -> dict:
    """The recorded spans + counters in Chrome-trace (JSON object) format.

    Spans become complete ("ph": "X") events with microsecond ts/dur;
    counters/gauges ride along both as trailing counter events and under
    the ``srjtCounters``/``srjtGauges``/``srjtHistograms`` keys (the
    object format ignores unknown top-level keys, so Perfetto and
    ``chrome://tracing`` both load it and ``tools/trace_report.py`` gets
    the registry without re-aggregating events)."""
    pid = os.getpid()
    events: list[dict] = []
    end_us = 0.0

    def emit(s: Span):
        nonlocal end_us
        ev = {"name": s.name, "cat": "srjt", "ph": "X", "pid": pid,
              "tid": s.tid, "ts": round(s.t0 * 1e6, 3),
              "dur": round(s.dur * 1e6, 3)}
        if s.attrs:
            ev["args"] = {k: v for k, v in s.attrs.items()}
        events.append(ev)
        end_us = max(end_us, (s.t0 + s.dur) * 1e6)

    with _lock:
        _walk(list(_roots), emit)
        counters = dict(_counters)
        gauges = dict(_gauges)
        hists = {k: {**v, "buckets": dict(v["buckets"])}
                 for k, v in _hists.items()}
        ledger = {k: dict(v) for k, v in _ledger.items()}
    for k, v in sorted(counters.items()):
        events.append({"name": k, "cat": "srjt", "ph": "C", "pid": pid,
                       "ts": round(end_us, 3), "args": {"value": v}})
    return {"traceEvents": events, "displayTimeUnit": "ms",
            "srjtCounters": counters, "srjtGauges": gauges,
            "srjtHistograms": hists, "srjtLedger": ledger}


def export_chrome_trace(path: Optional[str] = None) -> str:
    """Write :func:`chrome_trace` as JSON; returns the path written.
    Default path: ``SPARK_RAPIDS_TPU_METRICS_TRACE`` or
    ``srjt_trace.json``."""
    path = path or os.environ.get("SPARK_RAPIDS_TPU_METRICS_TRACE",
                                  "srjt_trace.json")
    with open(path, "w") as f:
        json.dump(chrome_trace(), f)
    return path


# --- Prometheus export ------------------------------------------------------

_PROM_BAD = re.compile(r"[^a-zA-Z0-9_:]")


def _prom_name(name: str) -> str:
    """``exec.queue_wait_ms`` → ``srjt_exec_queue_wait_ms`` (the
    text-format metric-name grammar admits ``[a-zA-Z_:][a-zA-Z0-9_:]*``)."""
    n = "srjt_" + _PROM_BAD.sub("_", name)
    if not re.match(r"[a-zA-Z_:]", n[0]):
        n = "_" + n
    return n


def _prom_num(v: float) -> str:
    f = float(v)
    return repr(int(f)) if f == int(f) and abs(f) < 2 ** 53 else repr(f)


def _prom_label(v: str) -> str:
    """Escape a label VALUE for the text exposition grammar (the CI lint
    admits ``[^"]*`` between the quotes — strip anything that would
    close or continue the quoted string)."""
    return str(v).replace("\\", "_").replace('"', "_").replace("\n", "_")


def to_prometheus() -> str:
    """The registry in Prometheus text exposition format (version 0.0.4).

    Counters and gauges export directly; every histogram exports as a
    native Prometheus histogram — cumulative ``_bucket{le="..."}`` series
    built from the log2 buckets, plus ``_sum`` and ``_count`` — so a
    scrape of the serving runtime yields rate()-able latency and
    admission series without any sidecar.  The output is linted against
    the grammar in ``tests/test_metrics.py``."""
    with _lock:
        counters = dict(_counters)
        gauges = dict(_gauges)
        hists = {k: {**v, "buckets": dict(v["buckets"])}
                 for k, v in _hists.items()}
        ledger = {k: dict(v) for k, v in _ledger.items()}
    lines: list[str] = []
    for name, v in sorted(counters.items()):
        p = _prom_name(name)
        lines.append(f"# TYPE {p} counter")
        lines.append(f"{p} {_prom_num(v)}")
    for name, v in sorted(gauges.items()):
        p = _prom_name(name)
        lines.append(f"# TYPE {p} gauge")
        lines.append(f"{p} {_prom_num(v)}")
    for name, h in sorted(hists.items()):
        p = _prom_name(name)
        lines.append(f"# TYPE {p} histogram")
        edges = sorted((int(k.rsplit("^", 1)[1]), c)
                       for k, c in h["buckets"].items())
        cum = 0
        for exp, c in edges:
            cum += c
            lines.append(f'{p}_bucket{{le="{float(1 << exp)!r}"}} {cum}')
        lines.append(f'{p}_bucket{{le="+Inf"}} {h["count"]}')
        lines.append(f"{p}_sum {_prom_num(h['total'])}")
        lines.append(f"{p}_count {h['count']}")
    if ledger:
        # compile-cost attribution: one labeled series per (plan, field)
        # — `rate(srjt_compile_ledger{kind="trace_ms"}[5m])` answers "who
        # is recompiling" straight off a scrape
        p = "srjt_compile_ledger"
        lines.append(f"# TYPE {p} counter")
        for plan, e in sorted(ledger.items()):
            for k, v in sorted(e.items()):
                lines.append(f'{p}{{plan="{_prom_label(plan)}",'
                             f'kind="{_prom_label(k)}"}} {_prom_num(v)}')
    return "\n".join(lines) + ("\n" if lines else "")


_http_server = None
_http_lock = sanitize.tracked_lock("utils.metrics.http")


def start_http_server(port: Optional[int] = None):
    """Serve :func:`to_prometheus` on ``http://0.0.0.0:<port>/metrics``
    from a daemon thread (the ops scrape surface; ``SRJT_METRICS_PORT``).
    Idempotent — one server per process; returns it (``.server_port``
    carries the bound port, useful with ``port=0`` in tests), or None
    when no port is configured."""
    global _http_server
    if port is None:
        port = knobs.get("SRJT_METRICS_PORT")
        if not port:
            return None
    port = int(port)
    with _http_lock:
        if _http_server is not None:
            return _http_server
        import http.server

        class _Handler(http.server.BaseHTTPRequestHandler):
            def do_GET(self):            # noqa: N802 (stdlib API name)
                if self.path.split("?")[0] not in ("/metrics", "/"):
                    self.send_response(404)
                    self.end_headers()
                    return
                body = to_prometheus().encode()
                self.send_response(200)
                self.send_header("Content-Type",
                                 "text/plain; version=0.0.4")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *a):    # scrapes must not spam stderr
                pass

        _http_server = http.server.ThreadingHTTPServer(
            ("0.0.0.0", port), _Handler)
        threading.Thread(target=_http_server.serve_forever,
                         name="srjt-metrics-http", daemon=True).start()
        return _http_server


def stop_http_server() -> None:
    """Shut the scrape endpoint down (tests)."""
    global _http_server
    with _http_lock:
        if _http_server is not None:
            _http_server.shutdown()
            _http_server.server_close()
            _http_server = None


install_compile_listeners()
