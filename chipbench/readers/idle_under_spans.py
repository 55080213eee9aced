"""Of the device-idle time inside the traced window, the share (%) that lies
under a leaf span of the program on the calling thread: how much of the
chip's waiting the program's own spans put a name to.

The program's spans are the host plane's ``TraceAnnotation`` events whose
names start with ``srjt:`` (``utils/metrics.py`` opens one for each span),
on the clock of the device plane's ``XLA Ops``.  The calling thread is each
host line that holds the span ``params["root"]``; a leaf is a span of that
line with no other span of the line inside it.  The ``.xplane.pb`` is found
the way the harness finds it, and parsed once a process.

``python3 -m chipbench.idle_report`` prints the table behind the number.
"""

from __future__ import annotations

import functools

import numpy as np

from .. import harness, trace

PROGRAM = "srjt:"


def measure(intervals: np.ndarray) -> float:
    return float((intervals[:, 1] - intervals[:, 0]).sum())


def covered_upto(merged: np.ndarray, t: np.ndarray) -> np.ndarray:
    """Length of the merged ``[k, 2]`` intervals that lies before each ``t``."""
    if not len(merged):
        return np.zeros_like(t, dtype=np.float64)
    cum = np.concatenate([[0.0], np.cumsum(merged[:, 1] - merged[:, 0])])
    i = np.searchsorted(merged[:, 0], t, side="right")
    over = np.where(i > 0, np.maximum(merged[np.maximum(i, 1) - 1, 1] - t,
                                      0.0), 0.0)
    return cum[i] - over


def overlap(merged: np.ndarray, starts, ends) -> np.ndarray:
    """Length of ``merged`` inside each ``[start, end)``."""
    starts, ends = np.asarray(starts, float), np.asarray(ends, float)
    return covered_upto(merged, ends) - covered_upto(merged, starts)


def nest(spans: list) -> list:
    """``(name, start, end)`` spans of one thread → ``(name, start, end,
    [indices of direct children])``, sorted by start."""
    spans = sorted(spans, key=lambda s: (s[1], -s[2]))
    out = [(n, a, b, []) for n, a, b in spans]
    open_ = []
    for k, (_, a, b, _) in enumerate(out):
        while open_ and out[open_[-1]][2] <= a:
            open_.pop()
        if open_:
            out[open_[-1]][3].append(k)
        open_.append(k)
    return out


@functools.lru_cache(maxsize=2)
def load(path: str) -> dict:
    """``{"window": (lo, hi), "idle": [per device plane: merged idle
    intervals], "threads": [per host line with program spans: nested
    spans]}``, times in ns; ``None`` where no device plane holds an
    operation."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    ops, threads, window = [], [], None
    for plane in data.planes:
        if trace._DEVICE_PLANE.match(plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            use = ([lines[trace._OPS_LINE]] if trace._OPS_LINE in lines else
                   [ln for n, ln in lines.items() if n not in trace._NOT_OPS])
            parts = [trace._events(ln) for ln in use]
            starts = np.concatenate([p[1] for p in parts] or [np.zeros(0)])
            durs = np.concatenate([p[2] for p in parts] or [np.zeros(0)])
            ops.append((starts, starts + durs))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                spans = []
                for e in ln.events:
                    if e.name.startswith(PROGRAM):
                        spans.append((e.name[len(PROGRAM):], e.start_ns,
                                      e.start_ns + e.duration_ns))
                    elif e.name == trace.WINDOW:
                        window = (e.start_ns, e.start_ns + e.duration_ns)
                if spans:
                    threads.append(nest(spans))
    if not any(s.size for s, _ in ops):
        return None
    if window is None:
        window = (min(s.min() for s, _ in ops if s.size),
                  max(e.max() for _, e in ops if e.size))
    lo, hi = window
    idle = []
    for starts, ends in ops:
        s, e, keep = trace._clip(starts, ends, lo, hi)
        busy = trace.union_intervals(s[keep], e[keep])
        edges = np.concatenate([[lo], busy.reshape(-1), [hi]]).reshape(-1, 2)
        idle.append(edges[edges[:, 1] > edges[:, 0]])
    return {"window": window, "idle": idle, "threads": threads}


def read(ctx: dict, params: dict):
    path = trace.find_xplane(harness.TRACE_DIR)
    loaded = load(path) if path else None
    if not loaded:
        return None
    lo, hi = loaded["window"]
    leaves = [(max(a, lo), min(b, hi))
              for spans in loaded["threads"]
              if any(n == params["root"] for n, _, _, _ in spans)
              for _, a, b, kids in spans if not kids and b > lo and a < hi]
    total = sum(measure(idle) for idle in loaded["idle"])
    if not leaves or not total:
        return None
    under = trace.union_intervals(np.array([a for a, _ in leaves], float),
                                  np.array([b for _, b in leaves], float))
    named = sum(float(overlap(idle, under[:, 0], under[:, 1]).sum())
                for idle in loaded["idle"])
    return 100.0 * named / total
