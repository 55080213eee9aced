"""The JAX profiler around a window, and the reduction of its trace.

``reduce_trace`` reads the ``.xplane.pb`` with nothing but JAX
(``jax.profiler.ProfileData``): device planes are ``/device:<KIND>:<n>``,
their ``XLA Ops`` line holds one event per executed operation, and the
host's ``TraceAnnotation`` spans (the harness's own, prefixed ``cb:``) sit on
the host plane on the same clock.  Busy time is the union of the operation
intervals inside the ``cb:window`` span, averaged over the device planes.
"""

from __future__ import annotations

import contextlib
import glob
import os
import re
import shutil

import numpy as np

PREFIX = "cb:"
WINDOW = PREFIX + "window"
_DEVICE_PLANE = re.compile(r"^/device:[A-Za-z]+:\d+$")
_OPS_LINE = "XLA Ops"
# lines of a device plane that restate the ops line at another grain
_NOT_OPS = ("Steps", "XLA Modules", "XLA TraceMe", "Framework Ops",
            "Framework Name Scope", "Source code")
TOP = 10
NAME_CHARS = 120            # of an operation's name as the profiler has it


@contextlib.contextmanager
def capture(directory: str):
    """Profile the block into ``directory`` (emptied first); host events at
    the level that keeps ``TraceAnnotation``, no Python tracer."""
    import jax
    shutil.rmtree(directory, ignore_errors=True)
    os.makedirs(directory)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(directory, profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def find_xplane(directory: str) -> str | None:
    found = sorted(glob.glob(os.path.join(
        directory, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def _events(line):
    names, starts, durs = [], [], []
    for e in line.events:
        names.append(e.name)
        starts.append(e.start_ns)
        durs.append(e.duration_ns)
    return names, np.asarray(starts, np.float64), np.asarray(durs, np.float64)


def union_intervals(starts: np.ndarray, ends: np.ndarray) -> np.ndarray:
    """Merged ``[k, 2]`` intervals of possibly nested/overlapping ones."""
    if starts.size == 0:
        return np.zeros((0, 2))
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], np.maximum.accumulate(ends[order])
    first = np.concatenate([[True], s[1:] > e[:-1]])
    last = np.concatenate([first[1:], [True]])
    return np.stack([s[first], e[last]], axis=1)


def _clip(starts, ends, lo, hi):
    s, e = np.maximum(starts, lo), np.minimum(ends, hi)
    keep = e > s
    return s, e, keep


def reduce_trace(path: str) -> dict:
    """``{"busy_s", "window_s", "devices", "device_ops", "idle_gaps"}`` of
    one recorded trace; ``busy_s`` is ``None`` where no device plane holds
    an operation (a CPU trace)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    host_spans = []                      # (name, start, end) of cb: spans
    planes = []
    for plane in data.planes:
        if _DEVICE_PLANE.match(plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            use = ([lines[_OPS_LINE]] if _OPS_LINE in lines else
                   [ln for n, ln in lines.items() if n not in _NOT_OPS])
            parts = [_events(ln) for ln in use]
            planes.append((
                [n for p in parts for n in p[0]],
                np.concatenate([p[1] for p in parts] or [np.zeros(0)]),
                np.concatenate([p[2] for p in parts] or [np.zeros(0)])))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith(PREFIX):
                        host_spans.append((e.name, e.start_ns,
                                           e.start_ns + e.duration_ns))
    window = [s for s in host_spans if s[0] == WINDOW]
    all_starts = [p[1] for p in planes if p[1].size]
    if window:
        lo, hi = window[0][1], window[0][2]
    elif all_starts:
        lo = min(s.min() for s in all_starts)
        hi = max((p[1] + p[2]).max() for p in planes if p[1].size)
    else:
        return {"busy_s": None, "window_s": None, "devices": len(planes),
                "device_ops": [], "idle_gaps": []}
    busy, per_op, gaps = [], {}, []
    for names, starts, durs in planes:
        s, e, keep = _clip(starts, starts + durs, lo, hi)
        merged = union_intervals(s[keep], e[keep])
        busy.append(float((merged[:, 1] - merged[:, 0]).sum()))
        for name, d in zip(np.asarray(names, object)[keep], (e - s)[keep]):
            per_op[name] = per_op.get(name, 0.0) + float(d)
        edges = np.concatenate([[lo], merged.reshape(-1), [hi]])
        for a, b in edges.reshape(-1, 2):
            if b > a:
                gaps.append((float(a), float(b)))
    spans = [s for s in host_spans if s[0] != WINDOW]
    gaps.sort(key=lambda g: g[0] - g[1])
    idle = []
    for a, b in gaps[:TOP]:
        best, best_cover = "no chipbench span open", 0.0
        for name, s0, s1 in spans:
            cover = min(b, s1) - max(a, s0)
            if cover > best_cover:
                best, best_cover = name[len(PREFIX):], cover
        idle.append([best, (b - a) / 1e9])
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]
    n = max(len(planes), 1)
    total_busy = sum(busy) / n / 1e9
    return {"busy_s": total_busy if planes and total_busy > 0 else None,
            "window_s": (hi - lo) / 1e9, "devices": len(planes),
            "device_ops": [[k[:NAME_CHARS], v / 1e9 / n] for k, v in ops],
            "idle_gaps": idle}
