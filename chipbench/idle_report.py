"""``python3 -m chipbench.idle_report <xplane.pb>``: for each of the
program's spans in a recorded trace (``srjt:`` on the host plane), how often
it ran, its total and self time, and the device-idle time that lay under it
and under its self time — where the chip waits, by the program's own names.
Times are seconds inside the ``cb:window`` span (the whole trace without
one); with more device planes than one, idle time is their mean.
"""

from __future__ import annotations

import sys

import numpy as np

from . import trace
from .readers import idle_under_spans as ius


def table(path: str):
    """``(rows, window_s, idle_s, idle_named_s)``; a row is ``[name, count,
    total_s, self_s, idle_s, idle_self_s]``, longest idle self time first.
    Spans of two threads can lie over the same idle time, so the rows may
    add up to more than ``idle_named_s``, the idle time under any span."""
    loaded = ius.load(path)
    if not loaded:
        return [], None, None, None
    lo, hi = loaded["window"]
    planes = loaded["idle"]
    rows: dict[str, list] = {}
    for spans in loaded["threads"]:
        a = np.clip([s[1] for s in spans], lo, hi)
        b = np.clip([s[2] for s in spans], lo, hi)
        dur = b - a
        idle = sum(ius.overlap(p, a, b) for p in planes) / len(planes)
        for k, (name, _, _, kids) in enumerate(spans):
            if dur[k] <= 0:
                continue
            row = rows.setdefault(name, [name, 0, 0.0, 0.0, 0.0, 0.0])
            row[1] += 1
            row[2] += dur[k] / 1e9
            row[3] += (dur[k] - dur[kids].sum()) / 1e9
            row[4] += idle[k] / 1e9
            row[5] += (idle[k] - idle[kids].sum()) / 1e9
    ordered = sorted(rows.values(), key=lambda r: -r[5])
    every = [s for spans in loaded["threads"] for s in spans]
    named = trace.union_intervals(np.clip([s[1] for s in every], lo, hi),
                                  np.clip([s[2] for s in every], lo, hi))
    idle_s = sum(ius.measure(p) for p in planes) / len(planes)
    idle_named = sum(float(ius.overlap(p, named[:, 0], named[:, 1]).sum())
                     for p in planes) / len(planes)
    return ordered, (hi - lo) / 1e9, idle_s / 1e9, idle_named / 1e9


def main(argv) -> int:
    if len(argv) != 1:
        print(__doc__, file=sys.stderr)
        return 2
    rows, window_s, idle_s, idle_named_s = table(argv[0])
    if window_s is None:
        print("no device plane with operations in", argv[0], file=sys.stderr)
        return 1
    print(f"window {window_s:.6f} s, device idle {idle_s:.6f} s "
          f"({100 * idle_s / window_s:.2f}%)")
    print(f"{'span':<40}{'count':>7}{'total_s':>12}{'self_s':>12}"
          f"{'idle_s':>12}{'idle_self_s':>13}")
    for name, count, total, own, idle, idle_own in rows:
        print(f"{name:<40}{count:>7}{total:>12.6f}{own:>12.6f}"
              f"{idle:>12.6f}{idle_own:>13.6f}")
    print(f"{'(idle under no program span)':<40}{'':>7}{'':>12}{'':>12}"
          f"{idle_s - idle_named_s:>12.6f}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
