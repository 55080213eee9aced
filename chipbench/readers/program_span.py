"""One of the program's own spans (``utils/metrics.py``), read from its
in-memory span store: the trees of the root spans that began inside the
window.  The program anchors ``window_s`` at the time of the call, so the
length is reckoned here, as the ``histogram`` reader does.  A program whose
store takes no window (one older than the spans' ``tid``) gives nothing.

``params``: ``span`` names the spans; ``attr`` takes a number the program
put on each of them in place of its duration (ms); ``stat`` is

* ``total_per_call`` — median over the window's calls of the sum, over
  every thread, within one call: spans of one call share a request id
  (``rid``).  A median, as ``median_call_ms`` is one, so that a few stalled
  calls do not set the parts apart from the whole;
* ``median`` — of the single values;
* ``self`` — median of what the span's own thread spent under it and under
  no leaf span: its duration less the union of the childless spans below
  it on its thread, so the self time of it and of every span that has
  children.  What no span accounts for.
"""

import statistics
import time


def flatten(trees: list) -> list:
    out, todo = [], list(trees)
    while todo:
        node = todo.pop()
        out.append(node)
        todo.extend(node.get("children", ()))
    return out


def unattributed_ms(node: dict) -> float:
    """``node``'s duration less the union of the leaves under it on its own
    thread."""
    leaves = sorted(
        (s["start_ms"], s["start_ms"] + s["dur_ms"])
        for s in flatten(node.get("children", []))
        if s["tid"] == node["tid"] and not any(
            c["tid"] == node["tid"] for c in s.get("children", ())))
    covered, edge = 0.0, node["start_ms"]
    for a, b in leaves:
        if b > edge:
            covered += b - max(a, edge)
            edge = b
    return node["dur_ms"] - covered


def read(ctx: dict, params: dict):
    store = ctx["program_metrics"]
    try:
        trees = store.span_roots(
            window_s=time.monotonic() - ctx["window_start_monotonic"])
    except TypeError:
        return None
    found = [s for s in flatten(trees) if s["name"] == params["span"]]
    if not found or "tid" not in found[0]:
        return None
    stat = params["stat"]
    if stat == "self":
        return statistics.median(unattributed_ms(s) for s in found)
    attr = params.get("attr")
    if attr is not None:
        found = [s for s in found if attr in s.get("attrs", {})]
    values = [s["dur_ms"] if attr is None else s["attrs"][attr]
              for s in found]
    if not values:
        return None
    if stat == "median":
        return statistics.median(values)
    if stat == "total_per_call":
        calls: dict = {}
        for s, v in zip(found, values):
            calls[s["rid"]] = calls.get(s["rid"], 0.0) + v
        return statistics.median(calls.values())
    raise ValueError(f"program_span: no stat {stat!r}")
