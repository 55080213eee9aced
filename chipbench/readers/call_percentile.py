"""A percentile (ms, nearest rank) of the latencies of every call of the
window, for a mix whose tail is no end-to-end metric."""

from .. import harness


def read(ctx: dict, params: dict):
    if not ctx["latencies"]:
        return None
    return harness.percentile(ctx["latencies"], params["percentile"]) * 1e3
