"""Median duration (ms) of one of chipbench's own spans inside the window."""

import statistics


def read(ctx: dict, params: dict):
    durations = ctx["spans"].get(params["span"])
    if not durations:
        return None
    return statistics.median(durations) * 1e3
