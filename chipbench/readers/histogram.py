"""Median of one of the program's histograms (``utils/metrics.py``) over
the samples it retained since the window began — never the lifetime
estimate, which is a log2-bucket edge.  The program anchors ``window_s`` at
the time of this call, so the length is reckoned here, from the window's
start to now: whatever ran between the window's end and this call (the
trace's reduction) cuts nothing off.  The program retains the newest 1024
samples of a histogram; a window with more reads its last 1024."""

import time


def read(ctx: dict, params: dict):
    return ctx["program_metrics"].percentile(
        params["histogram"], 50.0,
        window_s=time.monotonic() - ctx["window_start_monotonic"])
