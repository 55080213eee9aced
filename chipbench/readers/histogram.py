"""Median of one of the program's histograms (``utils/metrics.py``) over
the samples it retained inside the window — never the lifetime estimate,
which is a log2-bucket edge."""


def read(ctx: dict, params: dict):
    return ctx["program_metrics"].percentile(
        params["histogram"], 50.0, window_s=ctx["window_s"])
