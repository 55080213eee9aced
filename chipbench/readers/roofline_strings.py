"""Share of the HBM roofline (%) of the strings cell: the least bytes a
round trip has to move (``rooflines_strings.py``) over the published
bandwidth, over the device-busy time per call from the trace.  All device
time counts, as in ``roofline``."""

from .. import peaks, rooflines_strings
from . import trace_busy


def read(ctx: dict, params: dict):
    busy_ms = trace_busy.read(ctx, {})
    if busy_ms is None:
        return None
    least_bytes = rooflines_strings.BYTES[params["bytes"]](ctx["config"],
                                                          ctx["facts"])
    least_s = least_bytes / peaks.peak(ctx["device_kind"],
                                       "hbm_bytes_per_s")
    return 100.0 * least_s / (busy_ms / 1e3)
