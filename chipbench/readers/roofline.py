"""Share of the HBM roofline (%): the least bytes one call has to move, from
``rooflines.py``, over the published bandwidth, over the device-busy time
per call from the trace.  All device time counts, not kernels found by
name, so the share reads the same work whatever implements it."""

from .. import peaks, rooflines
from . import trace_busy


def read(ctx: dict, params: dict):
    busy_ms = trace_busy.read(ctx, {})
    if busy_ms is None:
        return None
    least_bytes = rooflines.BYTES[params["bytes"]](ctx["config"],
                                                  ctx["facts"])
    least_s = least_bytes / peaks.peak(ctx["device_kind"],
                                       "hbm_bytes_per_s")
    return 100.0 * least_s / (busy_ms / 1e3)
