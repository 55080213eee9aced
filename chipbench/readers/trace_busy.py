"""Device-busy time per completed call (ms): the union of the device-op
intervals inside the traced window over the calls of the window."""


def read(ctx: dict, params: dict):
    reduced = ctx["trace"]
    if not reduced or not reduced["busy_s"] or not ctx["calls"]:
        return None
    return reduced["busy_s"] / ctx["calls"] * 1e3
