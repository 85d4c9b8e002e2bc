"""device_idle: percent of the traced window in which no operation ran on
the device: 1 - (union of the XLA Ops intervals) / window, from the
profiler trace (device trace)."""


def read(ctx):
    t = ctx.trace
    if not t or not t["devices"] or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
