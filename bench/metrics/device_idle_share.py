"""Share of the traced partition call in which the device ran no op.

1 - (union of device-op intervals inside the call) / (the call's
interval), in percent, from the profiler trace. None without a trace.
"""


def read(run):
    t = run.trace
    if t is None or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
