"""Share of the traced partition call spent in the scoring kernels.

Summed device time of the ops named after the traffic's ``kernels``
(the Pallas ``hype_score`` kernels) over the call's interval, in
percent, from the profiler trace. None without a trace or where no
such op ran.
"""


def read(run):
    t = run.trace
    if t is None or t["window_s"] <= 0 or t["kernel_s"] <= 0:
        return None
    return 100.0 * t["kernel_s"] / t["window_s"]
