"""Share of a superstep partition call blocked on the device's results.

The engine's ``hype.superstep.wait`` span (``BatchedStats.spans``: from
the first blocking read of a superstep's results until they are on the
host) summed over the window's calls, over their summed wall time, in
percent. None where no call recorded the span.
"""

SPAN = "hype.superstep.wait"


def read(run):
    calls = [c for c in run.calls if SPAN in getattr(c.stats, "spans", {})]
    if not calls:
        return None
    return 100.0 * sum(c.stats.spans[SPAN][0] for c in calls) / sum(
        c.seconds for c in calls)
