"""Share of a batched partition call spent in scoring-kernel round trips.

The engine's ``hype.refill.kernel`` span (``BatchedStats.spans``: the
tile and fringe uploads, the ``hype_scores`` call and the read of its
result) summed over the window's calls, over their summed wall time, in
percent. None where no call recorded the span.
"""

SPAN = "hype.refill.kernel"


def read(run):
    calls = [c for c in run.calls if SPAN in getattr(c.stats, "spans", {})]
    if not calls:
        return None
    return 100.0 * sum(c.stats.spans[SPAN][0] for c in calls) / sum(
        c.seconds for c in calls)
