"""Share of a batched partition call spent drawing and tiling candidates.

From the engine's spans (``BatchedStats.spans``): ``hype.refill.draw``
plus the self time of ``hype.refill.score`` (its time less its child
``hype.refill.kernel``: the cache lookup and the host tile build),
summed over the window's calls, over their summed wall time, in
percent. None where no call recorded a draw.
"""


def _seconds(spans, name):
    return spans[name][0] if name in spans else 0.0


def read(run):
    calls = [c for c in run.calls
             if "hype.refill.draw" in getattr(c.stats, "spans", {})]
    if not calls:
        return None
    build = sum(_seconds(s, "hype.refill.draw")
                + _seconds(s, "hype.refill.score")
                - _seconds(s, "hype.refill.kernel")
                for s in (c.stats.spans for c in calls))
    return 100.0 * build / sum(c.seconds for c in calls)
