"""Host orchestration's share of a superstep partition's wall time.

``BatchedStats.host_s`` (the engine's own clock around packing and
harvest mirroring) summed over the window's calls, over their summed
wall time, in percent. None where no call ran supersteps.
"""


def read(run):
    calls = [c for c in run.calls if c.stats is not None
             and c.stats.supersteps > 0]
    if not calls:
        return None
    return 100.0 * sum(c.stats.host_s for c in calls) / sum(
        c.seconds for c in calls)
