"""Host-to-device bytes per vertex of a superstep partition.

``BatchedStats.host_to_device_bytes`` (the per-superstep id, bias and
delta buffers; the one-time graph image is not in it) summed over the
window's calls, over their summed vertex counts. None where no call ran
supersteps.
"""


def read(run):
    calls = [c for c in run.calls if c.stats is not None
             and c.stats.supersteps > 0]
    if not calls:
        return None
    return sum(c.stats.host_to_device_bytes for c in calls) / sum(
        c.n for c in calls)
