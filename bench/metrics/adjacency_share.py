"""Share of a partition call spent building the vertex adjacency.

The engine's ``hype.setup.adjacency`` span (``BatchedStats.spans``: its
``perf_counter`` around ``Hypergraph.vertex_adjacency``) summed over the
window's calls, over their summed wall time, in percent. None where no
call recorded the span (a program without named spans).
"""

SPAN = "hype.setup.adjacency"


def read(run):
    calls = [c for c in run.calls if SPAN in getattr(c.stats, "spans", {})]
    if not calls:
        return None
    return 100.0 * sum(c.stats.spans[SPAN][0] for c in calls) / sum(
        c.seconds for c in calls)
