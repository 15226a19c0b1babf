"""Calibrated power-law hypergraphs: the benchmark's own data generator.

A copy of the repository's ``powerlaw_hypergraph`` idea (power-law edge
sizes and vertex degrees, pins placed around an edge centre on a ring of
vertex stubs so that communities form at every scale), changed so that
the graph has the published shape of a bipartite membership network:

* exactly ``n`` vertices, ``m`` hyperedges and ``pins`` pins;
* every vertex has at least one membership and every hyperedge at least
  one pin (no isolated vertex, no empty edge);
* no vertex appears twice in one hyperedge.

Edge sizes and vertex degrees are drawn from truncated power laws and
then moved, one unit at a time, to the exact pin count. Every vertex
stub is matched to exactly one edge pin by rank (pins sorted by their
target position on the ring take the stubs in ring order), so the
degree sequence is met exactly; repeated (vertex, edge) pairs are then
broken by swapping vertices between pins, which keeps both sequences.

Only numpy; the same seed gives the same graph on every machine.
"""
from __future__ import annotations

import numpy as np


def powerlaw_counts(rng, count: int, total: int, alpha: float, lo: int,
                    hi: int) -> np.ndarray:
    """``count`` integers in ``[lo, hi]`` summing to exactly ``total``.

    Inverse-CDF samples of a continuous power law with exponent
    ``alpha`` on ``[lo, hi + 1)``, floored, then nudged by +-1 on
    random entries until the sum is ``total``.
    """
    if not lo * count <= total <= hi * count:
        raise ValueError(f"{total} cannot be split into {count} values "
                         f"in [{lo}, {hi}]")
    a1 = 1.0 - alpha
    u = rng.random(count)
    x = (((hi + 1.0) ** a1 - lo ** a1) * u + lo ** a1) ** (1.0 / a1)
    out = np.clip(np.floor(x).astype(np.int64), lo, hi)
    while True:
        diff = total - int(out.sum())
        if diff == 0:
            return out
        room = np.flatnonzero(out < hi) if diff > 0 else \
            np.flatnonzero(out > lo)
        pick = rng.choice(room, size=min(abs(diff), room.size),
                          replace=False)
        out[pick] += 1 if diff > 0 else -1


def calibrated_pins(n: int, m: int, pins: int, *, alpha_edge: float,
                    alpha_vertex: float, max_edge: int, max_degree: int,
                    locality: float, shape_seed: int, seed: int):
    """Parallel ``(vertex_ids, edge_ids)`` int64 pin arrays; see module.

    The multisets of edge sizes and vertex degrees come from
    ``shape_seed`` alone, so every ``seed`` partitions a graph of the
    same sizes (the same expanded-adjacency work); ``seed`` orders them
    and places the pins.
    """
    shape = np.random.default_rng(shape_seed)
    sizes = powerlaw_counts(shape, m, pins, alpha_edge, 1, max_edge)
    degs = powerlaw_counts(shape, n, pins, alpha_vertex, 1, max_degree)
    rng = np.random.default_rng(seed)
    sizes, degs = rng.permutation(sizes), rng.permutation(degs)
    edge_of_pin = np.repeat(np.arange(m, dtype=np.int64), sizes)
    # ring of stubs: vertex v owns degs[v] consecutive positions
    stub_owner = np.repeat(np.arange(n, dtype=np.int64), degs)
    # target position of each pin: heavy-tailed (Pareto) displacement
    # from its edge's centre, or anywhere for the global share
    centres = rng.random(m) * pins
    disp = 2.0 * rng.random(pins) ** (-1.0 / 0.9)
    sign = rng.integers(0, 2, size=pins) * 2 - 1
    local = rng.random(pins) < locality
    target = np.where(local, centres[edge_of_pin] + sign * disp,
                      rng.random(pins) * pins) % pins
    # rank matching: the i-th pin by target position takes stub i
    vert = np.empty(pins, dtype=np.int64)
    vert[np.argsort(target, kind="stable")] = stub_owner
    # break repeated (vertex, edge) pairs by swapping vertices with
    # random pins; each swap keeps every degree and every edge size
    for _ in range(1000):
        key = edge_of_pin * n + vert
        order = np.argsort(key, kind="stable")
        dup = order[1:][key[order][1:] == key[order][:-1]]
        if dup.size == 0:
            break
        others = np.ones(pins, dtype=bool)
        others[dup] = False
        partner = rng.permutation(np.flatnonzero(others))[:dup.size]
        vert[dup], vert[partner] = vert[partner], vert[dup].copy()
    else:
        raise RuntimeError("could not remove repeated pins")
    # vertex ids carry no ring position: a partitioner must not be able
    # to read the communities off the id order
    perm = rng.permutation(n)
    return perm[vert], edge_of_pin


def graph_stats(n: int, m: int, vertex_ids: np.ndarray,
                edge_ids: np.ndarray) -> dict:
    """Shape of a pin list: counts, isolated vertices, adjacency pairs."""
    sizes = np.bincount(edge_ids, minlength=m)
    degs = np.bincount(vertex_ids, minlength=n)
    return {"n": n, "m": m, "pins": int(vertex_ids.size),
            "isolated_vertices": int((degs == 0).sum()),
            "empty_edges": int((sizes == 0).sum()),
            "max_edge": int(sizes.max()), "max_degree": int(degs.max()),
            "expanded_pairs": int((sizes.astype(np.int64) ** 2).sum()),
            "repeated_pins": int(vertex_ids.size - np.unique(
                edge_ids * n + vertex_ids).size)}


def config_pins(cfg: dict, seed: int):
    """Pin arrays of a configuration file's graph at run seed ``seed``."""
    g = cfg["graph"]
    return calibrated_pins(
        g["n"], g["m"], g["pins"], alpha_edge=g["alpha_edge"],
        alpha_vertex=g["alpha_vertex"], max_edge=g["max_edge"],
        max_degree=g["max_degree"], locality=g["locality"],
        shape_seed=g["shape_seed"], seed=seed)
