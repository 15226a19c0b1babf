"""Plain sequential HYPE and the benchmark's own partition arithmetic.

Independent of the program under test: numpy only, nothing imported
from ``repro``. ``hype_reference`` follows the paper's Algorithms 1-3
(arXiv:1810.11319 §III) with the optimisations of §III-B2: a fringe of
at most ``s`` = 10 vertices, ``r`` = 2 new candidates per step drawn from
the smallest active hyperedges first, and external-neighbour scores
computed once per phase. The external-neighbour score of a candidate
counts its neighbours that are neither in the fringe nor in any core.

``score`` selects what the growth step ranks candidates by:

* ``"exact"``: the integer score (the reference);
* ``"bf16"``: the score rounded to bfloat16 (8 significant bits), the
  lower-precision control;
* ``"none"``: no score at all, the fringe is taken in arrival order,
  the control that drops the neighbourhood expansion HYPE guarantees.
"""
from __future__ import annotations

import heapq

import numpy as np

SCORES = ("exact", "bf16", "none")


class Csr:
    """Both incidence directions of a hypergraph, built from pin arrays."""

    def __init__(self, n: int, m: int, vertex_ids, edge_ids):
        v = np.asarray(vertex_ids, dtype=np.int64)
        e = np.asarray(edge_ids, dtype=np.int64)
        self.n, self.m = int(n), int(m)
        order = np.lexsort((v, e))
        self.e2v = v[order]
        self.e_ptr = np.concatenate(
            [[0], np.cumsum(np.bincount(e, minlength=m))]).astype(np.int64)
        order = np.lexsort((e, v))
        self.v2e = e[order]
        self.v_ptr = np.concatenate(
            [[0], np.cumsum(np.bincount(v, minlength=n))]).astype(np.int64)
        self.edge_of_pin = np.repeat(np.arange(m, dtype=np.int64),
                                     np.diff(self.e_ptr))


def km1(csr: Csr, assignment: np.ndarray, k: int) -> int:
    """k-1 metric: sum over hyperedges of (parts spanned - 1)."""
    parts = assignment[csr.e2v].astype(np.int64)
    spans = np.unique(csr.edge_of_pin * k + parts).size
    nonempty = int((np.diff(csr.e_ptr) > 0).sum())
    return int(spans - nonempty)


def part_sizes(assignment: np.ndarray, k: int) -> np.ndarray:
    """Vertices per part; entries outside ``[0, k)`` are not counted."""
    a = np.asarray(assignment)
    ok = (a >= 0) & (a < k)
    return np.bincount(a[ok].astype(np.int64), minlength=k)


def external_scores(csr: Csr, vs: np.ndarray, assignment: np.ndarray,
                    in_fringe: np.ndarray, hub_width: int):
    """HYPE's external-neighbour scores of ``vs`` in a given state.

    For each candidate ``v``: the distinct vertices that share a
    hyperedge with it, other than itself, that are unassigned
    (``assignment < 0``) and not in the fringe (``in_fringe``). Returns
    ``(scores, wide)``, ``wide`` marking candidates with more than
    ``hub_width`` unassigned neighbours (the hubs a scorer may truncate).
    """
    vs = np.asarray(vs, dtype=np.int64)
    if vs.size == 0:
        return np.zeros(0, dtype=np.int64), np.zeros(0, dtype=bool)
    n = csr.n
    edges, row = _gather(csr.v_ptr, csr.v2e, vs)
    nbrs, prow = _gather(csr.e_ptr, csr.e2v, edges)
    key = np.unique(row[prow] * n + nbrs)
    r, u = key // n, key % n
    free = (assignment[u] < 0) & (u != vs[r])
    ext = free & ~in_fringe[u]
    scores = np.bincount(r[ext], minlength=vs.size)
    wide = np.bincount(r[free], minlength=vs.size) > hub_width
    return scores, wide


def _gather(ptr: np.ndarray, idx: np.ndarray, ids: np.ndarray):
    """Concatenated CSR rows of ``ids`` and, per value, its row's slot."""
    starts = ptr[ids]
    lens = ptr[ids + 1] - starts
    total = int(lens.sum())
    if total == 0:
        return idx[:0], np.empty(0, dtype=np.int64)
    first = np.cumsum(lens) - lens
    pos = np.arange(total) - np.repeat(first, lens) + np.repeat(starts, lens)
    return idx[pos], np.repeat(np.arange(ids.size), lens)


def _round_bf16(x: np.ndarray) -> np.ndarray:
    """Round float32 values to the nearest bfloat16, ties to even."""
    b = np.asarray(x, dtype=np.float32).view(np.uint32).astype(np.uint64)
    b = (b + 0x7FFF + ((b >> 16) & 1)) & 0xFFFF0000
    return b.astype(np.uint32).view(np.float32).astype(np.float64)


class _State:
    def __init__(self, csr: Csr, k: int, seed: int, score: str):
        if score not in SCORES:
            raise ValueError(f"score must be one of {SCORES}")
        self.g, self.k, self.score_mode = csr, k, score
        n = csr.n
        self.assignment = np.full(n, -1, dtype=np.int32)
        self.in_fringe = np.zeros(n, dtype=bool)
        self.pins = csr.e2v.copy()          # assigned pins move to the front
        self.cursor = csr.e_ptr[:-1].copy()
        self.end = csr.e_ptr[1:]
        self.size = np.diff(csr.e_ptr)
        self.dead = self.cursor >= self.end
        self.epoch = np.full(csr.m, -1, dtype=np.int64)
        self.cache = np.full(n, -1.0)
        self.order = np.random.default_rng(seed).permutation(n)
        self.ptr = 0

    def random_unassigned(self) -> int:
        while self.ptr < self.g.n:
            v = int(self.order[self.ptr])
            self.ptr += 1
            if self.assignment[v] < 0 and not self.in_fringe[v]:
                return v
        rest = np.flatnonzero((self.assignment < 0) & ~self.in_fringe)
        return int(rest[0]) if rest.size else -1

    def score_many(self, vs: list) -> None:
        """Fill the phase's score cache for the unscored ``vs``."""
        miss = np.asarray([v for v in vs if self.cache[v] < 0.0],
                          dtype=np.int64)
        if miss.size == 0:
            return
        if self.score_mode == "none":
            self.cache[miss] = 0.0
            return
        g, n = self.g, self.g.n
        edges, row = _gather(g.v_ptr, g.v2e, miss)
        nbrs, prow = _gather(g.e_ptr, g.e2v, edges)
        key = np.unique(row[prow] * n + nbrs)
        r, u = key // n, key % n
        ext = ~self.in_fringe[u] & (self.assignment[u] < 0) & (u != miss[r])
        sc = np.bincount(r[ext], minlength=miss.size).astype(np.float64)
        if self.score_mode == "bf16":
            sc = _round_bf16(sc)
        self.cache[miss] = sc


def _grow(st: _State, part: int, target: int, r: int = 2,
          s: int = 10) -> None:
    g = st.g
    heap: list = []
    fringe: list = []
    st.cache[:] = -1.0

    def add_to_core(v: int) -> None:
        st.assignment[v] = part
        st.in_fringe[v] = False
        for e in g.v2e[g.v_ptr[v]:g.v_ptr[v + 1]]:
            e = int(e)
            if st.epoch[e] != part and not st.dead[e]:
                st.epoch[e] = part
                heapq.heappush(heap, (int(st.size[e]), e))

    seed = st.random_unassigned()
    if seed < 0:
        return
    add_to_core(seed)
    grown = 1
    while grown < target:
        cand: list = []
        keep: list = []
        while heap and len(cand) < r:
            size_e, e = heapq.heappop(heap)
            if st.epoch[e] != part or st.dead[e]:
                continue
            cur, end = int(st.cursor[e]), int(st.end[e])
            while cur < end and len(cand) < r:
                v = int(st.pins[cur])
                if st.assignment[v] >= 0:
                    front = int(st.cursor[e])
                    st.pins[cur], st.pins[front] = st.pins[front], v
                    st.cursor[e] += 1
                elif not st.in_fringe[v] and v not in cand:
                    cand.append(v)
                cur += 1
            if st.cursor[e] >= end:
                st.dead[e] = True
            else:
                keep.append((size_e, e))
        for item in keep:
            heapq.heappush(heap, item)
        pool = fringe + cand
        if pool:
            st.score_many(pool)
            ranked = sorted(pool, key=lambda v: st.cache[v])
            fringe = ranked[:s]
            st.in_fringe[ranked[s:]] = False
            st.in_fringe[fringe] = True
        if not fringe:
            v = st.random_unassigned()
            if v < 0:
                return
            fringe = [v]
            st.in_fringe[v] = True
        best = min(range(len(fringe)), key=lambda i: st.cache[fringe[i]]
                   if st.cache[fringe[i]] >= 0.0 else np.inf)
        add_to_core(fringe.pop(best))
        grown += 1
    st.in_fringe[fringe] = False


def hype_reference(csr: Csr, k: int, seed: int,
                   score: str = "exact") -> np.ndarray:
    """Sequential HYPE: ``k`` balanced parts, ``max - min <= 1``."""
    st = _State(csr, k, seed, score)
    base, rem = divmod(csr.n, k)
    for part in range(k - 1):
        _grow(st, part, base + (part < rem))
    st.assignment[st.assignment < 0] = k - 1
    return st.assignment
