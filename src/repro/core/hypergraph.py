"""Hypergraph data structure (CSR in both directions).

A hypergraph G = (V, E) with |V| = n vertices and |E| = m hyperedges is
stored as two CSR structures:

  * ``v2e``: for each vertex, the list of incident hyperedge ids.
  * ``e2v``: for each hyperedge, the list of member vertex ids (its "pins").

A *pin* is one (vertex, hyperedge) incidence. ``n_pins`` equals the paper's
"#Edges" column in Table II.

All arrays are plain numpy so the structure can scale to hundreds of
millions of pins on a single host; JAX-facing code converts the (small,
padded) views it needs.
"""
from __future__ import annotations

import dataclasses
import os
from concurrent.futures import ThreadPoolExecutor
from typing import Iterable, Sequence

import numpy as np

# Ids at or above 2**31 no longer fit int32; the decision is extracted so
# the boundary can be tested without allocating 2-billion-row graphs.
_INT32_LIMIT = 2**31

# Neighbour pairs per chunk of ``vertex_adjacency``: a chunk's largest
# int64 temporary stays at 8 MB, under glibc's 32 MB mmap ceiling, so
# freed chunk buffers are reused from the heap instead of faulting in
# fresh pages on every build.
_ADJ_CHUNK_PAIRS = 1 << 20


def csr_index_dtype(n: int, m: int):
    """Numpy dtype for CSR *indices* arrays of an (n, m) hypergraph.

    int32 while every vertex AND hyperedge id fits, int64 otherwise.
    Indptr arrays stay int64 regardless (pin counts overflow first).
    """
    return np.int32 if max(int(n), int(m)) < _INT32_LIMIT else np.int64


def device_ptr_dtype(n_indices: int):
    """JAX dtype for the device CSR ``indptr`` image.

    Offsets index into the flat indices array, so the flip happens at
    ``n_indices`` (pin count), not vertex count. Imports jax lazily —
    host-only code paths must not pay for it.
    """
    import jax.numpy as jnp
    return jnp.int32 if int(n_indices) < _INT32_LIMIT else jnp.int64


@dataclasses.dataclass(frozen=True)
class Hypergraph:
    n: int                     # number of vertices
    m: int                     # number of hyperedges
    v2e_indptr: np.ndarray     # (n+1,) int64
    v2e_indices: np.ndarray    # (n_pins,) int32/int64 hyperedge ids
    e2v_indptr: np.ndarray     # (m+1,) int64
    e2v_indices: np.ndarray    # (n_pins,) int32/int64 vertex ids

    # ------------------------------------------------------------------ #
    # Constructors
    # ------------------------------------------------------------------ #
    @classmethod
    def from_pins(cls, n: int, m: int, vertex_ids: np.ndarray,
                  edge_ids: np.ndarray) -> "Hypergraph":
        """Build from parallel pin arrays (vertex_ids[i] ∈ edge edge_ids[i]).

        Parameters
        ----------
        n, m : int
            Vertex and hyperedge counts; ids outside ``[0, n)`` /
            ``[0, m)`` raise ``ValueError``. Vertices or edges with no
            pins are legal (they become empty CSR rows).
        vertex_ids, edge_ids : array-like of int
            Parallel arrays, one entry per pin. Duplicate
            (vertex, edge) pins are deduplicated — a vertex appears at
            most once per hyperedge.

        Returns
        -------
        Hypergraph
            Immutable, with both CSR directions built; index dtype is
            int32 when ids fit, int64 otherwise. This is the
            construction path every loader and generator funnels
            through (``from_edge_lists`` included).
        """
        vertex_ids = np.asarray(vertex_ids, dtype=np.int64)
        edge_ids = np.asarray(edge_ids, dtype=np.int64)
        if vertex_ids.shape != edge_ids.shape:
            raise ValueError("pin arrays must be parallel")
        if vertex_ids.size and (vertex_ids.min() < 0 or vertex_ids.max() >= n):
            raise ValueError("vertex id out of range")
        if edge_ids.size and (edge_ids.min() < 0 or edge_ids.max() >= m):
            raise ValueError("edge id out of range")

        # de-duplicate pins (a vertex may appear at most once per hyperedge)
        key = edge_ids * np.int64(n) + vertex_ids
        _, uniq = np.unique(key, return_index=True)
        vertex_ids, edge_ids = vertex_ids[uniq], edge_ids[uniq]

        idx_dtype = csr_index_dtype(n, m)

        # e2v CSR: sort pins by edge id
        order = np.argsort(edge_ids, kind="stable")
        e2v_indices = vertex_ids[order].astype(idx_dtype)
        e2v_indptr = np.zeros(m + 1, dtype=np.int64)
        np.add.at(e2v_indptr, edge_ids + 1, 1)
        np.cumsum(e2v_indptr, out=e2v_indptr)

        # v2e CSR: sort pins by vertex id
        order = np.argsort(vertex_ids, kind="stable")
        v2e_indices = edge_ids[order].astype(idx_dtype)
        v2e_indptr = np.zeros(n + 1, dtype=np.int64)
        np.add.at(v2e_indptr, vertex_ids + 1, 1)
        np.cumsum(v2e_indptr, out=v2e_indptr)

        return cls(n=n, m=m, v2e_indptr=v2e_indptr, v2e_indices=v2e_indices,
                   e2v_indptr=e2v_indptr, e2v_indices=e2v_indices)

    @classmethod
    def from_edge_lists(cls, n: int, edges: Sequence[Iterable[int]]) -> "Hypergraph":
        """Build from a list of hyperedges, each an iterable of vertex ids.

        Convenience wrapper over ``from_pins`` for tests and small
        graphs (it materializes python lists — use ``from_pins``
        directly for anything large). ``len(edges)`` becomes ``m``;
        empty iterables are legal and become empty hyperedges.
        """
        edge_ids, vertex_ids = [], []
        for e, pins in enumerate(edges):
            for v in pins:
                edge_ids.append(e)
                vertex_ids.append(v)
        return cls.from_pins(n, len(edges),
                             np.asarray(vertex_ids, dtype=np.int64),
                             np.asarray(edge_ids, dtype=np.int64))

    # ------------------------------------------------------------------ #
    # Properties / views
    # ------------------------------------------------------------------ #
    @property
    def n_pins(self) -> int:
        return int(self.e2v_indices.shape[0])

    @property
    def edge_sizes(self) -> np.ndarray:
        return np.diff(self.e2v_indptr)

    @property
    def vertex_degrees(self) -> np.ndarray:
        return np.diff(self.v2e_indptr)

    def edge_pins(self, e: int) -> np.ndarray:
        return self.e2v_indices[self.e2v_indptr[e]:self.e2v_indptr[e + 1]]

    def vertex_edges(self, v: int) -> np.ndarray:
        return self.v2e_indices[self.v2e_indptr[v]:self.v2e_indptr[v + 1]]

    def neighbors(self, v: int) -> np.ndarray:
        """Unique neighbor set N(v). O(sum of incident edge sizes)."""
        es = self.vertex_edges(v)
        if es.size == 0:
            return np.empty(0, dtype=self.e2v_indices.dtype)
        parts = [self.edge_pins(int(e)) for e in es]
        nb = np.unique(np.concatenate(parts))
        return nb[nb != v]

    def vertex_adjacency(self, max_expanded: int = 80_000_000):
        """CSR of unique neighbor lists N(v) for ALL vertices, memoized.

        Every pin (v, e) contributes all pins of e, so the expansion is
        sum over edges of |e|^2 pairs. It is built per contiguous range of
        owner vertices (``_ADJ_CHUNK_PAIRS`` pairs a chunk, bounds fixed
        by the graph alone) on a thread pool of the host's cores; numpy
        releases the GIL in the sorts, gathers and ufuncs that do the
        work. Returns ``(indptr int64, indices int32)``: each row sorted
        ascending and unique, self-loops excluded, the same whatever the
        chunk or thread count. Returns None when the expansion would
        exceed ``max_expanded`` pairs (pathological hub edges; callers
        fall back to per-batch deduplication).

        ``_adj_build`` records ``(chunks, workers)`` of this call's
        build: ``(0, 0)`` on a memo hit or when the guard trips.
        """
        object.__setattr__(self, "_adj_build", (0, 0))
        cache = self.__dict__.get("_adj_cache")
        if cache is None:
            cache = {}
            object.__setattr__(self, "_adj_cache", cache)
        if max_expanded in cache:
            return cache[max_expanded]
        expanded = int((self.edge_sizes.astype(np.int64) ** 2).sum())
        if expanded > max_expanded:
            adj = None
        else:
            adj = self._build_adjacency()
        cache[max_expanded] = adj               # frozen-dataclass memo
        return adj

    def _build_adjacency(self):
        """``vertex_adjacency``'s build, chunked over owner ranges."""
        # pairs owned by each vertex: the sizes of its edges, summed
        pin_pairs = self.edge_sizes[self.v2e_indices]
        pin_cum = np.zeros(pin_pairs.size + 1, dtype=np.int64)
        np.cumsum(pin_pairs, out=pin_cum[1:])
        vcum = pin_cum[self.v2e_indptr]
        n_chunks = -(-int(vcum[-1]) // _ADJ_CHUNK_PAIRS)
        cuts = np.searchsorted(
            vcum, np.arange(1, n_chunks, dtype=np.int64) * _ADJ_CHUNK_PAIRS)
        bounds = np.unique(np.concatenate(([0], cuts, [self.n])))
        chunks = list(zip(bounds[:-1].tolist(), bounds[1:].tolist()))

        def rows(chunk):
            return self._adjacency_rows(*chunk, vcum)

        workers = min(len(os.sched_getaffinity(0)), len(chunks))
        if workers <= 1:
            parts = [rows(c) for c in chunks]
        else:
            with ThreadPoolExecutor(max_workers=workers) as pool:
                parts = list(pool.map(rows, chunks))
        object.__setattr__(self, "_adj_build", (len(chunks), workers))
        indptr = np.zeros(self.n + 1, dtype=np.int64)
        if parts:
            np.cumsum(np.concatenate([c for c, _ in parts]), out=indptr[1:])
            indices = np.concatenate([nb for _, nb in parts])
        else:
            indices = np.empty(0, dtype=np.int32)
        return indptr, indices

    def _adjacency_rows(self, v0: int, v1: int, vcum: np.ndarray):
        """Row lengths and int32 neighbours of the owners ``[v0, v1)``."""
        n = self.n
        edges = self.v2e_indices[self.v2e_indptr[v0]:self.v2e_indptr[v1]]
        starts = self.e2v_indptr[edges]
        lens = self.e2v_indptr[edges + 1] - starts
        out_start = np.cumsum(lens) - lens
        total = int(vcum[v1] - vcum[v0])
        # position of every pair's neighbour in e2v_indices
        pos = np.arange(total, dtype=np.int64)
        pos += np.repeat(starts - out_start, lens)
        keys = np.repeat(np.arange(v1 - v0, dtype=np.int64) * n,
                         np.diff(vcum[v0:v1 + 1]))
        keys += self.e2v_indices[pos]
        del pos
        # e2v rows are sorted, so the keys come in long sorted runs
        keys.sort(kind="stable")
        keep = np.ones(total, dtype=bool)
        np.not_equal(keys[1:], keys[:-1], out=keep[1:])
        keys = keys[keep]
        owner, nbr = np.divmod(keys, n)
        keep = nbr != owner + v0
        counts = np.bincount(owner[keep], minlength=v1 - v0)
        return counts, nbr[keep].astype(np.int32)

    def device_adjacency(self, max_expanded: int = 80_000_000, *,
                         mesh=None):
        """``vertex_adjacency`` uploaded to the device(s) once, memoized.

        Returns ``(indptr_dev, indices_dev)`` jax arrays (int32 where ids
        fit, otherwise int64) or None when the host-side expansion guard
        trips. The superstep engine gathers its candidate tiles from this
        image so refills never ship a freshly built (B, L) tile across
        the host boundary — only candidate *ids* move.

        With ``mesh`` (a ``jax.sharding.Mesh``), the CSR image is placed
        *replicated* across every mesh device — the layout the sharded
        superstep engine wants: each device gathers its own phase group's
        tiles from a full local copy, so sharding the phases never
        shards (or ships) the graph. Memoized per (max_expanded, mesh).
        """
        cache = self.__dict__.get("_device_adj_cache")
        if cache is None:
            cache = {}
            object.__setattr__(self, "_device_adj_cache", cache)
        key = (max_expanded, mesh)
        if key in cache:
            return cache[key]
        adj = self.vertex_adjacency(max_expanded)
        if adj is None:
            dev = None
        else:
            import jax
            import jax.numpy as jnp
            indptr, indices = adj
            ptr_t = device_ptr_dtype(indices.size)
            dev = (jnp.asarray(indptr, ptr_t), jnp.asarray(indices))
            if mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec
                rep = NamedSharding(mesh, PartitionSpec())
                dev = tuple(jax.device_put(a, rep) for a in dev)
        cache[key] = dev
        return dev

    # ------------------------------------------------------------------ #
    # Delta / append APIs (streaming engine, core/hype_stream.py)
    # ------------------------------------------------------------------ #
    def _pin_arrays(self):
        """Parallel ``(vertex_ids, edge_ids)`` int64 pin arrays."""
        edge_ids = np.repeat(np.arange(self.m, dtype=np.int64),
                             self.edge_sizes)
        return self.e2v_indices.astype(np.int64), edge_ids

    def with_edges(self, new_edges: Sequence[Iterable[int]],
                   n: int | None = None) -> "Hypergraph":
        """Append hyperedges; returns a new graph with ``m + len(new_edges)``.

        ``new_edges`` is a sequence of pin iterables over *existing*
        vertex ids (or ids below ``n`` when growing the vertex count).
        Edge ids of the incumbent graph are preserved — appended edges
        take ids ``m, m+1, ...`` — so per-edge bookkeeping (the stream
        engine's sketch buckets) stays valid across the append.
        """
        vids, eids = self._pin_arrays()
        add_v, add_e = [], []
        for i, pins in enumerate(new_edges):
            for v in pins:
                add_v.append(int(v))
                add_e.append(self.m + i)
        vids = np.concatenate([vids, np.asarray(add_v, dtype=np.int64)])
        eids = np.concatenate([eids, np.asarray(add_e, dtype=np.int64)])
        return Hypergraph.from_pins(n if n is not None else self.n,
                                    self.m + len(new_edges), vids, eids)

    def with_vertices(self, memberships: Sequence[Iterable[int]]
                      ) -> "Hypergraph":
        """Append vertices; returns a new graph with ``n + len(memberships)``.

        Each entry lists the *existing* hyperedge ids the new vertex
        joins (possibly empty — isolated vertices are legal). Incumbent
        vertex and edge ids are preserved; appended vertices take ids
        ``n, n+1, ...``.
        """
        vids, eids = self._pin_arrays()
        add_v, add_e = [], []
        for i, edges in enumerate(memberships):
            for e in edges:
                add_v.append(self.n + i)
                add_e.append(int(e))
        vids = np.concatenate([vids, np.asarray(add_v, dtype=np.int64)])
        eids = np.concatenate([eids, np.asarray(add_e, dtype=np.int64)])
        return Hypergraph.from_pins(self.n + len(memberships), self.m,
                                    vids, eids)

    def without_edges(self, edge_ids: Iterable[int]) -> "Hypergraph":
        """Drop all pins of the given hyperedges; ids stay stable.

        The edge *slots* are kept (they become empty hyperedges), so no
        surviving edge is renumbered — deletions never invalidate ids
        held by incremental state.
        """
        drop = np.zeros(self.m, dtype=bool)
        drop[np.asarray(list(edge_ids), dtype=np.int64)] = True
        vids, eids = self._pin_arrays()
        keep = ~drop[eids]
        return Hypergraph.from_pins(self.n, self.m, vids[keep],
                                    eids[keep])

    def without_vertices(self, vertex_ids: Iterable[int]) -> "Hypergraph":
        """Drop all pins of the given vertices; ids stay stable.

        The vertex *slots* are kept (they become isolated vertices), so
        no surviving vertex is renumbered.
        """
        drop = np.zeros(self.n, dtype=bool)
        drop[np.asarray(list(vertex_ids), dtype=np.int64)] = True
        vids, eids = self._pin_arrays()
        keep = ~drop[vids]
        return Hypergraph.from_pins(self.n, self.m, vids[keep],
                                    eids[keep])

    # ------------------------------------------------------------------ #
    # Transformations
    # ------------------------------------------------------------------ #
    def flip(self) -> "Hypergraph":
        """Swap roles of vertices and hyperedges (paper §III-C).

        Flipping twice is the identity (up to pin ordering). Used for
        perfect hyperedge balancing: balance vertices in the flipped graph.
        """
        return Hypergraph(n=self.m, m=self.n,
                          v2e_indptr=self.e2v_indptr, v2e_indices=self.e2v_indices,
                          e2v_indptr=self.v2e_indptr, e2v_indices=self.v2e_indices)

    def validate(self) -> None:
        """Check the CSR invariants; raise ``ValueError`` on corruption.

        Raises (never asserts — ``python -O`` strips ``assert``, which
        would turn validation into a silent no-op) with a message naming
        the violated invariant. Returns None on a well-formed structure.
        """
        if self.v2e_indptr.shape != (self.n + 1,):
            raise ValueError(
                f"v2e_indptr shape {self.v2e_indptr.shape} != (n+1,) "
                f"= ({self.n + 1},)")
        if self.e2v_indptr.shape != (self.m + 1,):
            raise ValueError(
                f"e2v_indptr shape {self.e2v_indptr.shape} != (m+1,) "
                f"= ({self.m + 1},)")
        if self.v2e_indptr[-1] != self.v2e_indices.shape[0]:
            raise ValueError(
                f"v2e_indptr[-1] = {int(self.v2e_indptr[-1])} does not "
                f"match v2e_indices size {self.v2e_indices.shape[0]}")
        if self.e2v_indptr[-1] != self.e2v_indices.shape[0]:
            raise ValueError(
                f"e2v_indptr[-1] = {int(self.e2v_indptr[-1])} does not "
                f"match e2v_indices size {self.e2v_indices.shape[0]}")
        if self.v2e_indices.shape != self.e2v_indices.shape:
            raise ValueError(
                f"pin-count mismatch: {self.v2e_indices.shape[0]} v2e "
                f"pins vs {self.e2v_indices.shape[0]} e2v pins")
        if self.e2v_indices.size:
            if self.e2v_indices.min() < 0:
                raise ValueError("negative vertex id in e2v_indices")
            if self.e2v_indices.max() >= self.n:
                raise ValueError(
                    f"vertex id {int(self.e2v_indices.max())} out of "
                    f"range [0, {self.n})")
        if self.v2e_indices.size:
            if self.v2e_indices.min() < 0:
                raise ValueError("negative edge id in v2e_indices")
            if self.v2e_indices.max() >= self.m:
                raise ValueError(
                    f"edge id {int(self.v2e_indices.max())} out of "
                    f"range [0, {self.m})")

    def fingerprint(self) -> str:
        """Stable 16-hex-digit digest of the CSR structure, memoized.

        Identifies the graph a ``PartitionCheckpoint`` belongs to
        (core/resilience.py): restore refuses a snapshot whose
        fingerprint does not match the hypergraph it is applied to.
        Covers (n, m) and all four CSR arrays, so any structural edit
        changes it.
        """
        cached = self.__dict__.get("_fingerprint")
        if cached is not None:
            return cached
        import hashlib
        h = hashlib.sha256()
        h.update(np.asarray([self.n, self.m], dtype=np.int64).tobytes())
        for a in (self.v2e_indptr, self.v2e_indices,
                  self.e2v_indptr, self.e2v_indices):
            h.update(np.ascontiguousarray(a).tobytes())
        fp = h.hexdigest()[:16]
        object.__setattr__(self, "_fingerprint", fp)
        return fp

    def stats(self) -> dict:
        es, vd = self.edge_sizes, self.vertex_degrees
        return {
            "n_vertices": self.n,
            "n_hyperedges": self.m,
            "n_pins": self.n_pins,
            "max_edge_size": int(es.max()) if self.m else 0,
            "mean_edge_size": float(es.mean()) if self.m else 0.0,
            "max_vertex_degree": int(vd.max()) if self.n else 0,
            "mean_vertex_degree": float(vd.mean()) if self.n else 0.0,
        }

    # Sorted-by-size edge order (ascending); HYPE sorts hyperedges once.
    def edges_by_size(self) -> np.ndarray:
        return np.argsort(self.edge_sizes, kind="stable")
