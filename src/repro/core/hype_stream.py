"""Single-pass streaming / online HYPE partitioner (DESIGN.md §4h).

Every engine in the ladder needs the full hypergraph up front; this one
maintains an assignment while vertices *arrive*. Two modes share one
state object:

  * **Streaming pass** (``hype_stream_partition``): vertices arrive in a
    deterministic stream order and are buffered into micro-batches. Each
    micro-batch is one device call (``scoring.stream_step_device``): the
    fused ``hype_score_select`` Pallas kernel scores the batch against
    all k partition *fringes* at once, then a sequential on-device
    commit loop scores each vertex's k targets against the live
    **partition sketch** — per-partition hashed edge-presence counts,
    ``(k, 2**sketch_bits)`` int32 — with a FREIGHT-style balance
    penalty, and admits it under a hard capacity cap. The sketch and
    size vectors stay device-resident (donated) across batches; only
    the (mb, L) tiles go down and the (mb,) choices come back.

        score(v, p) = conn(v, p) + fringe_weight * |N(v) ∩ fringe_p|
                      - balance_alpha * size_p * (k / n)

    where ``conn(v, p)`` counts incident hyperedges whose sketch bucket
    is already present in partition ``p``. Ties break to the lowest
    partition id; at ``micro_batch=1`` the schedule is exactly the
    sequential streaming algorithm, replicated bit-for-bit by the numpy
    oracle in tests/test_hype_stream.py.

  * **Incremental mode** (``apply_updates``): vertex/edge insertions
    and deletions mutate the existing assignment. Deletions
    exact-decrement the sketch (the same invariant the superstep
    engines keep for their score cache: ``sketch[p, b]`` always equals
    the recount over current pins — digest-testable, zero residue);
    insertions re-admit new vertices through the same micro-batch
    scorer; and the *dirtied neighborhoods* — everything within
    ``update_radius`` hops of a touched vertex or edge — are locally
    re-expanded through one bounded ``refine_kway`` pass
    (``candidates=``-restricted, the PR 5 subsystem), never the whole
    graph.

Resilience follows the engine family's contract: ``snapshot_every``
micro-batches publish a ``PartitionCheckpoint`` (exact same-config
restore resumes the stream bit-identically), and a ``FaultPlan``
(knob or ``REPRO_FAULT_PLAN``) injects pre-dispatch faults that are
retried by replaying the deterministic batch. Device bytes participate
in the §4g planner via ``membudget.plan_stream_memory`` — a tight
budget halves the micro-batch, then drops the tile width.
"""
from __future__ import annotations

import dataclasses
import hashlib
import time
from typing import Iterable, Optional, Sequence, Tuple

import numpy as np

from . import membudget, refine, resilience, scoring
from .hypergraph import Hypergraph
from .resilience import UnrecoverableFault

# Documented one-pass quality bound: km1(hype_stream) / km1(offline hype)
# on the quick generators stays under this factor. Streaming-partitioner
# papers report 1.5-4x for single-pass algorithms vs offline baselines;
# measured here the sketch+fringe scorer lands at 0.9-1.1x, so 2.0 keeps
# a comfortable margin. Enforced by tests/test_hype_stream.py and the
# compare_baseline bench gate (meta.streaming rows).
STREAM_KM1_BOUND = 2.0


@dataclasses.dataclass(frozen=True)
class StreamParams:
    """Knobs of the streaming engine (see module doc for semantics)."""
    micro_batch: int = 64       # vertices per device call
    sketch_bits: int = 16       # sketch table width: 2**sketch_bits buckets
    update_radius: int = 2      # dirty-neighborhood hops in apply_updates
    s: int = 16                 # fringe slots per partition
    balance_alpha: float = 1.0  # FREIGHT-style balance penalty weight
    fringe_weight: float = 0.5  # weight of the fringe-intersection term
    order: str = "random"       # arrival order: "random" (seeded) | "natural"
    seed: int = 0
    snapshot_every: int = 0     # micro-batches between snapshots (0 = off)
    snapshot_dir: Optional[str] = None
    keep_last: int = 3
    resume: Optional[str] = None
    fault_plan: Optional[object] = None
    max_retries: int = 2
    mem_budget: Optional[object] = None


@dataclasses.dataclass
class StreamStats:
    """Counters of one stream (and its later ``apply_updates`` calls)."""
    vertices: int = 0             # vertices admitted by the stream pass
    micro_batches: int = 0
    device_calls: int = 0
    kernel_rows: int = 0          # batch rows scored by the fused kernel
    host_to_device_bytes: int = 0
    stream_s: float = 0.0
    vertices_per_s: float = 0.0   # sustained stream throughput
    # memory plan (DESIGN.md §4g participation)
    planned_bytes: int = 0
    plan_micro_batch: int = 0
    plan_tile_l: int = 0
    # resilience
    faults_injected: int = 0
    retries: int = 0
    snapshots: int = 0
    snapshot_s: float = 0.0
    restore_s: float = 0.0
    resumed_at: int = -1          # micro-batch ordinal a resume continued at
    # incremental mode
    updates_applied: int = 0
    inserts: int = 0
    deletes: int = 0
    readmitted: int = 0           # vertices re-admitted by apply_updates
    refine_moves: int = 0         # bounded-radius re-expansion moves
    rebalance_moves: int = 0      # balance-guard forced moves
    update_s: float = 0.0
    updates_per_s: float = 0.0


@dataclasses.dataclass
class StreamState:
    """The online partitioner's full mutable state.

    ``assignment[v] == -1`` marks a vertex not currently admitted
    (never streamed yet, or deleted); ``full_assignment()`` fills those
    deterministically for metrics. The sketch invariant — maintained
    exactly by both modes — is ``sketch == recompute_sketch(...)``:
    every (pin, partition) incidence of the *current* graph is counted
    exactly once (``sketch_digest`` pins it in tests).
    """
    hg: Hypergraph
    k: int
    params: StreamParams
    assignment: np.ndarray        # (n,) int32, -1 = not admitted
    sizes: np.ndarray             # (k,) int32 admitted counts
    sketch: np.ndarray            # (k, 2**sketch_bits) int32
    fringe: np.ndarray            # (k, s) int32, -1 = empty slot
    fringe_pos: np.ndarray        # (k,) int64 ring write cursors
    cursor: int = 0               # vertices consumed from the stream order
    batch_idx: int = 0            # micro-batch ordinal (1-based after ++)
    stats: StreamStats = dataclasses.field(default_factory=StreamStats)

    def sketch_digest(self) -> str:
        """sha256 of (sketch, sizes) — the exact-decrement invariant."""
        h = hashlib.sha256()
        h.update(np.ascontiguousarray(self.sketch).tobytes())
        h.update(np.ascontiguousarray(self.sizes).tobytes())
        return h.hexdigest()[:16]

    def full_assignment(self) -> np.ndarray:
        """Complete assignment: unadmitted slots fill smallest-first.

        Deterministic (lowest partition id on ties, ascending vertex
        id), so metrics over a state with deletions are reproducible.
        """
        return _fill_unassigned(self.assignment, self.k)


def recompute_sketch(hg: Hypergraph, assignment: np.ndarray, k: int,
                     sketch_bits: int) -> Tuple[np.ndarray, np.ndarray]:
    """From-scratch ``(sketch, sizes)`` of (hg, assignment).

    The ground truth the exact-decrement bookkeeping must match: one
    count per current pin (v, e) with ``assignment[v] >= 0``.
    """
    sketch = np.zeros((k, 1 << sketch_bits), dtype=np.int32)
    sizes = np.bincount(assignment[assignment >= 0],
                        minlength=k).astype(np.int32)
    vids = hg.e2v_indices.astype(np.int64)
    eids = np.repeat(np.arange(hg.m, dtype=np.int64), hg.edge_sizes)
    parts = assignment[vids]
    live = parts >= 0
    buckets = scoring.stream_bucket(eids[live], sketch_bits)
    np.add.at(sketch, (parts[live].astype(np.int64), buckets), 1)
    return sketch, sizes


def _fill_unassigned(assignment: np.ndarray, k: int) -> np.ndarray:
    out = np.array(assignment, dtype=np.int32, copy=True)
    holes = np.flatnonzero(out < 0)
    if holes.size == 0:
        return out
    sizes = np.bincount(out[out >= 0], minlength=k).astype(np.int64)
    for v in holes:
        p = int(np.argmin(sizes))      # first-min = lowest id on ties
        out[v] = p
        sizes[p] += 1
    return out


# ----------------------------------------------------------- tile building

def _csr_tile(indptr, indices, ids: np.ndarray, cap: int,
              pad_rows: int) -> np.ndarray:
    """(pad_rows, L) -1-padded tile of CSR rows, truncated at ``cap``.

    Rows keep their CSR (sorted ascending) order; the width bucket is
    the smallest ``L_BUCKETS`` entry covering the truncated max row.
    The numpy oracle slices the same CSR rows at the same cap, so both
    sides see identical (possibly truncated) neighborhoods.
    """
    vals, owner = scoring.gather_csr_rows(indptr, indices, ids)
    counts = np.bincount(owner, minlength=ids.size) if vals.size else \
        np.zeros(ids.size, dtype=np.int64)
    width = int(min(counts.max() if counts.size else 0, cap))
    L = scoring._bucket_width(max(width, 1))
    tile = np.full((pad_rows, L), -1, np.int32)
    if vals.size:
        row_start = np.cumsum(counts) - counts
        offs = np.arange(vals.size, dtype=np.int64) - row_start[owner]
        keep = offs < cap
        tile[owner[keep], offs[keep]] = vals[keep]
    return tile


def _stream_adjacency(hg: Hypergraph):
    adj = hg.vertex_adjacency()
    if adj is not None:
        return adj
    # hub-expansion guard tripped: fall back to a degenerate adjacency
    # built per batch via neighbor_tile (rare; quality path unchanged)
    return None


def _batch_tiles(hg: Hypergraph, adj, batch: np.ndarray, tile_cap: int,
                 pad_rows: int) -> Tuple[np.ndarray, np.ndarray]:
    """Edge and neighbor tiles for a (pad-stripped) micro-batch."""
    edge_tile = _csr_tile(hg.v2e_indptr, hg.v2e_indices, batch,
                          tile_cap, pad_rows)
    if adj is not None:
        nbr_tile = _csr_tile(adj[0], adj[1], batch, tile_cap, pad_rows)
    else:
        dummy = np.full(hg.n, -1, np.int32)   # no assigned-filtering
        nbr_tile, _ = scoring.neighbor_tile(hg, batch, dummy,
                                            pad_b=pad_rows)
        if nbr_tile.shape[1] > tile_cap:
            nbr_tile = nbr_tile[:, :tile_cap]
    return edge_tile, nbr_tile


# --------------------------------------------------------------- the pass

def _validate_params(p: StreamParams) -> None:
    if p.micro_batch < 1:
        raise ValueError(f"micro_batch must be >= 1, got {p.micro_batch}")
    if not 4 <= p.sketch_bits <= 24:
        raise ValueError(
            f"sketch_bits must be in [4, 24], got {p.sketch_bits}")
    if p.s < 1:
        raise ValueError(f"s must be >= 1, got {p.s}")
    if p.order not in ("random", "natural"):
        raise ValueError(
            f"order must be 'random' or 'natural', got {p.order!r}")
    if p.update_radius < 0:
        raise ValueError(
            f"update_radius must be >= 0, got {p.update_radius}")
    if p.snapshot_every > 0 and not p.snapshot_dir:
        raise ValueError("snapshot_every > 0 requires snapshot_dir")


def _stream_order(n: int, p: StreamParams) -> np.ndarray:
    if p.order == "natural":
        return np.arange(n, dtype=np.int64)
    return np.random.default_rng(p.seed).permutation(n)


def _config_dict(state: StreamState, plan_mb: int, plan_tl: int) -> dict:
    p = state.params
    return {"k": state.k, "micro_batch": plan_mb, "tile_l": plan_tl,
            "sketch_bits": p.sketch_bits, "s": p.s,
            "balance_alpha": p.balance_alpha,
            "fringe_weight": p.fringe_weight, "order": p.order,
            "seed": p.seed}


def _push_fringe(state: StreamState, vs: np.ndarray,
                 parts: np.ndarray) -> None:
    """Ring-append admitted vertices to their partitions' fringes."""
    s = state.fringe.shape[1]
    for p in np.unique(parts[parts >= 0]):
        vp = vs[parts == p]
        pos = int(state.fringe_pos[p])
        if vp.size >= s:
            # only the last s sequential writes survive a full wrap
            start = (pos + vp.size - s) % s
            state.fringe[p, (start + np.arange(s)) % s] = vp[-s:]
        else:
            state.fringe[p, (pos + np.arange(vp.size)) % s] = vp
        state.fringe_pos[p] = pos + vp.size


def _snapshot(state: StreamState, plan_mb: int, plan_tl: int,
              sketch_dev, sizes_dev) -> None:
    t0 = time.perf_counter()
    state.sketch = np.array(sketch_dev, dtype=np.int32)
    state.sizes = np.array(sizes_dev, dtype=np.int32)
    ckpt = resilience.PartitionCheckpoint(
        engine="hype_stream", superstep=state.batch_idx,
        fingerprint=state.hg.fingerprint(),
        config=_config_dict(state, plan_mb, plan_tl),
        payload={"assignment": state.assignment.copy(),
                 "sizes": state.sizes.copy(),
                 "sketch": state.sketch.copy(),
                 "fringe": state.fringe.copy(),
                 "fringe_pos": state.fringe_pos.copy(),
                 "cursor": state.cursor,
                 "batch_idx": state.batch_idx})
    resilience.save_snapshot(state.params.snapshot_dir, ckpt,
                             state.params.keep_last)
    state.stats.snapshots += 1
    state.stats.snapshot_s += time.perf_counter() - t0


def _try_resume(state: StreamState, plan_mb: int, plan_tl: int) -> None:
    ckpt = resilience.load_latest(state.params.resume)
    if ckpt is None:
        return
    resilience.check_checkpoint(ckpt, state.hg, state.k)
    if ckpt.engine != "hype_stream" \
            or ckpt.config != _config_dict(state, plan_mb, plan_tl):
        return                      # cross-config snapshots cold-start
    t0 = time.perf_counter()
    pay = ckpt.payload
    state.assignment = np.asarray(pay["assignment"], np.int32).copy()
    state.sizes = np.asarray(pay["sizes"], np.int32).copy()
    state.sketch = np.asarray(pay["sketch"], np.int32).copy()
    state.fringe = np.asarray(pay["fringe"], np.int32).copy()
    state.fringe_pos = np.asarray(pay["fringe_pos"], np.int64).copy()
    state.cursor = int(pay["cursor"])
    state.batch_idx = int(pay["batch_idx"])
    state.stats.resumed_at = state.batch_idx
    state.stats.restore_s = time.perf_counter() - t0


def _fire_faults(plan, state: StreamState, ordinal: int) -> None:
    """Pre-dispatch fault site: injected faults replay the batch.

    Faults fire *before* the device call so the donated sketch/size
    buffers are never half-consumed; the batch is deterministic, so a
    retry replays it bit-identically. A fatal spec or an exhausted
    retry budget raises ``UnrecoverableFault``.
    """
    if plan is None:
        return
    retries = 0
    while True:
        spec = plan.fire(("dispatch", "nan"), ordinal)
        if spec is None:
            return
        state.stats.faults_injected += 1
        if spec.fatal:
            raise UnrecoverableFault(
                f"fatal injected {spec.kind} fault at stream "
                f"micro-batch {ordinal}")
        retries += 1
        state.stats.retries += 1
        if retries > state.params.max_retries:
            raise UnrecoverableFault(
                f"retry budget exhausted at stream micro-batch "
                f"{ordinal} ({retries} injected faults)")


def _run_stream(state: StreamState, order: np.ndarray, cap: int,
                plan_mb: int, plan_tl: int, plan) -> None:
    """Consume ``order[state.cursor:]`` in micro-batches of ``plan_mb``."""
    import jax.numpy as jnp
    from repro.kernels._compat import pallas_interpret

    hg, k, p, st = state.hg, state.k, state.params, state.stats
    n = hg.n
    adj = _stream_adjacency(hg)
    inv_target = np.float32(k / max(n, 1))
    sketch_dev = jnp.asarray(state.sketch)
    sizes_dev = jnp.asarray(state.sizes)
    t0 = time.perf_counter()
    snap_every = p.snapshot_every
    while state.cursor < order.size:
        batch = order[state.cursor:state.cursor + plan_mb]
        nb = batch.size
        edge_tile, nbr_tile = _batch_tiles(hg, adj, batch, plan_tl,
                                           plan_mb)
        valid_row = np.zeros(plan_mb, dtype=bool)
        valid_row[:nb] = True
        ordinal = state.batch_idx + 1
        _fire_faults(plan, state, ordinal)
        parts_dev, sketch_dev, sizes_dev = scoring.stream_step_device(
            jnp.asarray(edge_tile), jnp.asarray(nbr_tile),
            jnp.asarray(state.fringe), sketch_dev, sizes_dev,
            jnp.asarray(valid_row), alpha=p.balance_alpha,
            fringe_w=p.fringe_weight, inv_target=float(inv_target),
            cap=cap, sketch_bits=p.sketch_bits,
            interpret=pallas_interpret())
        parts = np.asarray(parts_dev)[:nb]
        state.assignment[batch] = parts
        _push_fringe(state, batch, parts)
        state.cursor += nb
        state.batch_idx = ordinal
        st.micro_batches += 1
        st.device_calls += 1
        st.kernel_rows += plan_mb
        st.host_to_device_bytes += (edge_tile.nbytes + nbr_tile.nbytes
                                    + state.fringe.nbytes + plan_mb)
        st.vertices += nb
        if snap_every and state.batch_idx % snap_every == 0:
            _snapshot(state, plan_mb, plan_tl, sketch_dev, sizes_dev)
    state.sketch = np.array(sketch_dev, dtype=np.int32)
    state.sizes = np.array(sizes_dev, dtype=np.int32)
    st.stream_s += time.perf_counter() - t0
    st.vertices_per_s = st.vertices / max(st.stream_s, 1e-9)


def hype_stream_partition(hg: Hypergraph, k: int,
                          params: Optional[StreamParams] = None, *,
                          return_stats: bool = False,
                          return_state: bool = False):
    """One streaming pass over ``hg``; see the module doc.

    Returns the complete int32 assignment; with ``return_stats`` a
    ``(assignment, StreamStats)`` pair, with ``return_state`` a
    ``(assignment, StreamState)`` pair (the state carries ``.stats``
    and feeds ``apply_updates``). Balance: ``max - min <= k`` via the
    hard ``ceil(n/k)`` capacity cap.
    """
    from repro.kernels._compat import configure_compile_cache

    p = params or StreamParams()
    _validate_params(p)
    if k < 1:
        raise ValueError(f"k must be >= 1, got {k}")
    configure_compile_cache()
    state = _fresh_state(hg, k, p)
    if k == 1 or hg.n == 0:
        state.assignment[:] = 0 if k >= 1 else -1
        state.sizes = np.bincount(
            state.assignment[state.assignment >= 0],
            minlength=k).astype(np.int32)
        state.sketch, state.sizes = recompute_sketch(
            hg, state.assignment, k, p.sketch_bits)
        return _pack_result(state, return_stats, return_state)

    # memory plan (DESIGN.md §4g): streaming buffers go through the
    # byte planner; a tight budget halves the micro-batch, then the
    # tile width — pre-emptive, the stream never donates-then-dies
    budget = membudget.resolve_budget(p.mem_budget)
    spec = membudget.StreamSpec(
        n=hg.n, k=k, micro_batch=p.micro_batch,
        sketch_bits=p.sketch_bits, s=p.s,
        tile_l=scoring.L_BUCKETS[-1])
    plan_mb, plan_tl, planned, _fits = membudget.plan_stream_memory(
        spec, budget)
    state.stats.planned_bytes = planned
    state.stats.plan_micro_batch = plan_mb
    state.stats.plan_tile_l = plan_tl

    plan = resilience.resolve_fault_plan(p.fault_plan)
    if p.resume:
        _try_resume(state, plan_mb, plan_tl)
    order = _stream_order(hg.n, p)
    cap = -(-hg.n // k)
    _run_stream(state, order, cap, plan_mb, plan_tl, plan)
    return _pack_result(state, return_stats, return_state)


def _fresh_state(hg: Hypergraph, k: int, p: StreamParams) -> StreamState:
    return StreamState(
        hg=hg, k=k, params=p,
        assignment=np.full(hg.n, -1, np.int32),
        sizes=np.zeros(k, np.int32),
        sketch=np.zeros((k, 1 << p.sketch_bits), np.int32),
        fringe=np.full((k, p.s), -1, np.int32),
        fringe_pos=np.zeros(k, np.int64))


def _pack_result(state: StreamState, return_stats: bool,
                 return_state: bool):
    assignment = state.assignment.copy()
    if return_state:
        return assignment, state
    if return_stats:
        return assignment, state.stats
    return assignment


# --------------------------------------------------------- incremental mode

def _sketch_add(state: StreamState, part: int, edge_ids: np.ndarray,
                sign: int) -> None:
    """Exact sketch increment/decrement for pins of one vertex."""
    if edge_ids.size == 0:
        return
    buckets = scoring.stream_bucket(edge_ids, state.params.sketch_bits)
    np.add.at(state.sketch[part], buckets, sign)


def _expand_radius(hg: Hypergraph, seeds: np.ndarray,
                   radius: int) -> np.ndarray:
    """Vertices within ``radius`` hops of ``seeds`` (seeds included)."""
    seeds = np.unique(seeds.astype(np.int64))
    if radius <= 0 or seeds.size == 0:
        return seeds
    adj = hg.vertex_adjacency()
    if adj is None:
        return seeds
    frontier, dirty = seeds, seeds
    for _ in range(radius):
        nbrs, _ = scoring.gather_csr_rows(adj[0], adj[1], frontier)
        frontier = np.setdiff1d(np.unique(nbrs.astype(np.int64)), dirty)
        if frontier.size == 0:
            break
        dirty = np.union1d(dirty, frontier)
    return dirty


def _readmit(state: StreamState, new_vs: np.ndarray) -> None:
    """Stream-admit queued vertices against the current sketch/fringe."""
    if new_vs.size == 0:
        return
    import jax.numpy as jnp
    from repro.kernels._compat import pallas_interpret

    hg, k, p, st = state.hg, state.k, state.params, state.stats
    active = int((state.assignment >= 0).sum()) + int(new_vs.size)
    cap = max(-(-active // k), int(state.sizes.max()))
    inv_target = np.float32(k / max(hg.n, 1))
    adj = _stream_adjacency(hg)
    mb = st.plan_micro_batch or p.micro_batch
    tl = st.plan_tile_l or scoring.L_BUCKETS[-1]
    sketch_dev = jnp.asarray(state.sketch)
    sizes_dev = jnp.asarray(state.sizes)
    for b0 in range(0, new_vs.size, mb):
        batch = new_vs[b0:b0 + mb]
        edge_tile, nbr_tile = _batch_tiles(hg, adj, batch, tl, mb)
        valid_row = np.zeros(mb, dtype=bool)
        valid_row[:batch.size] = True
        parts_dev, sketch_dev, sizes_dev = scoring.stream_step_device(
            jnp.asarray(edge_tile), jnp.asarray(nbr_tile),
            jnp.asarray(state.fringe), sketch_dev, sizes_dev,
            jnp.asarray(valid_row), alpha=p.balance_alpha,
            fringe_w=p.fringe_weight, inv_target=float(inv_target),
            cap=cap, sketch_bits=p.sketch_bits,
            interpret=pallas_interpret())
        parts = np.asarray(parts_dev)[:batch.size]
        state.assignment[batch] = parts
        st.device_calls += 1
        st.readmitted += int(batch.size)
    state.sketch = np.array(sketch_dev, dtype=np.int32)
    state.sizes = np.array(sizes_dev, dtype=np.int32)


def _local_refine(state: StreamState, dirty: np.ndarray) -> None:
    """Bounded-radius re-expansion: one candidate-restricted refine pass."""
    if dirty.size == 0 or state.k <= 1:
        return
    hg, k = state.hg, state.k
    before = _fill_unassigned(state.assignment, k)
    refined, _rs = refine.refine_kway(
        hg, before, k, passes=1, candidates=dirty, use_device=False)
    moved = np.flatnonzero((refined != before)
                           & (state.assignment >= 0))
    for v in moved:
        src, dst = int(before[v]), int(refined[v])
        es = hg.vertex_edges(int(v)).astype(np.int64)
        _sketch_add(state, src, es, -1)
        _sketch_add(state, dst, es, +1)
        state.assignment[v] = dst
        state.sizes[src] -= 1
        state.sizes[dst] += 1
        state.stats.refine_moves += 1


def _rebalance_guard(state: StreamState) -> None:
    """Force the documented ``max - min <= k`` slack after deletions.

    Deterministic: while the slack is violated, move the best-gain
    (lowest id on ties) vertex from the largest partition to the
    smallest, keeping the sketch exact per move.
    """
    hg, k = state.hg, state.k
    adj = hg.vertex_adjacency()
    while True:
        sizes = state.sizes
        p_big = int(np.argmax(sizes))
        p_small = int(np.argmin(sizes))
        if int(sizes[p_big]) - int(sizes[p_small]) <= k:
            return
        cand = np.flatnonzero(state.assignment == p_big)
        if cand.size == 0:
            return
        if adj is not None:
            gains = refine._host_gains(
                adj, cand, _fill_unassigned(state.assignment, k),
                k)[:, p_small]
            v = int(cand[np.lexsort((cand, -gains))[0]])
        else:
            v = int(cand[0])
        es = hg.vertex_edges(v).astype(np.int64)
        _sketch_add(state, p_big, es, -1)
        _sketch_add(state, p_small, es, +1)
        state.assignment[v] = p_small
        state.sizes[p_big] -= 1
        state.sizes[p_small] += 1
        state.stats.rebalance_moves += 1


def apply_updates(state: StreamState,
                  ops: Sequence[Tuple]) -> StreamState:
    """Replay an op log against the live state; returns ``state``.

    Ops (applied in order):

      * ``("add_vertex", edge_ids)`` — append vertex ``n`` joining the
        listed existing hyperedges; it is re-admitted through the
        streaming scorer at the end of the call.
      * ``("remove_vertex", v)`` — drop all pins of ``v``; its slot
        stays (isolated), its sketch contributions are exact-decremented
        and it leaves every fringe.
      * ``("add_edge", vertex_ids)`` — append hyperedge ``m`` over the
        listed existing vertices; assigned pins increment the sketch.
      * ``("remove_edge", e)`` — drop all pins of hyperedge ``e``;
        assigned pins exact-decrement the sketch.

    After the log replays, new vertices are admitted micro-batch-wise,
    the dirtied neighborhoods (``update_radius`` hops around every
    touched vertex) get one candidate-restricted ``refine_kway`` pass,
    and a balance guard restores the documented ``max - min <= k``
    slack if deletions broke it. The sketch invariant
    (``sketch_digest() == digest(recompute_sketch(...))``) holds at
    return — the property the incremental-consistency suite pins.
    """
    t0 = time.perf_counter()
    st = state.stats
    dirty_parts: list = []
    new_vs: list = []
    for op in ops:
        kind = op[0]
        if kind == "add_vertex":
            edge_ids = np.asarray(list(op[1]), dtype=np.int64)
            vid = state.hg.n
            state.hg = state.hg.with_vertices([edge_ids.tolist()])
            state.assignment = np.append(
                state.assignment, np.int32(-1)).astype(np.int32)
            if edge_ids.size:
                pins, _ = scoring.gather_csr_rows(
                    state.hg.e2v_indptr, state.hg.e2v_indices, edge_ids)
                dirty_parts.append(pins.astype(np.int64))
            new_vs.append(vid)
            st.inserts += 1
        elif kind == "remove_vertex":
            v = int(op[1])
            part = int(state.assignment[v])
            es = state.hg.vertex_edges(v).astype(np.int64)
            dirty_parts.append(state.hg.neighbors(v).astype(np.int64))
            if part >= 0:
                _sketch_add(state, part, es, -1)
                state.sizes[part] -= 1
            state.assignment[v] = -1
            state.fringe[state.fringe == v] = -1
            state.hg = state.hg.without_vertices([v])
            new_vs = [u for u in new_vs if u != v]
            st.deletes += 1
        elif kind == "add_edge":
            pins = np.asarray(list(op[1]), dtype=np.int64)
            e = state.hg.m
            state.hg = state.hg.with_edges([pins.tolist()])
            b = int(scoring.stream_bucket(
                np.asarray([e]), state.params.sketch_bits)[0])
            # de-duplicated pins (from_pins semantics)
            for part in state.assignment[np.unique(pins)]:
                if part >= 0:
                    state.sketch[int(part), b] += 1
            dirty_parts.append(pins)
            st.inserts += 1
        elif kind == "remove_edge":
            e = int(op[1])
            pins = state.hg.edge_pins(e).astype(np.int64)
            b = int(scoring.stream_bucket(
                np.asarray([e]), state.params.sketch_bits)[0])
            for part in state.assignment[pins]:
                if part >= 0:
                    state.sketch[int(part), b] -= 1
            dirty_parts.append(pins)
            state.hg = state.hg.without_edges([e])
            st.deletes += 1
        else:
            raise ValueError(f"unknown stream op kind {kind!r}")
    st.updates_applied += len(ops)

    queued = np.asarray(sorted(set(new_vs)), dtype=np.int64)
    _readmit(state, queued)
    dirty = np.concatenate([a for a in dirty_parts if a.size]
                           + [queued]) if (dirty_parts or queued.size) \
        else np.empty(0, np.int64)
    dirty = dirty[dirty < state.hg.n]
    dirty = _expand_radius(state.hg, dirty, state.params.update_radius)
    _local_refine(state, dirty)
    _rebalance_guard(state)
    st.update_s += time.perf_counter() - t0
    st.updates_per_s = st.updates_applied / max(st.update_s, 1e-9)
    return state
