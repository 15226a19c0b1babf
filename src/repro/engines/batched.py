"""Batched-candidate HYPE: the throughput-oriented host engine (§4).

The paper's engine (``core/hype.py``) moves ONE vertex per growth step
and scores r=2 candidates at a time — latency-bound, CPU-idiomatic.
This engine turns the inner loop into tile work:

  per growth step
    1. (when the candidate pool runs low) draw a bulk batch of candidate
       vertices from the *smallest* active hyperedges — size-bucketed
       queues instead of a heap, one vectorized pin scan per draw,
    2. gather their unassigned-neighbor lists as dense (b, L) tiles
       (``scoring.neighbor_tile_adj``; assigned pins dropped, hubs
       capped),
    3. score every cache-miss candidate through the Pallas
       ``hype_scores`` kernel (fringe membership subtracted on the VPU),
    4. keep scored candidates in a pool sorted by score — the paper's
       s-sized fringe is its top-s — and admit the top-``t`` per step.

``t`` is the quality/speed knob: steps per partition drop from
O(target) to O(target / t); ``t=1`` recovers the sequential admission
order (same greedy rule, wider candidate pool). Scores are lazily
cached per phase exactly like the paper's optimization (c), so the
kernel only sees first-time candidates.

This is the first real consumer of ``kernels/hype_score`` — on CPU the
kernel runs in interpret mode (still one fused batched evaluation); on
TPU the same call compiles to the VPU tile loop the kernel was built
for. The device-resident engines live in their sibling modules
(``engines.superstep`` / ``engines.sharded`` / ``engines.device``) on
the shared ``engines.runtime`` driver.
"""
from __future__ import annotations

import collections
import dataclasses
from typing import Optional

import numpy as np

from ..core.hypergraph import Hypergraph
from ..core import resilience
from ..core import scoring
from .runtime import (BatchedStats, EngineRuntime, maybe_refine,
                      partition_root, span)


@dataclasses.dataclass
class BatchedParams:
    b: int = 256           # rows per kernel tile (the paper's r=2)
    s: int = 16            # max fringe size (kernel compares vs s slots)
    t: int = 8             # admissions per step; 1 = sequential order
    pool_cap: int = 64     # scored candidates held between steps
    refill_lo: int = 64    # refill the pool when it drops below this
    cap_pins: int = 3072   # pins scanned per candidate before truncation
    kernel_min: int = 16   # min batch worth a device round-trip; smaller
    #                        dribbles score on host (same formula and hub
    #                        truncation convention as the kernel tiles)
    refine_passes: int = 0  # post-pass boundary-refinement passes
    #                         (core/refine.py, DESIGN.md §4e); 0 = off,
    #                         output bit-identical to the bare engine
    seed: int = 0
    # resilience knobs (core/resilience.py, DESIGN.md §4f):
    snapshot_every: int = 0     # checkpoint cadence, counted in
    #                             supersteps (device engines) or
    #                             completed phases (batched); 0 = never.
    #                             The cadence is part of the schedule: a
    #                             resumed run is bit-identical to an
    #                             uninterrupted run with the SAME cadence
    #                             (snapshots drain the pipeline).
    snapshot_dir: Optional[str] = None   # where snapshots are published
    keep_last: int = 3          # snapshots the GC retains per directory
    resume: Optional[str] = None    # snapshot file or directory to
    #                                 resume from; a missing or empty
    #                                 directory starts fresh (what the
    #                                 degradation ladder wants)
    fault_plan: Optional[object] = None  # resilience.FaultPlan instance,
    #                                      spec string, or None = read
    #                                      the REPRO_FAULT_PLAN env var
    max_retries: int = 2        # transient-fault retry budget per call
    retry_backoff_s: float = 0.01   # linear backoff between retries


class BatchedState(EngineRuntime):
    """Mutable state for the k growth phases (host side, all numpy)."""

    def __init__(self, hg: Hypergraph, k: int, p: BatchedParams):
        super().__init__(hg, k, p)
        n, m = hg.n, hg.m
        self.in_fringe = np.zeros(n, dtype=bool)
        self.cur_fringe = np.empty(0, dtype=np.int64)
        self.cache = np.full(n, -1.0)
        self.edge_epoch = np.full(m, -1, dtype=np.int32)   # activation epoch
        # size-bucketed active-edge queues (replaces the paper's min-heap):
        # buckets[size] is a FIFO of edge-id arrays; scanning pops from the
        # front and re-queues still-live edges at the front, so smallest
        # edges keep being drawn first, like the heap's requeue.
        self.buckets: dict = {}
        self._fringe_buf = np.full(p.s, -1, dtype=np.int32)

    def set_fringe(self, new_fringe: np.ndarray) -> None:
        """Sync the s-sized fringe view (paper's F) used for scoring."""
        self.in_fringe[self.cur_fringe] = False
        self.in_fringe[new_fringe] = True
        self.cur_fringe = new_fringe
        self._fringe_buf[:] = -1
        self._fringe_buf[:new_fringe.size] = new_fringe

    # ------------------------------------------------------------------ #
    def activate(self, vs: np.ndarray, phase: int) -> None:
        """Mark the edges incident to newly admitted vertices active."""
        edges, _ = scoring.gather_csr_rows(
            self.hg.v2e_indptr, self.hg.v2e_indices, vs)
        if edges.size == 0:
            return
        edges = np.unique(edges.astype(np.int64))
        fresh = edges[(self.edge_epoch[edges] != phase)
                      & ~self.edge_dead[edges]]
        if fresh.size == 0:
            return
        self.edge_epoch[fresh] = phase
        sizes = self.edge_sizes[fresh]
        for sz in np.unique(sizes):
            self.buckets.setdefault(int(sz), collections.deque()).append(
                fresh[sizes == sz])

    # ------------------------------------------------------------------ #
    def draw_candidates(self, need: int) -> np.ndarray:
        """Up to ``need`` distinct universe vertices from smallest edges.

        One vectorized pass: pull edges smallest-size-first under a pin
        budget, scan all their pins at once, retire dead edges (no
        unassigned pin left — forever), requeue the still-live ones at the
        bucket fronts so they are rescanned first next time (the heap's
        requeue, without the heap). Serves the classic batched engine;
        the superstep engines draw all phases at once from the flat
        bucket store instead (``PipelineState.pack_superstep``).
        """
        buckets = self.buckets
        in_pool = self.in_pool
        if need <= 0:
            return np.empty(0, dtype=np.int64)
        budget = max(4 * need, 512)
        batches: list = []
        keys: list = []     # (source bucket key, count) pairs, for requeues
        pulled = 0
        for sz in sorted(buckets.keys()):
            q = buckets[sz]
            while q and pulled < budget:
                arr = q.popleft()
                n_take = (budget - pulled + sz - 1) // max(sz, 1)
                if arr.size > n_take:
                    q.appendleft(arr[n_take:])
                    arr = arr[:n_take]
                batches.append(arr)
                keys.append((sz, arr.size))
                pulled += arr.size * max(sz, 1)
            if not q:
                del buckets[sz]
            if pulled >= budget:
                break
        if not batches:
            return np.empty(0, dtype=np.int64)
        edges = np.concatenate(batches)
        pins, prow = scoring.gather_csr_rows(
            self.hg.e2v_indptr, self.hg.e2v_indices, edges)
        pins = pins.astype(np.int64)
        unassigned = self.assignment[pins] < 0
        live = np.bincount(prow[unassigned], minlength=edges.size) > 0
        if not live.all():
            self.edge_dead[edges[~live]] = True     # dead forever
        live_edges = edges[live]
        if live_edges.size:
            # requeue under the key each edge was drawn from, so the
            # caller's key scheme (exact sizes for the classic engine,
            # power-of-two classes for the superstep engine) is preserved
            lkey = np.repeat([k for k, _ in keys],
                             [c for _, c in keys])[live]
            for s in np.unique(lkey):
                buckets.setdefault(
                    int(s), collections.deque()).appendleft(
                        live_edges[lkey == s])
        fresh = unassigned & ~in_pool[pins]
        cand = pins[fresh]
        if cand.size:
            _, first = np.unique(cand, return_index=True)
            cand = cand[np.sort(first)][:need]
        return cand

    # ------------------------------------------------------------------ #
    def score_misses(self, cand: np.ndarray) -> None:
        """Score cache-miss candidates in one batched pass, fill the cache.

        Large batches (every phase opening, where the bulk of the scoring
        lives) go through the Pallas ``hype_scores`` kernel as one (b, L)
        tile; dribbles below ``kernel_min`` rows are scored by the exact
        same formula on host, because a device round-trip per 2-3 rows is
        precisely the latency-bound pattern this engine exists to avoid.
        The fringe upload, once a pass, and each kernel call, tile upload
        and result read included, are the child span ``hype.refill.kernel``.
        """
        if cand.size == 0:
            return
        miss = cand[self.cache[cand] < 0.0]
        self.stats.cache_hits += cand.size - miss.size
        if miss.size == 0:
            return
        if miss.size >= self.p.kernel_min:
            import jax.numpy as jnp
            from repro.kernels.hype_score.ops import hype_scores

            plan = self.fault_plan
            with span(self, "hype.refill.kernel"):
                fringe_dev = jnp.asarray(self._fringe_buf)
            for lo in range(0, miss.size, self.p.b):
                chunk = miss[lo:lo + self.p.b]
                # two B buckets (64 / b) keep retraces rare while small
                # top-up batches avoid paying for a full-width tile
                pad_b = 64 if chunk.size <= 64 else self.p.b
                if self.adj is not None:
                    tile, truncated = scoring.neighbor_tile_adj(
                        self.adj, chunk, self.assignment, pad_b=pad_b)
                else:
                    tile, truncated = scoring.neighbor_tile(
                        self.hg, chunk, self.assignment,
                        cap_pins=self.p.cap_pins, pad_b=pad_b)
                ordinal = self.stats.kernel_calls + 1
                with span(self, "hype.refill.kernel"):
                    out = np.asarray(self._guarded_kernel(
                        lambda: hype_scores(jnp.asarray(tile), fringe_dev),
                        ordinal)).astype(np.float64)
                if plan is not None:
                    sp = plan.fire(("nan",), ordinal)
                    if sp is not None:    # poison the whole score tile
                        self.stats.faults_injected += 1
                        if sp.fatal:
                            raise resilience.UnrecoverableFault(
                                f"injected fatal nan tile at kernel "
                                f"call {ordinal}")
                        out = out.copy()
                        out[:chunk.size] = np.nan
                sc = out[:chunk.size]
                bad = ~np.isfinite(sc)
                if bad.any():   # quarantine: rescore poisoned rows on
                    #             host, bit-identical to a clean kernel
                    sc[bad] = self._rescore_rows(chunk[bad])
                    self.stats.host_rows += int(bad.sum())
                sc[truncated] += scoring.TRUNC_PENALTY
                self.cache[chunk] = sc
                self.stats.kernel_calls += 1
                self.stats.kernel_rows += int(chunk.size)
        else:
            if self.adj is not None:
                sc = scoring.batched_dext_adj(
                    self.adj, miss, self.in_fringe, self.assignment)
            else:
                sc = scoring.batched_dext_numpy(
                    self.hg, miss, self.in_fringe, self.assignment,
                    cap_pins=self.p.cap_pins,
                    max_width=scoring.L_BUCKETS[-1])
            self.stats.host_rows += int(miss.size)
            self.cache[miss] = sc

    def _rescore_rows(self, ids: np.ndarray) -> np.ndarray:
        """Host re-score of NaN-quarantined kernel rows (DESIGN.md §4f).

        Rebuilds the same clipped neighbor tile the kernel saw and
        emulates its count (valid entries minus fringe members), so the
        recovered scores are bit-identical to an unpoisoned kernel call:
        the kernel's integer counts are float32-exact and the truncation
        penalty is applied by the caller either way.
        """
        if self.adj is not None:
            tile, _ = scoring.neighbor_tile_adj(
                self.adj, ids, self.assignment)
        else:
            tile, _ = scoring.neighbor_tile(
                self.hg, ids, self.assignment, cap_pins=self.p.cap_pins)
        tile = tile[:ids.size]
        valid = tile >= 0
        ent = np.where(valid, tile, 0)
        return (valid & ~self.in_fringe[ent]).sum(axis=1).astype(
            np.float64)


def _grow_partition(st: BatchedState, phase: int, target: int,
                    warm: bool = False) -> None:
    """Grow core set ``phase`` to ``target`` vertices.

    The step loop keeps a *pool* of up to ``pool_cap`` scored candidates
    sorted by cached score. Refills happen in bulk (one kernel tile per
    ``b`` rows) whenever the pool runs low; between refills a step is just
    "admit the t best, queue their edges" — the latency-bound per-vertex
    machinery of the sequential engines is gone entirely. The paper's
    s-sized fringe survives as the top-s of the pool: it is what the
    scoring kernel subtracts, exactly like F in Eq. 1.

    ``warm`` continues a phase that already has members (a cross-engine
    warm start from a snapshot, DESIGN.md §4f): existing members are
    activated instead of seeding, and growth resumes from their count.
    """
    p = st.p
    st.cache[:] = -1.0
    st.buckets = {}
    pool = np.empty(0, dtype=np.int64)       # kept sorted by score asc
    pending: list = []                       # admitted, edges not yet queued

    acc = 0
    if warm:
        members = np.flatnonzero(st.assignment == phase)
        acc = int(members.size)
        if acc >= target:
            return
        if acc:
            st.activate(members.astype(np.int64), phase)
    if acc == 0:
        seeds = st.random_unassigned(1)
        if seeds.size == 0:
            return
        st.assignment[seeds] = phase
        st.activate(seeds, phase)
        acc = 1

    while acc < target:
        st.stats.steps += 1
        # ------- refill: bulk-draw and kernel-score new candidates -------
        if pool.size < max(p.t, p.refill_lo):
            if pending:
                with span(st, "hype.refill.activate"):
                    st.activate(np.concatenate(pending), phase)
                pending = []
            with span(st, "hype.refill.draw"):
                cand = st.draw_candidates(p.pool_cap - pool.size)
            if cand.size:
                with span(st, "hype.refill.score"):
                    st.score_misses(cand)
                st.in_pool[cand] = True
                pool = np.concatenate([pool, cand])
                pool = pool[np.argsort(st.cache[pool], kind="stable")]
                st.set_fringe(pool[:p.s])
        if pool.size == 0:                    # random restart (batched: on
            # shattered remainders each isolated vertex would otherwise
            # cost a full step, so seed up to t fresh growth points)
            vs = st.random_unassigned(p.t)
            if vs.size == 0:
                return
            pool = vs
            st.in_pool[vs] = True
            st.cache[vs] = 0.0
            st.set_fringe(pool[:p.s])
        # ------- core update: admit the t best pool vertices -------
        nt = min(p.t, target - acc, pool.size)
        admit, pool = pool[:nt], pool[nt:]
        st.assignment[admit] = phase
        st.in_pool[admit] = False
        pending.append(admit)
        st.set_fringe(pool[:p.s])
        acc += int(admit.size)

    # release fringe + pool back to the universe (§III-B1 step 4)
    st.set_fringe(np.empty(0, dtype=np.int64))
    st.in_pool[pool] = False


@partition_root
def hype_batched_partition(hg: Hypergraph, k: int,
                           params: Optional[BatchedParams] = None,
                           return_stats: bool = False):
    """Partition ``hg`` into ``k`` parts with batched-candidate HYPE.

    Same contract as ``hype_partition``: complete int32 assignment with
    perfectly balanced partition sizes (max - min <= 1).

    Resilience (DESIGN.md §4f): snapshots are phase-granular — between
    ``_grow_partition`` calls all transient state (score cache, pools,
    buckets) is empty, so a checkpoint is just the assignment plus edge
    flags and the random stream; resuming a same-config snapshot
    continues bit-identically, and a cross-engine snapshot (the
    degradation ladder) warm-starts every phase from its members.
    """
    if params is None:
        params = BatchedParams()
    if k < 1:
        raise ValueError("k must be >= 1")
    if params.t < 1 or params.b < 1 or params.s < 1:
        raise ValueError("b, s, t must all be >= 1")
    if params.pool_cap < 1:
        raise ValueError("pool_cap must be >= 1")
    if params.snapshot_every > 0 and not params.snapshot_dir:
        raise ValueError("snapshot_every requires snapshot_dir")
    st = BatchedState(hg, k, params)
    n = hg.n
    base, rem = divmod(n, k)
    snap_every = max(0, int(params.snapshot_every or 0))
    config = {"k": k, "t": params.t, "b": params.b, "s": params.s,
              "pool_cap": params.pool_cap, "refill_lo": params.refill_lo,
              "cap_pins": params.cap_pins,
              "kernel_min": params.kernel_min, "seed": params.seed,
              "snapshot_every": snap_every}
    start = 0
    warm = False
    ckpt = (resilience.load_latest(params.resume) if params.resume
            else None)
    if ckpt is not None:
        with span(st, "hype.restore"):
            resilience.check_checkpoint(ckpt, hg, k)
            if ckpt.engine == "hype_batched" and ckpt.config == config:
                pay = ckpt.payload
                st.assignment = pay["assignment"].copy()
                st.edge_dead = pay["edge_dead"].copy()
                st.edge_epoch = pay["edge_epoch"].copy()
                st.rand_ptr = int(pay["rand_ptr"])
                st.rng.bit_generator.state = pay["rng_state"]
                st.stats = pay["stats"].copy()
                start = int(pay["next_phase"])
            else:
                wa = resilience.warm_assignment(ckpt)
                got = wa >= 0
                st.assignment[got] = wa[got]
                warm = True
            st.stats.resumed_at = int(ckpt.superstep)
    last_snap = start
    for i in range(start, k - 1):
        with span(st, "hype.grow"):
            _grow_partition(st, i, base + (1 if i < rem else 0), warm=warm)
        if snap_every and i + 1 - last_snap >= snap_every:
            with span(st, "hype.snapshot"):
                st.stats.snapshots += 1
                resilience.save_snapshot(
                    params.snapshot_dir,
                    resilience.PartitionCheckpoint(
                        "hype_batched", i + 1, hg.fingerprint(),
                        dict(config),
                        {"assignment": st.assignment.copy(),
                         "edge_dead": st.edge_dead.copy(),
                         "edge_epoch": st.edge_epoch.copy(),
                         "rand_ptr": int(st.rand_ptr),
                         "rng_state": st.rng.bit_generator.state,
                         "stats": st.stats.copy(),
                         "next_phase": i + 1}),
                    keep_last=int(params.keep_last))
            last_snap = i + 1
    with span(st, "hype.finish"):
        # the last phase takes every vertex still unassigned
        st.assignment[st.assignment < 0] = k - 1
        st.in_fringe[:] = False
        assignment = maybe_refine(hg, k, params, st.assignment, st.stats)
    if return_stats:
        return assignment, st.stats
    return assignment


__all__ = ["BatchedParams", "BatchedState", "BatchedStats",
           "hype_batched_partition"]
