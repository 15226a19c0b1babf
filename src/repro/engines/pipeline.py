"""Shared superstep pipeline state for the device-image engines.

``PipelineState`` is the host half of the double-buffered superstep
pipeline (DESIGN.md §4d) shared by the ``superstep``, ``sharded`` and
``device`` engines: the device-resident graph image and its memory plan
(§4g), the flat (phase, class, edge) bucket store, per-phase candidate
pools, superstep packing, async dispatch/harvest with poisoned-superstep
replay (§4f), and exact score-cache decrement bookkeeping.

The one thing it does NOT own is the device call itself:
``_call_program`` is abstract, and each engine module co-locates its
program with a subclass (``engines.superstep.SuperstepState``,
``engines.sharded.ShardedState``). ``engines.device`` builds the carry
for its while_loop megakernel from a plain ``PipelineState`` — it never
dispatches through it.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

from ..core.hypergraph import Hypergraph
from ..core import membudget
from ..core import resilience
from ..core import scoring
from .runtime import EngineRuntime, SnapshotMixin, _RESET0, _RESET1, span

# Flat bucket-store key layout: one sorted int64 per queued (phase,
# class, edge) activation — phase in the top bits, the power-of-two
# size-class exponent below it, and a sequence number in the low bits.
# Keeping the store sorted by this key makes "draw smallest classes
# first, FIFO within a class, requeues at the front" a pure prefix scan
# per phase: back-appends allocate increasing sequence numbers, front
# requeues allocate decreasing ones.
_PH_SHIFT = 50
_CLS_SHIFT = 44
_SEQ_START = np.int64(1) << 43


@dataclasses.dataclass
class _CallArgs:
    """The host-built buffers of one superstep's device call.

    Kept on the in-flight handle so a quarantined superstep can be
    replayed *exactly* (same pure program, same inputs, current image
    state). ``bias`` is always the CLEAN bias — an injected NaN tile
    poisons a copy at dispatch time only.
    """
    delta: np.ndarray
    vals: np.ndarray
    dirty: np.ndarray
    dcnt: np.ndarray
    fresh: np.ndarray
    bias: np.ndarray
    pool_arr: np.ndarray
    fringe: np.ndarray
    targets: np.ndarray
    select_k: int
    # spill rung only: the held pool's scores from the host cache
    # mirror, captured at dispatch AFTER the dirty decrements were
    # applied host-side — a replay reuses them verbatim, so the
    # decrements are never double-applied (DESIGN.md §4g)
    prev: Optional[np.ndarray] = None


@dataclasses.dataclass
class _Superstep:
    """One in-flight superstep: result futures + replay material.

    ``winners``/``n_stale``/``poison`` (and ``ncf`` for the sharded
    engine) are device futures the driver blocks on at harvest;
    ``donated`` pins the consumed image arrays until that block (a
    donated buffer's last reference must not drop while the execution
    consuming it is still in flight); ``args`` is the clean input set
    for poisoned-superstep replays.
    """
    winners: object
    n_stale: object
    poison: object
    fresh_ids: np.ndarray
    donated: tuple
    args: _CallArgs
    ncf: object = None
    # spill rung only: the fresh scores the host cache mirror adopts at
    # harvest (after the poison check — a quarantined superstep's
    # scores are garbage and are replaced by the replay's)
    scores: object = None


class PipelineState(SnapshotMixin, EngineRuntime):
    """The device-resident graph image and per-phase growth state.

    The host keeps only ids and flags (assignment mirror, pool id lists,
    the flat active-edge bucket store, a has-been-scored bitmask); every
    *score* lives in the device cache and is maintained exactly by the
    decrement rule in the engine's superstep program — no per-phase
    wipe. Admissions are selected, capped and applied *on device*
    (``dispatch``); the host mirrors them at ``harvest`` time, possibly
    several supersteps later, which is what lets the pipeline driver
    overlap host orchestration with device compute.
    """

    def __init__(self, hg: Hypergraph, k: int, p,
                 mesh=None, mem_rung: int = 0):
        super().__init__(hg, k, p)
        self.dev_cache = None       # device score cache (None when spilled)
        self.host_cache = None      # host float32 mirror (spill rung only)
        self.paged_adj = None       # membudget.PagedAdjacency (paged rung)
        self.mem_plan = None
        self.g_chunk = 1
        self.mem_rung = int(mem_rung)
        if k >= 1 << (63 - _PH_SHIFT):      # bucket-store key width
            self.dev = None
            return
        if self.adj is None:        # hub-expansion guard tripped on host
            self.dev = None
            return
        deg = np.diff(self.adj[0])
        self.deg = deg
        # One gather-width per run: every distinct shape retraces the
        # whole jitted superstep program (~0.5-1s in interpret mode), and
        # padding a gather is far cheaper than a retrace. The tile width
        # is the bucket of the 99.5th-percentile degree — the handful of
        # rows wider than that are truncated and carry the hub penalty
        # (they'd compare as "huge neighborhood" anyway).
        self.tile_l = scoring._bucket_width(int(min(
            np.percentile(deg, 99.5) if deg.size else 1,
            scoring.L_BUCKETS[-1])))
        # memory plan (core/membudget.py, DESIGN.md §4g): size every
        # device-resident tensor BEFORE upload against the resolved
        # budget; ``mem_rung`` > 0 means an earlier attempt OOMed and
        # the retry loop wants the next-smaller configuration. An
        # unconstrained budget at rung 0 reproduces today's tile
        # choices bit for bit. MemoryLadderExhausted propagates to the
        # retry loop, which hands the engine-degradation ladder over.
        rows = p.rows if p.rows else max(8, p.t)
        self.mem_budget = membudget.resolve_budget(
            getattr(p, "mem_budget", None))
        spec = membudget.MemSpec(
            n=hg.n, adj_pins=int(self.adj[1].size), k=k, rows=int(rows),
            pool_cap=int(p.pool_cap), t=int(p.t),
            tile_l=int(self.tile_l),
            pipeline_depth=max(1, int(p.pipeline_depth)))
        plan = membudget.plan_memory(spec, self.mem_budget,
                                     self._mem_features,
                                     rung_start=self.mem_rung)
        self.mem_plan = plan
        self.mem_rung = plan.rung
        self.tile_l = plan.tile_l
        self.g_chunk = plan.g_chunk
        self.stats.plan_rung = plan.rung
        self.stats.peak_bytes_planned = int(plan.planned_bytes)
        fplan = self.fault_plan
        if fplan is not None:
            sp = fplan.fire(("oom",), 0)
            if sp is not None:
                # simulated allocation failure at the image-upload site
                self.stats.faults_injected += 1
                if sp.fatal:
                    raise resilience.UnrecoverableFault(
                        "injected fatal OOM during device image upload")
                raise membudget.DeviceOOM(
                    "injected OOM during device image upload",
                    rung=self.mem_rung)
        import jax
        import jax.numpy as jnp

        n, m = hg.n, hg.m
        # times the upload's issue only: the transfer runs on and lands
        # in the first superstep's ``hype.superstep.wait``
        with span(self, "hype.setup.image"):
            try:
                if plan.paged:
                    # no resident CSR: the pager uploads id-range chunks
                    # on demand under its own LRU byte budget. ``dev``
                    # keeps a non-None sentinel so ``run_pipeline`` takes the
                    # device path.
                    self.paged_adj = membudget.PagedAdjacency(
                        self.adj, plan.page_bytes, self.stats)
                    self.dev = (None, None)
                else:
                    self.dev = hg.device_adjacency(mesh=mesh)
                    if self.dev is None:
                        return
                self.dev_assign = jnp.full((n,), -1, jnp.int32)
                if plan.spill_cache:
                    self.host_cache = np.full(n, -1.0, dtype=np.float32)
                else:
                    self.dev_cache = jnp.full((n,), -1.0, jnp.float32)
                self.dev_acc = jnp.zeros((k,), jnp.int32)
                # sticky NaN-quarantine flag (scoring._poison_guard),
                # donated through every superstep like the rest of the
                # mutable image
                self.dev_poison = jnp.zeros((1,), jnp.int32)
            except Exception as exc:
                if membudget.is_oom_error(exc):
                    raise membudget.DeviceOOM(
                        f"device image upload failed: {exc!r}",
                        rung=self.mem_rung) from exc
                raise
            if mesh is not None:       # replicate the mutable image too
                from jax.sharding import NamedSharding, PartitionSpec
                rep = NamedSharding(mesh, PartitionSpec())
                self.dev_assign = jax.device_put(self.dev_assign, rep)
                self.dev_cache = jax.device_put(self.dev_cache, rep)
                self.dev_acc = jax.device_put(self.dev_acc, rep)
                self.dev_poison = jax.device_put(self.dev_poison, rep)
        self.cache_scored = np.zeros(n, dtype=bool)
        self.pools = [np.empty(0, dtype=np.int64) for _ in range(k)]
        # flat (phase, class, edge) bucket store — two parallel arrays
        # sorted by the composite key above, replacing the per-phase
        # dict-of-deques
        self.bq_key = np.empty(0, dtype=np.int64)
        self.bq_edge = np.empty(0, dtype=np.int64)
        self._bq_pending: list = []     # rows awaiting the lazy merge
        self._seq_back = np.int64(_SEQ_START)
        self._seq_front = np.int64(_SEQ_START) - 1
        self.edge_queued = np.zeros((k, m), dtype=bool)
        self.delta_ids: list = []
        self.delta_vals: list = []
        self.pending_dirty: list = []   # queued winner decrements
        self._excl_scratch = np.zeros(n, dtype=bool)
        # The dirty-pair pad is pre-sized from the expected per-superstep
        # dirty rate and only ratchets up (monotone -> at most a couple
        # of traces).
        mean_deg = self.adj[1].size / max(hg.n, 1)
        expect = min(hg.n, max(256, int(2 * k * p.t * mean_deg)))
        self._dirty_ratchet = 1 << int(np.ceil(np.log2(expect + 1)))
        csr_bytes = (0 if self.paged_adj is not None
                     else self.dev[0].nbytes + self.dev[1].nbytes)
        cache_bytes = (0 if self.dev_cache is None
                       else self.dev_cache.nbytes)
        self.stats.device_image_bytes = int(
            csr_bytes + cache_bytes + self.dev_assign.nbytes
            + self.dev_acc.nbytes)

    # ------------------------------------------------------------------ #
    # injected faults this engine's dispatch site can see (the sharded
    # engine adds "collective" — its dispatch owns the all_gather);
    # "oom@N" lets chaos suites simulate mid-run allocation failures
    _fault_kinds = ("dispatch", "oom")
    # memory-rung reductions this engine has program variants for
    # (membudget.rung_ladder); the sharded engine only supports the
    # width/depth knobs — its CSR is replicated per device
    _mem_features = membudget.SUPERSTEP_FEATURES

    @property
    def interpret(self) -> bool:
        """Pallas interpret mode, re-resolved per call.

        A property, not an ``__init__`` attribute, so flipping
        ``REPRO_PALLAS_INTERPRET`` steers even a live engine — the
        NaN-quarantine tests flip it without rebuilding state, and
        ``kernels/_compat.pallas_interpret`` already reads the env per
        call; this was the one residual cache of its value.
        """
        from repro.kernels._compat import pallas_interpret
        return pallas_interpret()

    def _to_device(self, arr: np.ndarray):
        """Upload a host array as this engine's replicated image layout."""
        import jax.numpy as jnp
        return jnp.asarray(arr)

    def release_pools(self) -> None:
        """End-of-run hook: clear every pool-membership mask."""
        self.in_pool[:] = False

    # ------------------------------------------------------------------ #
    def _pmask(self, g: int) -> np.ndarray:
        """Pool-membership mask governing phase ``g``'s draws.

        Engine-wide for the single-device engine; the sharded engine
        overrides this with the per-device-group mask.
        """
        return self.in_pool

    def _restart_mask(self) -> np.ndarray:
        """Mask a restart injection must avoid: every engine pool.

        Injections are applied to the device image with an unconditional
        scatter, so they must never name a vertex an in-flight superstep
        could still admit — i.e. anything in ANY pool. For the
        single-device engine that is exactly ``in_pool``; the sharded
        engine unions its per-group masks.
        """
        return self.in_pool

    def assign_now(self, vs: np.ndarray, phase: int) -> None:
        """Assign ``vs`` to ``phase``; queue the device delta + dirtying."""
        vs = np.asarray(vs, dtype=np.int64)
        self.assignment[vs] = phase
        self.in_pool[vs] = False
        self.delta_ids.append(vs)
        self.delta_vals.append(np.full(vs.size, phase, dtype=np.int32))

    def activate_phase(self, vs: np.ndarray, phase: int) -> None:
        """Queue the edges incident to newly admitted vertices of a phase."""
        self.activate_many(np.asarray(vs, dtype=np.int64),
                           np.full(len(vs), phase, dtype=np.int64))

    def activate_many(self, vs: np.ndarray, phases: np.ndarray) -> None:
        """Queue incident edges for a whole superstep's admissions at once.

        ``vs``/``phases`` are parallel arrays; one CSR gather + one
        lexsort appends every fresh (phase, edge) activation to the back
        of the flat sorted bucket store — no per-phase python pass.
        """
        edges, owner = scoring.gather_csr_rows(
            self.hg.v2e_indptr, self.hg.v2e_indices, vs)
        if edges.size == 0:
            return
        edges = edges.astype(np.int64)
        ph = phases[owner]
        key = np.unique(ph * np.int64(self.hg.m) + edges)
        ph, edges = key // self.hg.m, key % self.hg.m
        live = ~self.edge_queued[ph, edges] & ~self.edge_dead[edges]
        ph, edges = ph[live], edges[live]
        if edges.size == 0:
            return
        self.edge_queued[ph, edges] = True
        # power-of-two size classes instead of exact sizes: smallest-first
        # drawing is a heuristic, and ~12 classes keep the number of
        # (phase, class) segments small.
        sizes = self.edge_sizes[edges]
        cls = np.where(
            sizes <= 1, np.int64(0),
            np.ceil(np.log2(np.maximum(sizes, 2))).astype(np.int64))
        order = np.lexsort((cls, ph))
        ph, edges, cls = ph[order], edges[order], cls[order]
        seq = np.arange(self._seq_back, self._seq_back + edges.size,
                        dtype=np.int64)
        self._seq_back += edges.size
        self._store_insert(
            (ph << _PH_SHIFT) | (cls << _CLS_SHIFT) | seq, edges)

    # ------------------------------------------------------ bucket store
    def _store_insert(self, key: np.ndarray, edges: np.ndarray) -> None:
        """Queue rows for the store; merged lazily at the next draw.

        Batching the merges (one sorted-merge per pack instead of one
        per activation) keeps store maintenance O(store) *per superstep*
        rather than per call — visibility is identical because draws
        only happen at pack time, after ``_store_flush``.
        """
        if key.size:
            self._bq_pending.append((key, edges))

    def _store_flush(self) -> None:
        if not self._bq_pending:
            return
        key = np.concatenate([kk for kk, _ in self._bq_pending])
        edges = np.concatenate([ee for _, ee in self._bq_pending])
        self._bq_pending = []
        order = np.argsort(key, kind="stable")
        key, edges = key[order], edges[order]
        if self.bq_key.size == 0:
            self.bq_key, self.bq_edge = key, edges
            return
        pos = np.searchsorted(self.bq_key, key)
        self.bq_key = np.insert(self.bq_key, pos, key)
        self.bq_edge = np.insert(self.bq_edge, pos, edges)

    def _store_take(self, budget: np.ndarray):
        """Greedy smallest-class-first prefix take for every phase.

        ``budget`` is the per-phase pin budget; each queued edge
        contributes its power-of-two class value (the same accounting
        the dict-of-deques draw used). Only each phase's front slice
        (at most ``budget`` rows — every edge costs >= 1 unit) is ever
        decoded, so the take is O(sum budgets + k log store), not
        O(store). Returns the taken rows' ``(edges, ph, cls_log)``
        columns, phase-major (the store is key-sorted), and drops them
        from the store.
        """
        self._store_flush()
        key = self.bq_key
        if key.size == 0 or not budget.any():
            empty = np.empty(0, dtype=np.int64)
            return empty, empty, empty
        k = self.k
        bounds = np.searchsorted(
            key, np.arange(k + 1, dtype=np.int64) << _PH_SHIFT)
        start = bounds[:k]
        cap = np.minimum(bounds[1:] - start, budget)
        tot = int(cap.sum())
        if tot == 0:
            empty = np.empty(0, dtype=np.int64)
            return empty, empty, empty
        head = np.cumsum(cap) - cap
        local = np.arange(tot, dtype=np.int64) - np.repeat(head, cap)
        rows = np.repeat(start, cap) + local
        ph_r = np.repeat(np.arange(k, dtype=np.int64), cap)
        ckey = key[rows]
        cls_log = (ckey >> _CLS_SHIFT) & np.int64(63)
        csize = np.int64(1) << cls_log
        cum = np.cumsum(csize)
        excl = cum - csize
        base = np.zeros(k, dtype=np.int64)
        has = cap > 0
        base[has] = excl[head[has]]
        take = (excl - base[ph_r]) < budget[ph_r]
        tk = rows[take]
        edges_t, ph_t, cls_t = self.bq_edge[tk], ph_r[take], cls_log[take]
        if tk.size:     # drop taken rows NOW — restarts may insert
            keep = np.ones(key.size, dtype=bool)
            keep[tk] = False
            self.bq_key = key[keep]
            self.bq_edge = self.bq_edge[keep]
        return edges_t, ph_t, cls_t

    def _store_requeue(self, rq_ph: list, rq_cls: list,
                       rq_edge: list) -> None:
        """Requeue still-live taken rows at their queue fronts."""
        if not rq_ph:
            return
        ph = np.concatenate(rq_ph)
        cls = np.concatenate(rq_cls)
        edges = np.concatenate(rq_edge)
        seq = np.arange(self._seq_front - edges.size + 1,
                        self._seq_front + 1, dtype=np.int64)
        self._seq_front -= edges.size
        key = (ph << _PH_SHIFT) | (cls << _CLS_SHIFT) | seq
        order = np.argsort(key, kind="stable")
        self._store_insert(key[order], edges[order])

    def take_delta(self, cap: int):
        """Drain up to ``cap`` queued (id, phase) assignment pairs.

        FIFO across calls: an overflowing drain leaves the tail queued
        (int64 ids / int32 phases preserved) for the next superstep.
        """
        if not self.delta_ids:
            return (np.empty(0, dtype=np.int64),
                    np.empty(0, dtype=np.int32))
        ids = np.concatenate(self.delta_ids).astype(np.int64, copy=False)
        vals = np.concatenate(self.delta_vals).astype(np.int32,
                                                      copy=False)
        if ids.size <= cap:
            self.delta_ids, self.delta_vals = [], []
            return ids, vals
        self.delta_ids = [ids[cap:]]
        self.delta_vals = [vals[cap:]]
        return ids[:cap], vals[:cap]

    def _pack_delta_dirty(self, delta_cap, extra_dirty=()):
        """Drain queued assignments into the padded device buffers.

        Pre-aggregates the dirtied-neighbor multiset of the drained
        delta — one CSR gather + bincount, shipped as (unique id, count)
        pairs padded to a power-of-two bucket (bounded retraces,
        O(unique) device scatter). ``extra_dirty`` merges additional raw
        neighbor-id arrays into the multiset (the sharded engine's
        queued decrement tails). Returns ``(delta, vals, dirty, dcnt)``;
        shared by both device engines so their cache-exactness
        bookkeeping cannot drift apart.
        """
        d_ids, d_vals = self.take_delta(delta_cap)
        delta = np.full(delta_cap, -1, dtype=np.int32)
        vals = np.zeros(delta_cap, dtype=np.int32)
        delta[:d_ids.size] = d_ids
        vals[:d_ids.size] = d_vals
        nbrs, _ = scoring.gather_csr_rows(self.adj[0], self.adj[1], d_ids)
        parts = list(extra_dirty)
        if nbrs.size:
            parts.append(nbrs.astype(np.int64))
        if parts:
            counts = np.bincount(np.concatenate(parts))
            uniq = np.flatnonzero(counts)
            self.stats.cache_invalidations += int(uniq.size)
        else:
            uniq = np.empty(0, dtype=np.int64)
            counts = np.empty(0, dtype=np.int64)
        cap = max(self._dirty_ratchet,
                  1 << int(np.ceil(np.log2(max(uniq.size, 1)))))
        self._dirty_ratchet = cap
        dirty = np.full(cap, -1, dtype=np.int32)
        dcnt = np.zeros(cap, dtype=np.float32)
        dirty[:uniq.size] = uniq
        dcnt[:uniq.size] = counts[uniq]
        return delta, vals, dirty, dcnt

    # ---------------------------------------------------- pipeline hooks
    def pack_superstep(self, active, R: int, P: int, t: int,
                       targets: np.ndarray, acc: np.ndarray):
        """Host half of one superstep: draw, dedup, tile-pack, restart.

        One flat store scan + ONE pins gather covers every active
        phase's candidate draw (stage A, assignment-independent); a thin
        rotation-ordered pass then applies the order-sensitive pieces —
        edge liveness, candidate acceptance against the live pool masks,
        and random restarts (stage B). Mutates pools/masks/acc for the
        injections and returns ``(packed, injected)`` where ``packed``
        is ``(fresh, bias, pool_arr, fresh_ids)`` or None when no phase
        had anything to score.
        """
        kG = self.k
        rot = self.stats.supersteps % active.size
        order = np.concatenate([active[rot:], active[:rot]])
        # stage 0: drop ids that went stale (admitted meanwhile) from
        # the held pools, then size each phase's draw
        need = np.zeros(kG, dtype=np.int64)
        budget = np.zeros(kG, dtype=np.int64)
        for g in order:
            gi = int(g)
            ids = self.pools[gi]
            if ids.size:
                keep = self.assignment[ids] < 0
                if not keep.all():
                    self._pmask(gi)[ids[~keep]] = False
                    ids = ids[keep]
                    self.pools[gi] = ids
            need[gi] = min(R, P - ids.size)
            if need[gi] > 0:
                budget[gi] = max(4 * need[gi], 512)
        # stage A: one prefix take over the sorted store + one CSR
        # gather for every taken edge of every phase
        edges_t, ph_t, cls_t = self._store_take(budget)
        pins, prow = scoring.gather_csr_rows(
            self.hg.e2v_indptr, self.hg.e2v_indices, edges_t)
        pins = pins.astype(np.int64)
        edge_lo = np.searchsorted(ph_t, np.arange(kG + 1, dtype=np.int64))
        pin_lo = np.searchsorted(prow, edge_lo)
        # per-phase first-occurrence dedup of the pin streams. The
        # acceptance filters below are per-pin properties, so deduping
        # before filtering equals the old filter-then-dedup, row for row.
        if pins.size:
            pph = ph_t[prow]
            _, first = np.unique(pph * np.int64(self.hg.n) + pins,
                                 return_index=True)
            first = np.sort(first)
            cand_all = pins[first]
            cand_lo = np.searchsorted(pph[first],
                                      np.arange(kG + 1, dtype=np.int64))
        else:
            cand_all = pins
            cand_lo = np.zeros(kG + 1, dtype=np.int64)
        # stage B: rotation-ordered liveness / acceptance / restarts
        fresh = np.full((kG, R), -1, dtype=np.int32)
        bias = np.full((kG, R), np.inf, dtype=np.float32)
        pool_arr = np.full((kG, P), -1, dtype=np.int32)
        fresh_parts: list = []
        rq_ph: list = []
        rq_cls: list = []
        rq_edge: list = []
        injected = 0
        packed_any = False
        rmask = None    # injection-safety mask, computed at most once
        #                 per pack (the sharded union is O(devices * n))
        for g in order:
            gi = int(g)
            e0, e1 = int(edge_lo[gi]), int(edge_lo[gi + 1])
            if e1 > e0:     # edge liveness at this phase's turn
                p0, p1 = int(pin_lo[gi]), int(pin_lo[gi + 1])
                unas = self.assignment[pins[p0:p1]] < 0
                live = np.bincount(prow[p0:p1][unas] - e0,
                                   minlength=e1 - e0) > 0
                eg = edges_t[e0:e1]
                if not live.all():
                    self.edge_dead[eg[~live]] = True    # dead forever
                if live.any():
                    rq_ph.append(ph_t[e0:e1][live])
                    rq_cls.append(cls_t[e0:e1][live])
                    rq_edge.append(eg[live])
            pmask = self._pmask(gi)
            cg = cand_all[int(cand_lo[gi]):int(cand_lo[gi + 1])]
            drawn = cg
            if cg.size:
                okc = (self.assignment[cg] < 0) & ~pmask[cg]
                drawn = cg[okc][:need[gi]]
            ids = self.pools[gi]
            miss = np.empty(0, dtype=np.int64)
            if drawn.size:
                pmask[drawn] = True
                if rmask is not None and rmask is not pmask:
                    rmask[drawn] = True     # keep the union mask live
                scored = self.cache_scored[drawn]
                hits, miss = drawn[scored], drawn[~scored]
                if hits.size:       # cross-phase reuse: already cached
                    ids = np.concatenate([ids, hits])
            if ids.size == 0 and miss.size == 0:
                # shattered remainder: seed fresh growth points directly
                if rmask is None:
                    rmask = self._restart_mask()
                vs = self.random_unassigned(
                    min(t, int(targets[gi] - acc[gi])), in_pool=rmask)
                if vs.size:
                    self.assign_now(vs, gi)
                    self.activate_phase(vs, gi)
                    acc[gi] += vs.size
                    injected += int(vs.size)
                continue
            fresh[gi, :miss.size] = miss
            bias[gi, :miss.size] = np.where(
                self.deg[miss] > self.tile_l, scoring.TRUNC_PENALTY, 0.0)
            pool_arr[gi, :ids.size] = ids
            # every pool_arr slot is a score served straight from the
            # device cache (held-over or cross-phase hit) instead of a
            # kernel rescore — the reuse the exact-decrement design buys
            self.stats.cache_hits += int(ids.size)
            self.pools[gi] = np.concatenate([ids, miss])
            fresh_parts.append(miss)
            self.stats.kernel_rows += int(miss.size)
            packed_any = True
        self._store_requeue(rq_ph, rq_cls, rq_edge)
        if not packed_any:
            return None, injected
        fresh_ids = (np.concatenate(fresh_parts) if fresh_parts
                     else np.empty(0, dtype=np.int64))
        return (fresh, bias, pool_arr, fresh_ids), injected

    def _image_buffers(self) -> tuple:
        """The live donated image arrays of this engine's current mode.

        The spill rung keeps no device cache and the paged rung no
        resident CSR, so the donated set is mode-dependent — every
        dispatch/replay handle pins exactly these.
        """
        bufs = [self.dev_assign, self.dev_acc, self.dev_poison]
        if self.dev_cache is not None:
            bufs.insert(1, self.dev_cache)
        return tuple(bufs)

    def _call_program(self, args: _CallArgs, reset: np.ndarray):
        """Issue the engine's fused superstep program; rotate the image.

        Returns ``(winners, n_stale, ncf, scores)`` futures (``ncf`` is
        None for the single-device engine; ``scores`` is None except on
        the spill rung, where the host owns the score cache and the
        fresh scores ride back with the winners). Abstract here: each
        engine module co-locates its device program with its state
        subclass — the ONLY device-call difference between the
        superstep and sharded engines.
        """
        raise NotImplementedError(
            "PipelineState subclasses co-locate their device program")

    def _call_guarded(self, args: _CallArgs, reset: np.ndarray):
        """``_call_program`` under fault injection + bounded retry."""
        return self._guarded_kernel(
            lambda: self._call_program(args, reset),
            int(self.stats.supersteps), self._fault_kinds,
            donated=self._image_buffers())

    def _count_dispatch(self, fresh: np.ndarray, select_k: int) -> None:
        """Per-dispatch counter hook (the sharded engine adds
        collective accounting). Replays never come through here — the
        kernel_calls == supersteps invariant survives recovery."""

    def _count_harvest(self, handle: _Superstep) -> None:
        """Per-harvest counter hook (sharded: admission conflicts)."""

    def dispatch(self, fresh, bias, pool_arr, fringe, fresh_ids,
                 targets_i32, delta_cap: int, select_k: int):
        """Launch one superstep on the device (async); returns a handle.

        JAX's async dispatch returns immediately — the returned handle's
        arrays are futures the driver blocks on only at ``harvest``, so
        the host keeps packing while the device computes. The previous
        (donated) image arrays ride the handle: deleting a donated
        buffer synchronizes with the execution consuming it, so their
        last reference must not drop before the harvest-time block.

        Fault-injection sites (DESIGN.md §4f): a ``dispatch`` (or, for
        the sharded engine, ``collective``) spec raises here and is
        retried/escalated by ``_call_guarded``; a ``nan`` spec poisons a
        COPY of the bias buffer so the device program's quarantine
        guard trips — the handle keeps the clean args for the replay.
        """
        tails = self.pending_dirty
        self.pending_dirty = []
        delta, vals, dirty, dcnt = self._pack_delta_dirty(
            delta_cap, extra_dirty=tails)
        prev = None
        if self.host_cache is not None:
            # spill rung: the host owns the score cache. Apply the dirty
            # decrements to the float32 mirror NOW (the same IEEE adds
            # the device program would have scattered) and ship the held
            # pool's scores in; the device still masks stale slots
            # itself against the post-injection assignment.
            u = dirty >= 0
            ids = dirty[u].astype(np.int64)
            self.host_cache[ids] -= dcnt[u]
            prev = self.host_cache[np.where(pool_arr >= 0, pool_arr,
                                            0)].astype(np.float32)
        self.stats.host_to_device_bytes += (
            fresh.nbytes + bias.nbytes + pool_arr.nbytes + fringe.nbytes
            + delta.nbytes + vals.nbytes + dirty.nbytes + dcnt.nbytes
            + targets_i32.nbytes)
        self.stats.supersteps += 1
        self.stats.kernel_calls += 1
        self._count_dispatch(fresh, select_k)
        args = _CallArgs(delta, vals, dirty, dcnt, fresh, bias,
                         pool_arr, fringe, targets_i32, select_k,
                         prev=prev)
        send = args
        plan = self.fault_plan
        if plan is not None:
            sp = plan.fire(("nan",), int(self.stats.supersteps))
            if sp is not None:
                self.stats.faults_injected += 1
                if sp.fatal:
                    raise resilience.UnrecoverableFault(
                        f"injected fatal nan tile at superstep "
                        f"{self.stats.supersteps}")
                bias_bad = bias.copy()
                bias_bad[fresh >= 0] = np.nan
                send = dataclasses.replace(args, bias=bias_bad)
        donated = self._image_buffers()
        winners, n_stale, ncf, scores = self._call_guarded(send, _RESET0)
        return _Superstep(winners, n_stale, self.dev_poison, fresh_ids,
                          donated, args, ncf, scores)

    def replay(self, h: _Superstep) -> _Superstep:
        """Re-issue a quarantined superstep from its clean args.

        The poisoned superstep (and every later in-flight one — the
        poison flag is sticky) reverted all of its device mutations, so
        the current image equals the state just before it ran: calling
        the same pure program with the handle's clean args and
        ``reset=1`` recovers exactly what a fault-free run computed.
        Counts as a retry only — never as a new superstep/kernel call.
        A superstep still poisoned after a clean replay means the
        non-finite scores are real (not injected): unrecoverable here,
        the ladder's host engines score around poisoned rows instead.
        """
        self.stats.retries += 1
        donated = self._image_buffers()
        winners, n_stale, ncf, scores = self._call_program(h.args,
                                                           _RESET1)
        nh = _Superstep(winners, n_stale, self.dev_poison, h.fresh_ids,
                        donated, h.args, ncf, scores)
        if int(np.asarray(nh.poison)[0]) > 0:
            raise resilience.UnrecoverableFault(
                "superstep still poisoned after a clean replay: the "
                "non-finite scores did not come from an injected fault")
        return nh

    def harvest(self, handle, acc: np.ndarray, targets: np.ndarray,
                exclude=()) -> tuple[int, bool]:
        """Block on one in-flight superstep and mirror its admissions.

        Returns the admissions mirrored and whether the handle was
        quarantined and replayed.

        The only blocking transfer of the steady state: everything else
        the driver does (packing superstep N+1) happens while the device
        still computes superstep N. Admission mirroring is fully
        vectorized — no per-slot python loop. ``exclude`` carries the
        fresh-id arrays of the supersteps still in flight: their scores
        were computed *after* this superstep's winners were applied, so
        the queued winner decrements must skip them (double-decrement
        otherwise).

        A quarantined handle (non-finite scores poisoned the superstep,
        which reverted itself on device) is replayed from its clean
        args before mirroring — direct dispatch/harvest callers survive
        an injected NaN tile without the pipeline driver's help; the
        driver then replays the rest of its in-flight window to keep
        device-effect order (see ``runtime._harvest_next``).

        Two spans: ``hype.superstep.wait`` from the first blocking read
        (the poison flag) until the results are on the host, then
        ``hype.superstep.mirror``.
        """
        with span(self, "hype.superstep.wait"):
            replayed = int(np.asarray(handle.poison)[0]) > 0
            if replayed:
                handle = self.replay(handle)
            try:
                winners = np.asarray(handle.winners)
                n_stale = int(handle.n_stale)
                if (self.host_cache is not None
                        and handle.scores is not None):
                    # spill rung: adopt the fresh scores into the host
                    # mirror — the same pad-dropping scatter the device
                    # cache write performs, after the poison check above
                    flat = handle.args.fresh.reshape(-1)
                    sc = np.asarray(handle.scores).reshape(-1)
                    real = flat >= 0
                    self.host_cache[flat[real].astype(np.int64)] = sc[real]
            except membudget.DeviceOOM:
                raise
            except Exception as exc:
                # a real allocator failure can surface at the blocking
                # transfer, not just at dispatch — same recovery path
                if membudget.is_oom_error(exc):
                    raise membudget.DeviceOOM(
                        f"superstep harvest failed: {exc!r}",
                        rung=self.mem_rung) from exc
                raise
        with span(self, "hype.superstep.mirror"):
            return self._mirror(handle, winners, n_stale, acc, targets,
                                exclude), replayed

    def _mirror(self, handle, winners: np.ndarray, n_stale: int,
                acc: np.ndarray, targets: np.ndarray, exclude) -> int:
        """Mirror a harvested superstep's admissions on the host."""
        fresh_ids = handle.fresh_ids
        self.stats.stale_redraws += n_stale
        if fresh_ids.size:
            self.cache_scored[fresh_ids] = True
        kG, t = winners.shape
        flat = winners.reshape(-1).astype(np.int64)
        mask = flat >= 0
        vs = flat[mask]
        progress = int(vs.size)
        if vs.size:
            ph = np.repeat(np.arange(kG, dtype=np.int64), t)[mask]
            self.assignment[vs] = ph.astype(np.int32)
            self._release_members(vs, ph)
            acc += np.bincount(ph, minlength=kG)
            self.activate_many(vs, ph)
            self._queue_decrements(vs, exclude)
            for g in np.unique(ph):
                if acc[g] >= targets[g]:    # phase done: release pool
                    gi = int(g)
                    self._pmask(gi)[self.pools[gi]] = False
                    self.pools[gi] = np.empty(0, dtype=np.int64)
        self._count_harvest(handle)
        return progress

    def _release_members(self, vs: np.ndarray, ph: np.ndarray) -> None:
        """Clear pool membership for freshly mirrored winners."""
        self.in_pool[vs] = False

    def _filter_rescored(self, nbrs: np.ndarray, exclude) -> np.ndarray:
        """Drop ids fresh-rescored by a still-in-flight superstep.

        Their cache entries are written *after* the winners applied, so
        they already reflect the admissions — decrementing them again
        would double-count. O(|nbrs| + |exclude|) via a reusable
        boolean scratch.
        """
        parts = [e for e in exclude if e.size]
        if not parts or nbrs.size == 0:
            return nbrs
        ex = np.concatenate(parts)
        scratch = self._excl_scratch
        scratch[ex] = True
        out = nbrs[~scratch[nbrs]]
        scratch[ex] = False
        return out

    def _queue_decrements(self, vs: np.ndarray, exclude=()) -> None:
        """Queue the winners' neighbor decrements for the next dispatch.

        The full multiset — one CSR gather, pre-aggregated into
        (unique id, count) pairs by ``_pack_delta_dirty`` — exactly the
        lock-step engine's decrement schedule at depth 1; ids rescored
        by an in-flight superstep are excluded (see
        ``_filter_rescored``).
        """
        nbrs, _ = scoring.gather_csr_rows(self.adj[0], self.adj[1], vs)
        if nbrs.size == 0:
            return
        nbrs = self._filter_rescored(nbrs.astype(np.int64), exclude)
        if nbrs.size:
            self.pending_dirty.append(nbrs)
