"""Shared engine runtime for the HYPE engine family (DESIGN.md §1).

Every fast engine (``batched``, ``superstep``, ``sharded``, ``device``)
used to hand-copy the same cross-cutting concerns; this module owns them
once:

  * ``EngineRuntime`` — the host-side state core every engine extends:
    assignment/pool bookkeeping, the deterministic random stream,
    fault-injection + bounded-retry device calls (``_guarded_kernel``,
    core/resilience.py §4f), and the compile-cache placement.
  * ``BatchedStats`` — the family-wide counter dataclass, plus ``merge``
    for combining the stats of split or restarted runs.
  * ``span`` / ``partition_root`` — named host spans: each
    adds its wall time to ``BatchedStats.spans`` and opens a profiler
    ``TraceAnnotation`` carrying the partition call's id.
  * ``SnapshotMixin`` — snapshot capture / exact restore / cross-engine
    warm start for the device-image engines (§4f cadence semantics).
  * ``run_pipeline`` / ``run_pipeline_budgeted`` — the double-buffered
    superstep pipeline driver (§4d) and its memory-rung retry loop
    (§4g), parameterized by a state factory so the superstep and
    sharded engines share one driver without importing each other.
  * ``maybe_refine`` — the post-run k-way refinement stage (§4e).

Engine modules may import this module and ``engines.pipeline``; they
never import each other's internals (enforced by
``tools/check_layering.py``).
"""
from __future__ import annotations

import collections
import contextvars
import dataclasses
import functools
import itertools
import time
from typing import Optional

import numpy as np
from jax.profiler import TraceAnnotation

from ..core.hypergraph import Hypergraph
from ..core import membudget
from ..core import resilience

# (1,) int32 replay markers for the device programs' sticky poison flag
# (scoring._poison_guard): 0 = normal superstep, 1 = host-driven replay
# of a quarantined superstep. Module constants so repeated dispatches
# hand jit the same host buffers.
_RESET0 = np.zeros(1, dtype=np.int32)
_RESET1 = np.ones(1, dtype=np.int32)


# the spans the pipeline counters are read from
_HOST_SPANS = ("hype.superstep.pack", "hype.superstep.dispatch",
               "hype.superstep.mirror")
_WAIT_SPAN = "hype.superstep.wait"

# id of the partition() call whose spans are open; 0 outside any call
_CALL = contextvars.ContextVar("hype_call", default=0)
_CALL_IDS = itertools.count(1)


@dataclasses.dataclass
class BatchedStats:
    kernel_calls: int = 0
    kernel_rows: int = 0       # candidate rows scored by the Pallas kernel
    host_rows: int = 0         # rows scored by the numpy fallback
    cache_hits: int = 0
    steps: int = 0
    # superstep-engine counters (zero for the classic batched path):
    supersteps: int = 0             # fused device calls
    device_image_bytes: int = 0     # one-time CSR + assignment + cache
    #                                 upload at partition() start
    host_to_device_bytes: int = 0   # per-call id/bias buffers — the whole
    #                                 steady-state H2D traffic
    cache_invalidations: int = 0    # cached scores decremented by admission
    # sharded-engine counters (zero for the single-device engines):
    collectives: int = 0            # all_gather ops (one per superstep)
    collective_bytes: int = 0       # bytes materialized by the gathers:
    #                                 devices x global payload per superstep
    admission_conflicts: int = 0    # proposed admissions lost to the
    #                                 lowest-phase-wins conflict rule
    # pipeline counters (superstep/sharded engines; ``host_s`` and
    # ``device_s`` are read from ``spans``, below):
    pipeline_stalls: int = 0        # rounds where the host could pack
    #                                 nothing and the device went idle
    stale_redraws: int = 0          # pool slots skipped on device because
    #                                 an interleaved superstep of the
    #                                 pipeline had already assigned them
    # device-loop counters (hype_device, DESIGN.md §4i):
    loop_chunks: int = 0            # host-visible while_loop segments
    loop_rounds: int = 0            # pack+dispatch rounds run on device
    loop_pack_only: int = 0         # rounds that had nothing to score
    loop_store_peak: int = 0        # peak live rows across phase stores
    loop_state_bytes: int = 0       # device-resident carry (loop state)
    refill_signals: int = 0         # kernel refill-trigger flags raised
    #                                 (phases whose candidate slots ran
    #                                 out during selection)
    # resilience counters (core/resilience.py, DESIGN.md §4f):
    faults_injected: int = 0        # FaultPlan specs that fired this run
    retries: int = 0                # transient-fault retries + poisoned-
    #                                 superstep replays (never counted as
    #                                 extra kernel_calls / supersteps)
    fallbacks: int = 0              # hand-offs to another engine: ladder
    #                                 rungs exhausted before this engine
    #                                 ran (partition_resilient) plus
    #                                 in-engine fallbacks to a lower one
    snapshots: int = 0              # checkpoints published
    resumed_at: int = -1            # superstep/phase the run resumed
    #                                 from; -1 = fresh start
    # memory-budget counters (core/membudget.py, DESIGN.md §4g):
    mem_retries: int = 0            # DeviceOOM-driven same-engine retries
    #                                 (real allocator failures + injected
    #                                 non-fatal oom faults)
    plan_rung: int = -1             # memory-plan rung the run executed at;
    #                                 -1 = engine never planned (host path)
    peak_bytes_planned: int = 0     # the plan's modeled peak device bytes
    peak_bytes_observed: int = 0    # backend peak_bytes_in_use when the
    #                                 allocator tracks it; the planned
    #                                 model value otherwise
    page_uploads: int = 0           # paged-adjacency chunk uploads
    page_hits: int = 0              # chunk requests served LRU-resident
    page_evictions: int = 0         # chunks evicted to stay under budget
    page_bytes: int = 0             # total bytes uploaded by the pager
    # refinement post-pass (None unless refine_passes > 0 ran):
    refine: Optional[object] = None     # core.refine.RefineStats
    # named host spans (``span``): name -> [inclusive wall seconds, count]
    spans: dict = dataclasses.field(default_factory=dict)

    # counters that are high-water marks / identities rather than sums —
    # ``merge`` keeps the max (or the non-default value) instead of adding
    _MERGE_MAX = ("loop_store_peak", "loop_state_bytes",
                  "peak_bytes_planned", "peak_bytes_observed",
                  "device_image_bytes", "plan_rung", "resumed_at")

    def merge(self, other: "BatchedStats") -> "BatchedStats":
        """Combine two runs' counters into a new ``BatchedStats``.

        Additive counters sum; peak/identity fields keep the max; the
        ``refine`` record of the later run wins (the earlier one refined
        an assignment that no longer exists); span totals and counts sum
        by name. Used when a partition is assembled from multiple engine
        runs (restarts, split ladders).
        """
        out = BatchedStats()
        for f in dataclasses.fields(BatchedStats):
            a, b = getattr(self, f.name), getattr(other, f.name)
            if f.name == "refine":
                out.refine = b if b is not None else a
            elif f.name == "spans":
                out.spans = {n: list(r) for n, r in a.items()}
                for name, (sec, count) in b.items():
                    out.add_span(name, sec, count)
            elif f.name in self._MERGE_MAX:
                setattr(out, f.name, max(a, b))
            else:
                setattr(out, f.name, a + b)
        return out

    def copy(self) -> "BatchedStats":
        """An independent copy (snapshots hold one; spans are mutable)."""
        return dataclasses.replace(
            self, spans={n: list(r) for n, r in self.spans.items()})

    def add_span(self, name: str, seconds: float, count: int = 1) -> None:
        rec = self.spans.get(name)
        if rec is None:
            self.spans[name] = [seconds, count]
        else:
            rec[0] += seconds
            rec[1] += count

    def span_s(self, *names: str) -> float:
        """Summed inclusive seconds of the named spans."""
        return sum(self.spans[n][0] for n in names if n in self.spans)

    @property
    def host_s(self) -> float:
        """Host packing, dispatch and harvest mirroring (overlappable)."""
        return self.span_s(*_HOST_SPANS)

    @property
    def device_s(self) -> float:
        """Blocked at harvest waiting on the device's results."""
        return self.span_s(_WAIT_SPAN)

    @property
    def snapshot_s(self) -> float:
        """Publishing checkpoints."""
        return self.span_s("hype.snapshot")

    @property
    def restore_s(self) -> float:
        """Restoring the resume checkpoint."""
        return self.span_s("hype.restore")


class span:
    """A named host span: ``with span(state, "hype.superstep.pack"):``.

    Adds the block's inclusive wall time and a count to
    ``state.stats.spans[name]``, with ``state.stats`` read as the block
    closes, so a restore that swaps the stats object inside the block
    lands its time on the restored one. While a profiler records, the
    block also runs inside a ``jax.profiler.TraceAnnotation`` carrying
    the id of the enclosing ``partition_root`` call, so the trace holds
    the span with its start, end and parent; otherwise no annotation is
    made. Only totals are kept; the profiler keeps the events.
    """
    __slots__ = ("owner", "name", "ann", "t0")

    def __init__(self, owner, name: str):
        self.owner = owner
        self.name = name
        self.ann = None

    def __enter__(self):
        if TraceAnnotation.is_enabled():
            self.ann = TraceAnnotation(self.name, call=_CALL.get())
            self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self.t0
        if self.ann is not None:
            self.ann.__exit__(*exc)
        self.owner.stats.add_span(self.name, dt)


def partition_root(runner):
    """Run an engine runner inside the call's root span, ``hype.partition``.

    Draws the call id that every span of the call carries and adds the
    call's wall time to the stats the runner returns. A runner called
    inside another's root (superstep handing off to batched) runs as
    part of that call, under its root. The signature stays the runners'
    ``(hg, k, params=None, return_stats=False)``.
    """
    @functools.wraps(runner)
    def run(hg, k, params=None, return_stats=False):
        if _CALL.get():
            return runner(hg, k, params, return_stats)
        token = _CALL.set(next(_CALL_IDS))
        try:
            t0 = time.perf_counter()
            with TraceAnnotation("hype.partition", call=_CALL.get()):
                assignment, stats = runner(hg, k, params,
                                           return_stats=True)
            stats.add_span("hype.partition", time.perf_counter() - t0)
        finally:
            _CALL.reset(token)
        return (assignment, stats) if return_stats else assignment
    return run


class EngineRuntime:
    """Mutable host-side state core shared by every fast engine.

    Owns the bookkeeping every engine needs regardless of where its
    scores live: the assignment mirror, pool membership, the seeded
    random stream, per-run stats, the memoized vertex adjacency, and
    the resolved fault plan. Device-call protection
    (``_guarded_kernel``) lives here so retry/escalation semantics
    cannot drift between engines.
    """

    def __init__(self, hg: Hypergraph, k: int, p):
        # place the persistent compile cache before any engine traces
        from repro.kernels._compat import configure_compile_cache
        configure_compile_cache()
        self.hg = hg
        self.k = k
        self.p = p
        n = hg.n
        self.assignment = np.full(n, -1, dtype=np.int32)
        self.in_pool = np.zeros(n, dtype=bool)     # fringe ∪ held candidates
        self.edge_sizes = np.asarray(hg.edge_sizes, dtype=np.int64)
        self.edge_dead = self.edge_sizes == 0              # no live pins left
        self.rng = np.random.default_rng(p.seed)
        self.rand_order = self.rng.permutation(n)
        self.rand_ptr = 0
        self.stats = BatchedStats()
        # One-time unique-neighbor CSR (memoized on hg): turns every tile
        # build into a pure gather. None for pathological hub expansions —
        # scoring then falls back to per-batch dedup with cap_pins.
        with span(self, "hype.setup.adjacency"):
            self.adj = hg.vertex_adjacency()
        # deterministic fault schedule: the param (shared instance across
        # a degradation ladder) or a FRESH parse of REPRO_FAULT_PLAN per
        # engine run, so every run of a chaos suite sees the full plan
        self.fault_plan = resilience.resolve_fault_plan(p.fault_plan)

    # ------------------------------------------------------------------ #
    def _guarded_kernel(self, fn, ordinal: int, kinds=("dispatch",),
                        donated=()):
        """Run a device call under fault injection + bounded retry.

        Injected faults fire *before* the call (the dispatch site), so a
        transient retry re-issues the identical pure computation — which
        is what keeps recovery bit-identical to a fault-free run. A
        fatal spec, an exhausted retry budget, or a real failure after
        any ``donated`` buffer was consumed (the call cannot be
        re-issued) raises ``UnrecoverableFault`` for the ladder.

        Memory faults are different: a real allocator failure
        (``membudget.is_oom_error``) or a non-fatal injected ``oom``
        raises ``DeviceOOM`` immediately — retrying the identical call
        cannot help an allocation that does not fit, and the memory-rung
        retry loop (``run_pipeline_budgeted``, DESIGN.md §4g) rebuilds
        the whole engine state at a smaller plan anyway, donated or not.
        """
        plan = self.fault_plan
        attempts = 0
        while True:
            try:
                if plan is not None:
                    sp = plan.fire(kinds, ordinal)
                    if sp is not None:
                        self.stats.faults_injected += 1
                        raise resilience.FaultInjected(
                            sp.kind, ordinal, sp.fatal)
                return fn()
            except resilience.UnrecoverableFault:
                raise
            except membudget.DeviceOOM:
                raise
            except resilience.FaultInjected as exc:
                if exc.fatal:
                    raise resilience.UnrecoverableFault(str(exc)) from exc
                if exc.kind == "oom":
                    raise membudget.DeviceOOM(
                        str(exc),
                        rung=getattr(self, "mem_rung", None)) from exc
                err = exc
            except Exception as exc:
                if membudget.is_oom_error(exc):
                    raise membudget.DeviceOOM(
                        f"device allocation failed: {exc!r}",
                        rung=getattr(self, "mem_rung", None)) from exc
                if any(a.is_deleted() for a in donated):
                    raise resilience.UnrecoverableFault(
                        f"device call failed after buffer donation: "
                        f"{exc!r}") from exc
                err = exc
            attempts += 1
            if attempts > int(self.p.max_retries):
                raise resilience.UnrecoverableFault(
                    f"retry budget ({self.p.max_retries}) exhausted: "
                    f"{err!r}") from err
            self.stats.retries += 1
            time.sleep(float(self.p.retry_backoff_s) * attempts)

    # ------------------------------------------------------------------ #
    def random_unassigned(self, count: int = 1,
                          in_pool: Optional[np.ndarray] = None
                          ) -> np.ndarray:
        """Next ``count`` unassigned non-pool vertices of the random stream.

        Vectorized skip-pointer scan over the shuffled order; the pointer
        only advances past consumed positions so no vertex is skipped.
        ``in_pool`` selects which pool-membership mask to respect (the
        sharded engine keeps one per device group); default is the
        engine-wide mask.
        """
        if in_pool is None:
            in_pool = self.in_pool
        n = self.hg.n
        out: list = []
        got = 0
        while self.rand_ptr < n and got < count:
            chunk = self.rand_order[self.rand_ptr:
                                    self.rand_ptr + max(1024, count)]
            ok = np.flatnonzero((self.assignment[chunk] < 0)
                                & ~in_pool[chunk])
            if ok.size >= count - got:
                ok = ok[:count - got]
                self.rand_ptr += int(ok[-1]) + 1
            else:
                self.rand_ptr += chunk.size
            take = chunk[ok].astype(np.int64)
            got += take.size
            if take.size:
                out.append(take)
        if got < count:     # stream exhausted; the stragglers sit earlier
            rem = np.flatnonzero((self.assignment < 0) & ~in_pool)
            if out:
                rem = np.setdiff1d(rem, np.concatenate(out),
                                   assume_unique=True)
            if rem.size:
                out.append(rem[:count - got].astype(np.int64))
        return (np.concatenate(out) if out
                else np.empty(0, dtype=np.int64))


class SnapshotMixin:
    """Snapshot/resume for the device-image pipeline states (§4f).

    Mixed into ``engines.pipeline.PipelineState``: captures the complete
    engine state at a drained superstep boundary, restores it
    bit-identically for a same-engine/same-config resume, and
    warm-starts growth from a cross-engine snapshot's assignment.
    """

    def capture_payload(self, acc: np.ndarray, cur_depth: int) -> dict:
        """Complete engine state at a drained superstep boundary.

        Called with the pipeline empty (the driver drains in-flight
        supersteps first), so the only live state is host bookkeeping
        plus the settled device image. Everything the continuation
        reads is captured; static derivatives (adjacency, tile width,
        random order) are rebuilt from the config at restore.
        """
        self._store_flush()
        return {
            "assignment": self.assignment.copy(),
            "acc": acc.copy(),
            "cur_depth": int(cur_depth),
            "in_pool": self.in_pool.copy(),
            "cache_scored": self.cache_scored.copy(),
            "pools": [ids.copy() for ids in self.pools],
            "bq_key": self.bq_key.copy(),
            "bq_edge": self.bq_edge.copy(),
            "seq_back": int(self._seq_back),
            "seq_front": int(self._seq_front),
            "edge_queued": self.edge_queued.copy(),
            "edge_dead": self.edge_dead.copy(),
            "delta_ids": [a.copy() for a in self.delta_ids],
            "delta_vals": [a.copy() for a in self.delta_vals],
            "pending_dirty": [a.copy() for a in self.pending_dirty],
            "rand_ptr": int(self.rand_ptr),
            "rng_state": self.rng.bit_generator.state,
            "dirty_ratchet": int(self._dirty_ratchet),
            "stats": self.stats.copy(),
            "dev_assign": np.asarray(self.dev_assign),
            # on the spill rung the authoritative cache IS the host
            # mirror; either way the payload carries plain numpy
            "dev_cache": (self.host_cache.copy()
                          if self.host_cache is not None
                          else np.asarray(self.dev_cache)),
            "dev_acc": np.asarray(self.dev_acc),
        }

    def restore_exact(self, pay: dict):
        """Resume bit-identically from a same-engine/config payload.

        Returns ``(acc, cur_depth)`` for the driver. The device image
        is re-uploaded from the snapshot's downloaded copies; the
        poison flag restarts clean (snapshots are only taken at drained,
        replayed-if-needed boundaries).
        """
        self.assignment = pay["assignment"].copy()
        self.in_pool = pay["in_pool"].copy()
        self.cache_scored = pay["cache_scored"].copy()
        self.pools = [ids.copy() for ids in pay["pools"]]
        self.bq_key = pay["bq_key"].copy()
        self.bq_edge = pay["bq_edge"].copy()
        self._bq_pending = []
        self._seq_back = np.int64(pay["seq_back"])
        self._seq_front = np.int64(pay["seq_front"])
        self.edge_queued = pay["edge_queued"].copy()
        self.edge_dead = pay["edge_dead"].copy()
        self.delta_ids = [a.copy() for a in pay["delta_ids"]]
        self.delta_vals = [a.copy() for a in pay["delta_vals"]]
        self.pending_dirty = [a.copy() for a in pay["pending_dirty"]]
        self.rand_ptr = int(pay["rand_ptr"])
        self.rng.bit_generator.state = pay["rng_state"]
        self._dirty_ratchet = int(pay["dirty_ratchet"])
        self.stats = pay["stats"].copy()
        self.dev_assign = self._to_device(pay["dev_assign"])
        if self.host_cache is not None:
            self.host_cache = pay["dev_cache"].astype(np.float32,
                                                      copy=True)
        else:
            self.dev_cache = self._to_device(pay["dev_cache"])
        self.dev_acc = self._to_device(pay["dev_acc"])
        self.dev_poison = self._to_device(np.zeros(1, dtype=np.int32))
        return pay["acc"].copy(), int(pay["cur_depth"])

    def restore_warm(self, warm: np.ndarray) -> np.ndarray:
        """Cross-engine warm start: adopt a (partial) assignment.

        Mirrors the assignment into the device image and activates the
        incident edges of every adopted member, so growth continues
        from the snapshot instead of from scratch. Exactness is not
        claimed (the donor engine's transient state is gone) — this is
        the degradation ladder's path. Returns the per-phase totals.
        """
        done = np.flatnonzero(warm >= 0)
        acc = np.zeros(self.k, dtype=np.int64)
        if done.size:
            ph = warm[done].astype(np.int64)
            self.assignment[done] = warm[done]
            acc[:int(ph.max()) + 1] = np.bincount(ph)
            self.dev_assign = self._to_device(
                self.assignment.astype(np.int32, copy=True))
            self.dev_acc = self._to_device(
                acc.astype(np.int32, copy=True))
            self.activate_many(done.astype(np.int64), ph)
        return acc


def maybe_refine(hg: Hypergraph, k: int, params,
                 assignment: np.ndarray, stats: BatchedStats
                 ) -> np.ndarray:
    """Run the k-way refinement post-pass when ``refine_passes`` > 0.

    Shared by every engine of the family (DESIGN.md §4e): boundary
    vertices are screened on device by the ``kway_gains`` kernel and
    moved under exact-gain, balance-capped admission, so the engine's
    ``max - min <= 1`` contract survives. ``refine_passes = 0`` returns
    the assignment object untouched — the engines stay bit-identical to
    their pre-refinement outputs (golden-hash-enforced).
    """
    passes = getattr(params, "refine_passes", 0)
    if passes <= 0 or k <= 1:
        return assignment
    from ..core.refine import refine_kway

    refined, rstats = refine_kway(hg, assignment, k, passes)
    stats.refine = rstats
    return refined


def _harvest_next(st, inflight: collections.deque,
                  acc: np.ndarray, targets: np.ndarray) -> int:
    """Harvest the oldest in-flight superstep, replaying a poisoned one.

    When the popped superstep was quarantined (non-finite scores — an
    injected NaN tile, normally), ``harvest`` replays it, and every
    in-flight superstep dispatched after it self-aborted on the sticky
    poison flag: replay the whole window in FIFO order from the handles'
    clean args so device-effect order — and therefore bit-identical
    recovery — is preserved. Mirroring touches only host state, so the
    window may replay after it. The window's replay is one more
    ``hype.superstep.wait`` span.
    """
    h = inflight.popleft()
    progress, replayed = st.harvest(h, acc, targets,
                                    [e.fresh_ids for e in inflight])
    if replayed and inflight:
        with span(st, "hype.superstep.wait"):
            redo = list(inflight)
            inflight.clear()
            for old in redo:
                inflight.append(st.replay(old))
    return progress


def _teardown_pipeline(st, inflight: collections.deque) -> None:
    """Settle the donated-buffer chains of an aborted run (§4f).

    Blocks on every in-flight superstep's outputs so each donated
    execution completes (deleting a donated buffer synchronizes with
    the execution consuming it), then drops the handles and the queued
    host transients. Nothing device-side survives except the state's
    own current image arrays — no zombie refs, and the process is free
    to start a fresh engine run.
    """
    for h in list(inflight):
        try:
            np.asarray(h.winners)
            np.asarray(h.poison)
        except Exception:       # the abort may have broken the call
            pass
    inflight.clear()
    st.delta_ids, st.delta_vals = [], []
    st.pending_dirty = []


def run_pipeline(hg: Hypergraph, k: int, p, make_state, engine: str,
                 devices: int = 0, mem_rung: int = 0,
                 mem_warm: Optional[np.ndarray] = None,
                 mem_retries: int = 0):
    """Grow all ``k`` partitions concurrently; returns (assignment, state).

    The shared double-buffered superstep driver of the device engines
    (DESIGN.md §4d). Each *superstep* is one fused device call that
    scores the stacked fresh-candidate tiles of every growing phase and
    admits each phase's top-``t`` on device (paper §VI k-way growth).
    Up to ``p.pipeline_depth`` supersteps stay in flight: while the
    device computes superstep N, the host mirrors superstep N-1's
    admissions and speculatively draws/packs superstep N+1; proposals
    that went stale in between are skipped on device by the
    deterministic redraw rule, so results are seeded-deterministic at
    any depth and ``pipeline_depth=1`` reproduces the lock-step engine
    bit for bit.

    ``make_state(p, mem_rung)`` builds the engine's pipeline state (a
    ``engines.pipeline.PipelineState`` subclass); its ``st.k`` may pad
    ``k`` up (the sharded engine's device-aligned phase groups) and its
    ``release_pools`` hook clears the engine's pool masks at the end.
    ``engine``/``devices`` identify the schedule in snapshot configs.

    Resilience (DESIGN.md §4f): every ``p.snapshot_every`` supersteps
    the driver drains the pipeline and publishes a checkpoint; with
    ``p.resume`` pointing at a same-engine/same-config snapshot the run
    restores it and continues bit-identically to an uninterrupted run
    with the same cadence (a cross-engine snapshot warm-starts from its
    assignment instead). Any exception tears the pipeline down safely.
    """
    st = make_state(p, mem_rung)
    if st.dev is None:
        return None, None                       # caller falls back
    kG = st.k
    st.stats.mem_retries = int(mem_retries)
    n = hg.n
    base, rem = divmod(n, k)
    targets = np.zeros(kG, dtype=np.int64)
    targets[:k] = base + (np.arange(k) < rem)
    targets_i32 = targets.astype(np.int32)
    acc = np.zeros(kG, dtype=np.int64)
    R, P, t = p.rows, p.pool_cap, p.t
    delta_cap = max(2 * kG * t, kG)
    # the memory plan may clamp the pipeline to lock-step (rung >= the
    # depth reduction): the clamp is part of the schedule, and at an
    # unconstrained budget the plan echoes the param unchanged
    depth = max(1, min(int(p.pipeline_depth),
                       int(st.mem_plan.pipeline_depth)))
    fringe = np.full((kG, 1), -1, dtype=np.int32)   # fringe-free scoring
    snap_every = max(0, int(p.snapshot_every or 0))
    # everything that decides the superstep schedule: an exact restore
    # requires all of it to match (snapshot cadence included — draining
    # the pipeline at snapshots IS part of the schedule at depth > 1).
    # Of the memory plan (§4g) only the EFFECTIVE tile width and the
    # depth clamp enter: the chunk/spill/paged rungs are bit-exact per
    # superstep, so a snapshot restores exactly across them, while a
    # tile_l or depth change is a schedule change and must warm-start
    config = {"k": k, "devices": devices, "t": t, "rows": R,
              "pool_cap": P, "s": p.s, "seed": p.seed,
              "pipeline_depth": depth, "snapshot_every": snap_every,
              "tile_l": int(st.tile_l)}

    cur_depth = depth
    seeded = False
    ckpt = resilience.load_latest(p.resume) if p.resume else None
    if ckpt is not None:
        with span(st, "hype.restore"):
            resilience.check_checkpoint(ckpt, hg, k)
            if ckpt.engine == engine and ckpt.config == config:
                acc, cur_depth = st.restore_exact(ckpt.payload)
                seeded = True   # the snapshot already carries the seeds
            else:
                acc = st.restore_warm(resilience.warm_assignment(ckpt))
            st.stats.resumed_at = int(ckpt.superstep)
    elif mem_warm is not None:
        # memory-rung retry (DESIGN.md §4g): adopt the failed attempt's
        # host assignment mirror so already-grown members survive the
        # re-tiling — the seeding below only fills still-empty phases
        acc = st.restore_warm(np.asarray(mem_warm, dtype=np.int32))

    if not seeded:
        # seed every empty phase with one random vertex (paper §III-B1
        # step 1); a warm start only seeds phases the snapshot left empty
        seeds = st.random_unassigned(
            int(((acc == 0) & (targets > 0)).sum()))
        gi = 0
        for g in range(kG):
            if targets[g] == 0 or acc[g] > 0 or gi >= seeds.size:
                continue
            v = seeds[gi:gi + 1]
            gi += 1
            st.assign_now(v, g)
            st.activate_phase(v, g)
            acc[g] += 1

    last_snap = int(st.stats.supersteps)
    inflight: collections.deque = collections.deque()
    try:
        with span(st, "hype.grow"):
            while True:
                progress = 0
                if (snap_every
                        and st.stats.supersteps - last_snap >= snap_every):
                    while inflight:     # drain: snapshots see settled state
                        progress += _harvest_next(st, inflight, acc, targets)
                    with span(st, "hype.snapshot"):
                        st.stats.snapshots += 1
                        resilience.save_snapshot(
                            p.snapshot_dir,
                            resilience.PartitionCheckpoint(
                                engine, int(st.stats.supersteps),
                                hg.fingerprint(), dict(config),
                                st.capture_payload(acc, cur_depth)),
                            keep_last=int(p.keep_last))
                    last_snap = int(st.stats.supersteps)
                active = np.flatnonzero(acc < targets)
                if active.size == 0:
                    break
                while len(inflight) >= cur_depth:   # tail heuristic shrank
                    progress += _harvest_next(st, inflight, acc, targets)
                with span(st, "hype.superstep.pack"):
                    packed, injected = st.pack_superstep(active, R, P, t,
                                                         targets, acc)
                progress += injected
                if packed is not None:
                    fresh, bias, pool_arr, fresh_ids = packed
                    with span(st, "hype.superstep.dispatch"):
                        inflight.append(st.dispatch(
                            fresh, bias, pool_arr, fringe, fresh_ids,
                            targets_i32, delta_cap, t))
                elif inflight:
                    st.stats.pipeline_stalls += 1   # device idles this round
                if inflight and (len(inflight) >= cur_depth
                                 or packed is None):
                    harvested = _harvest_next(st, inflight, acc, targets)
                    progress += harvested
                    # adaptive depth: while a superstep admits less than
                    # half its capacity the draw view — not the device — is
                    # the bottleneck, and speculative packs only waste
                    # fixed-cost device calls; drop to lock-step until
                    # admissions recover. Deterministic: based solely on
                    # mirrored results.
                    cur_depth = 1 if 2 * harvested < active.size * t else depth
                if progress == 0 and not inflight:
                    break   # starved: remaining vertices sit in other pools
            while inflight:     # drain the pipeline before the safety net
                _harvest_next(st, inflight, acc, targets)
    except membudget.DeviceOOM as exc:
        # memory fault mid-run: settle the pipeline, then enrich the
        # exception with everything the re-tiling retry loop needs —
        # the rung this attempt ran at and the host assignment mirror
        # (the admissions harvested so far) for the warm start
        _teardown_pipeline(st, inflight)
        if exc.rung is None:
            exc.rung = int(st.mem_plan.rung)
        exc.partial = st.assignment.copy()
        raise
    except BaseException:
        # abort path (injected unrecoverable fault, KeyboardInterrupt,
        # real device failure): settle every donated chain before
        # propagating so no zombie buffer outlives the run
        _teardown_pipeline(st, inflight)
        raise

    with span(st, "hype.finish"):
        # safety net: balance-fill any stragglers into underfull phases
        rem_v = np.flatnonzero(st.assignment < 0)
        if rem_v.size:
            deficit = np.maximum(targets - acc, 0)
            fill = np.repeat(np.arange(kG), deficit)[:rem_v.size]
            st.assignment[rem_v[:fill.size]] = fill.astype(np.int32)
        st.release_pools()
        # the device image syncs at superstep boundaries only; the final
        # injections' delta dies with the state (the host assignment is
        # authoritative). Tests needing device/host parity flush
        # explicitly through dispatch/harvest.
        st.delta_ids, st.delta_vals = [], []
        obs = membudget.observed_peak_bytes()
        st.stats.peak_bytes_observed = (int(obs) if obs else
                                        int(st.stats.peak_bytes_planned))
    return st.assignment, st


def run_pipeline_budgeted(hg: Hypergraph, k: int, p, make_state,
                          engine: str, devices: int = 0):
    """``run_pipeline`` under the memory-rung retry loop (§4g).

    A ``DeviceOOM`` — a real allocator failure at the upload, dispatch
    or harvest site, or an injected non-fatal ``oom`` fault — retries
    the SAME engine at the next-smaller memory plan, warm-started from
    the failed attempt's host assignment mirror, before the
    engine-degradation ladder (``partition_resilient``) is ever
    consulted. Only an exhausted rung ladder escalates, as
    ``UnrecoverableFault``. The fault plan is resolved once up front so
    a one-shot injected ``oom`` spec stays consumed across retries
    (re-parsing ``REPRO_FAULT_PLAN`` per attempt would re-fire it
    forever).
    """
    fplan = resilience.resolve_fault_plan(p.fault_plan)
    if fplan is not None:
        p = dataclasses.replace(p, fault_plan=fplan)
    rung, warm, retries = 0, None, 0
    while True:
        try:
            return run_pipeline(hg, k, p, make_state, engine, devices,
                                mem_rung=rung, mem_warm=warm,
                                mem_retries=retries)
        except membudget.DeviceOOM as exc:
            retries += 1
            rung = (rung if exc.rung is None else int(exc.rung)) + 1
            if exc.partial is not None and (exc.partial >= 0).any():
                warm = exc.partial
        except membudget.MemoryLadderExhausted as exc:
            raise resilience.UnrecoverableFault(
                f"device memory rungs exhausted: {exc}") from exc
