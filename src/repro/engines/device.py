"""Fully device-resident loop engine (DESIGN.md §4i).

The entire k-way growth loop — pool maintenance, store draws, scoring,
admission, exact cache decrements, restarts — runs as one
``lax.while_loop`` program on device (``core/device_loop.py``); the
host uploads the graph image once and downloads a few scalars per chunk
of supersteps. The schedule is the lock-step pd1 cadence by
construction, which is what makes the engine golden-hash bit-identical
to ``hype_superstep`` at ``pipeline_depth=1``.

The driver builds its initial carry from a plain
``engines.pipeline.PipelineState`` (the seeded host bookkeeping + the
uploaded image) — it never dispatches through the pipeline, so the
abstract ``_call_program`` is never reached. Fallbacks: the superstep
host pipeline down the §4g rung ladder on device OOM, the engine ladder
(``superstep`` → ``batched``) when the int32 encoding gates or the
memory plan reject the graph.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional

import numpy as np

from ..core.hypergraph import Hypergraph
from ..core import device_loop
from ..core import membudget
from ..core import resilience
from ..core.scoring import gather_csr_rows
from .pipeline import PipelineState
from .runtime import BatchedStats, maybe_refine, span
from .superstep import SuperstepParams


@dataclasses.dataclass
class DeviceParams(SuperstepParams):
    """Knobs for the fully device-resident loop engine (DESIGN.md §4i).

    ``pipeline_depth`` is ignored: the device loop runs the lock-step
    pd1 cadence by construction — that is exactly what makes it
    golden-hash bit-identical to ``hype_superstep`` at depth 1.
    """
    # supersteps per host-visible while_loop segment; the host syncs a
    # handful of scalars (flags / progress / acc) once per chunk and the
    # snapshot cadence shortens chunks to land on its boundaries
    chunk_supersteps: int = 64
    # device score-cache storage: "float32" is bit-identical to the host
    # engines; "float16" halves the cache bytes — scores are small exact
    # integers plus the 1e12 hub penalty, so fp16 rounding only perturbs
    # ties above 2048 external neighbors (bounded-error tested)
    cache_dtype: str = "float32"
    # capacity overrides for the fixed device rings (None = planned from
    # graph statistics; the driver doubles a flagged cap and re-runs —
    # schedules are capacity-independent, so the rerun is bit-identical)
    store_cap: Optional[int] = None
    act_cap: Optional[int] = None


def _device_probe_faults(st: PipelineState, lo: int, hi: int):
    """Fire injected dispatch/oom specs for superstep ordinals [lo, hi].

    The host engines fire these one superstep at a time inside
    ``_guarded_kernel``; the device loop runs a whole chunk per host
    call, so the driver probes the chunk's ordinal range up front —
    same plan, same ordinals, same escalation rules.
    """
    plan = st.fault_plan
    if plan is None:
        return
    for o in range(lo, hi + 1):
        sp = plan.fire(("dispatch", "oom"), o)
        if sp is None:
            continue
        st.stats.faults_injected += 1
        if sp.fatal:
            raise resilience.UnrecoverableFault(
                f"injected fatal {sp.kind} fault at superstep {o}")
        if sp.kind == "oom":
            raise membudget.DeviceOOM(
                f"injected OOM at superstep {o}", rung=st.mem_rung)
        # transient dispatch fault: the injection fires *before* the
        # call, so the retry re-issues the identical pure chunk —
        # mirror _guarded_kernel's accounting and continue
        st.stats.retries += 1
        time.sleep(float(st.p.retry_backoff_s))


def _device_probe_nan(st: PipelineState, lo: int, hi: int):
    """Find the first injected nan spec in [lo, hi]; returns ordinal|-1.

    The device program poisons the flagged superstep's bias tile on
    device (``poison_at``) and replays it in place with the clean bias
    — the same quarantine/replay recovery as the host pipeline.
    """
    plan = st.fault_plan
    if plan is None:
        return -1
    for o in range(lo, hi + 1):
        sp = plan.fire(("nan",), o)
        if sp is None:
            continue
        st.stats.faults_injected += 1
        if sp.fatal:
            raise resilience.UnrecoverableFault(
                f"injected fatal nan tile at superstep {o}")
        return o
    return -1


def _device_export(st: PipelineState, k: int, acc: np.ndarray,
                   caps: dict, cache_f16: bool):
    """Build the initial device carry from the seeded host state.

    Returns ``(carry_np, caps)`` — plain numpy; the attempt loop
    uploads. ``caps["sp"]`` may grow if the host store does not fit.
    """
    hg, n, m = st.hg, st.hg.n, st.hg.m
    P = int(st.p.pool_cap)
    st._store_flush()
    enc = device_loop.host_store_to_device(
        st.bq_key, st.bq_edge, k, caps["sp"])
    while enc is None:
        caps = dict(caps, sp=caps["sp"] * 2)
        enc = device_loop.host_store_to_device(
            st.bq_key, st.bq_edge, k, caps["sp"])
    skey, sedge, sback, sfront = enc
    pool = np.full((k, P), -1, dtype=np.int32)
    pool_n = np.zeros(k, dtype=np.int32)
    for g, ids in enumerate(st.pools):
        pool[g, :ids.size] = ids
        pool_n[g] = ids.size
    # queued decrements: the undrained delta's neighbor multiset (the
    # host drains it at the next dispatch) plus any queued winner tails
    pend = np.zeros(n, dtype=np.int32)
    d_ids, _ = st.take_delta(1 << 60)
    if d_ids.size:
        nbrs, _ = gather_csr_rows(st.adj[0], st.adj[1], d_ids)
        np.add.at(pend, nbrs, 1)
    for a in st.pending_dirty:
        np.add.at(pend, np.asarray(a, dtype=np.int64), 1)
    st.pending_dirty = []
    cache = np.asarray(st.dev_cache, dtype=np.float32).copy()
    if cache_f16:
        cache = np.clip(cache, -65504.0, 65504.0).astype(np.float16)
    carry = dict(
        assign=st.assignment.astype(np.int32, copy=True),
        cache=cache,
        acc=acc.astype(np.int32, copy=True),
        in_pool=st.in_pool.copy(),
        cache_scored=st.cache_scored.copy(),
        edge_queued=st.edge_queued.copy(),
        edge_dead=st.edge_dead.copy(),
        skey=skey, sedge=sedge, sback=sback, sfront=sfront,
        pool=pool, pool_n=pool_n, pend=pend,
        rand_ptr=np.int32(st.rand_ptr),
        supersteps=np.int32(st.stats.supersteps),
        progress=np.int32(1),
        flags=np.int32(0),
        ss_in_chunk=np.int32(0),
        stats=np.zeros(device_loop.NSTATS, dtype=np.int32),
    )
    return carry, caps


def _device_attempt(hg: Hypergraph, k: int, p: DeviceParams,
                    caps_over: dict):
    """One capacity attempt of the device loop.

    Returns ``("ok", assignment, st)``, ``("fallback", reason, None)``
    or ``("overflow", flags, caps)``. DeviceOOM propagates (enriched
    with rung + partial) for the caller's ladder.
    """
    chunk_max = max(1, int(getattr(p, "chunk_supersteps", 64)))
    cache_dtype = str(getattr(p, "cache_dtype", "float32"))
    cache_f16 = cache_dtype == "float16"
    st = PipelineState(hg, k, dataclasses.replace(p, pipeline_depth=1),
                       mem_rung=0)
    if st.dev is None:
        return ("fallback", "no device adjacency", None)
    if st.mem_plan.rung != 0:
        # the budget wants a reduced configuration; the §4g rungs are
        # host-pipeline programs — hand the whole run to that engine
        return ("fallback", "memory plan below rung 0", None)
    n, m = hg.n, hg.m
    base, rem = divmod(n, k)
    targets = np.zeros(k, dtype=np.int64)
    targets[:] = base + (np.arange(k) < rem)
    acc = np.zeros(k, dtype=np.int64)
    R, P, t = int(p.rows), int(p.pool_cap), int(p.t)
    vdeg = np.diff(hg.v2e_indptr).astype(np.int64)
    mean_vdeg = float(vdeg.mean()) if n else 1.0
    mean_adeg = float(st.deg.mean()) if n else 1.0
    sizes = st.edge_sizes
    max_edge = int(sizes.max()) if m else 1
    caps = device_loop.plan_caps(
        n=n, m=m, kG=k, rows=R, t=t, mean_vdeg=mean_vdeg,
        mean_adeg=mean_adeg, max_edge=max_edge,
        store_cap=getattr(p, "store_cap", None),
        act_cap=getattr(p, "act_cap", None))
    caps.update(caps_over)
    if not device_loop.supported(n=n, m=m, kG=k, bud=caps["bud"]):
        return ("fallback", "int32 encoding gates", None)

    snap_every = max(0, int(p.snapshot_every or 0))
    config = {"k": k, "devices": 0, "t": t, "rows": R, "pool_cap": P,
              "s": p.s, "seed": p.seed, "pipeline_depth": 1,
              "snapshot_every": snap_every, "tile_l": int(st.tile_l),
              "chunk_supersteps": chunk_max, "cache_dtype": cache_dtype}
    engine = "hype_device"
    resumed_carry = None
    ckpt = resilience.load_latest(p.resume) if p.resume else None
    if ckpt is not None:
        with span(st, "hype.restore"):
            resilience.check_checkpoint(ckpt, hg, k)
            if ckpt.engine == engine and ckpt.config == config:
                pay = ckpt.payload
                resumed_carry = {kk: vv.copy()
                                 for kk, vv in pay["carry"].items()}
                caps = dict(pay["caps"])
                caps.update(caps_over)
                st.stats = pay["stats"].copy()
                acc = np.asarray(resumed_carry["acc"], dtype=np.int64)
            else:
                acc = st.restore_warm(resilience.warm_assignment(ckpt))
            st.stats.resumed_at = int(ckpt.superstep)

    if resumed_carry is None:
        # seed every empty phase with one random vertex — exactly the
        # pipeline driver's loop, so the device schedule starts from
        # the same state and random stream position
        seeds = st.random_unassigned(
            int(((acc == 0) & (targets > 0)).sum()))
        gi = 0
        for g in range(k):
            if targets[g] == 0 or acc[g] > 0 or gi >= seeds.size:
                continue
            v = seeds[gi:gi + 1]
            gi += 1
            st.assign_now(v, g)
            st.activate_phase(v, g)
            acc[g] += 1
        carry_np, caps = _device_export(st, k, acc, caps, cache_f16)
    else:
        carry_np = resumed_carry
        carry_np["flags"] = np.int32(0)
        carry_np["progress"] = np.int32(1)

    cfg = device_loop.DeviceLoopConfig(
        n=n, m=m, kG=k, rows=R, pool_cap=P, t=t, tile_l=int(st.tile_l),
        bud=caps["bud"], pp=caps["pp"], sp=caps["sp"], act=caps["act"],
        rawt=caps["rawt"], rawd=caps["rawd"], cw=caps["cw"],
        cache_f16=cache_f16, interpret=bool(st.interpret))

    import jax
    import jax.numpy as jnp

    cls_edge = np.where(
        sizes <= 1, np.int64(0),
        np.ceil(np.log2(np.maximum(sizes, 2))).astype(np.int64))
    consts = dict(
        adj_indptr=jnp.asarray(st.adj[0].astype(np.int32)),
        adj_indices=jnp.asarray(st.adj[1].astype(np.int32)),
        v2e_indptr=jnp.asarray(hg.v2e_indptr.astype(np.int32)),
        v2e_indices=jnp.asarray(hg.v2e_indices.astype(np.int32)),
        e2v_indptr=jnp.asarray(hg.e2v_indptr.astype(np.int32)),
        e2v_indices=jnp.asarray(hg.e2v_indices.astype(np.int32)),
        cls_edge=jnp.asarray(cls_edge.astype(np.int32)),
        deg=jnp.asarray(st.deg.astype(np.int32)),
        vdeg=jnp.asarray(vdeg.astype(np.int32)),
        targets=jnp.asarray(targets.astype(np.int32)),
        rand_order=jnp.asarray(st.rand_order.astype(np.int32)),
        fringe=jnp.full((k, 1), -1, jnp.int32),
    )
    try:
        run = device_loop.device_loop_program(cfg)
        carry = {kk: jnp.asarray(vv) for kk, vv in carry_np.items()}
    except Exception as exc:
        if membudget.is_oom_error(exc):
            raise membudget.DeviceOOM(
                f"device loop image upload failed: {exc!r}",
                rung=st.mem_rung) from exc
        raise
    st.stats.loop_state_bytes = device_loop.carry_bytes(carry_np)
    st.stats.device_image_bytes = int(
        sum(int(v.nbytes) for v in consts.values())) + \
        st.stats.loop_state_bytes

    def _snapshot_payload(carry_dev):
        return {"carry": {kk: np.asarray(vv)
                          for kk, vv in carry_dev.items()},
                "caps": dict(caps),
                "stats": st.stats.copy()}

    last_snap = int(carry_np["supersteps"])
    last_known = st.assignment.copy()
    try:
        while True:
            # the host's turn: read the carry, snapshot, probe faults
            with span(st, "hype.superstep.dispatch"):
                ss_now = int(np.asarray(carry["supersteps"]))
                acc_h = np.asarray(carry["acc"]).astype(np.int64)
                if snap_every and ss_now - last_snap >= snap_every:
                    with span(st, "hype.snapshot"):
                        st.stats.snapshots += 1
                        resilience.save_snapshot(
                            p.snapshot_dir,
                            resilience.PartitionCheckpoint(
                                engine, ss_now, hg.fingerprint(),
                                dict(config), _snapshot_payload(carry)),
                            keep_last=int(p.keep_last))
                    last_snap = ss_now
                    last_known = np.asarray(carry["assign"]).copy()
                if (acc_h >= targets).all():
                    break
                if int(np.asarray(carry["progress"])) == 0:
                    break   # starved: stragglers sit in other pools
                cap = chunk_max
                if snap_every:
                    cap = min(cap, snap_every - (ss_now - last_snap))
                cap = max(1, cap)
                _device_probe_faults(st, ss_now + 1, ss_now + cap)
                poison_at = _device_probe_nan(st, ss_now + 1,
                                              ss_now + cap)
                if poison_at > 0:
                    cap = poison_at - ss_now    # poisoned step ends chunk
            with span(st, "hype.superstep.wait"):
                try:
                    carry = run(consts, carry, jnp.int32(cap),
                                jnp.int32(poison_at))
                    flags = int(np.asarray(carry["flags"]))   # blocks
                except Exception as exc:
                    if membudget.is_oom_error(exc):
                        raise membudget.DeviceOOM(
                            f"device loop chunk failed: {exc!r}",
                            rung=st.mem_rung) from exc
                    raise
            st.stats.loop_chunks += 1
            if flags:
                if flags & device_loop.FLAG_POISON:
                    raise resilience.UnrecoverableFault(
                        "superstep still poisoned after a clean "
                        "replay: the kernel emits non-finite scores "
                        "for finite inputs")
                return ("overflow", flags, caps)
    except membudget.DeviceOOM as exc:
        if exc.rung is None:
            exc.rung = int(st.mem_plan.rung)
        exc.partial = last_known
        raise

    # final download + host mirror
    st.assignment = np.asarray(carry["assign"]).astype(np.int32,
                                                       copy=True)
    acc = np.asarray(carry["acc"]).astype(np.int64)
    dstats = np.asarray(carry["stats"]).astype(np.int64)
    st.stats.supersteps = int(np.asarray(carry["supersteps"]))
    st.stats.kernel_calls += st.stats.supersteps
    st.stats.loop_rounds += int(dstats[device_loop.S_ROUNDS])
    st.stats.loop_pack_only += int(dstats[device_loop.S_PACK_ONLY])
    st.stats.loop_store_peak = max(
        st.stats.loop_store_peak,
        int(dstats[device_loop.S_STORE_PEAK]))
    st.stats.refill_signals += int(dstats[device_loop.S_REFILL])
    st.stats.kernel_rows += int(dstats[device_loop.S_KERNEL_ROWS])
    st.stats.cache_invalidations += int(dstats[device_loop.S_CACHE_INV])
    st.stats.cache_hits += int(dstats[device_loop.S_CACHE_HITS])
    st.stats.stale_redraws += int(dstats[device_loop.S_STALE])
    st.stats.retries += int(dstats[device_loop.S_RETRIES])
    # safety net: balance-fill any stragglers into underfull phases
    rem_v = np.flatnonzero(st.assignment < 0)
    if rem_v.size:
        deficit = np.maximum(targets - acc, 0)
        fill = np.repeat(np.arange(k), deficit)[:rem_v.size]
        st.assignment[rem_v[:fill.size]] = fill.astype(np.int32)
    st.in_pool[:] = False
    obs = membudget.observed_peak_bytes()
    st.stats.peak_bytes_observed = (int(obs) if obs else
                                    int(st.stats.peak_bytes_planned))
    return ("ok", st.assignment, st)


def _run_device_loop(hg: Hypergraph, k: int, p: DeviceParams):
    """Run the §4i device loop with the capacity-doubling rerun ladder.

    Returns ``(assignment, st)`` or ``(None, None)`` for the caller's
    engine fallback. A rerun with doubled caps replays bit-identically
    (the superstep schedule is capacity-independent); FLAG_SEQ —
    per-phase sequence-space exhaustion — has no doubling answer and
    falls back.
    """
    caps_over: dict = {}
    for _ in range(5):
        kind, a, b = _device_attempt(hg, k, p, caps_over)
        if kind == "ok":
            return a, b
        if kind == "fallback":
            return None, None
        flags, caps = a, b
        if flags & device_loop.FLAG_SEQ:
            return None, None
        if flags & device_loop.FLAG_STORE:
            caps_over["sp"] = 2 * caps["sp"]
        if flags & device_loop.FLAG_ACT:
            caps_over["act"] = 2 * caps["act"]
        if flags & device_loop.FLAG_RAWT:
            caps_over["rawt"] = 2 * caps["rawt"]
        if flags & device_loop.FLAG_RAWD:
            caps_over["rawd"] = 2 * caps["rawd"]
    return None, None


def hype_device_partition(hg: Hypergraph, k: int,
                          params: Optional[DeviceParams] = None,
                          return_stats: bool = False):
    """Partition ``hg`` with the fully device-resident loop (§4i).

    The entire k-way growth loop — pool maintenance, store draws,
    scoring, admission, exact cache decrements, restarts — runs as one
    ``lax.while_loop`` program on device; the host uploads the graph
    image once and downloads a few scalars per chunk of supersteps.
    Bit-identical to ``hype_superstep_partition`` at
    ``pipeline_depth=1`` with matching knobs. Falls back to
    ``hype_superstep_partition`` when the int32 encoding gates or the
    memory plan reject the graph, and down the §4g rung ladder (via the
    host pipeline) on device OOM; each hand-off counts in
    ``stats.fallbacks``.
    """
    if params is None:
        params = DeviceParams()
    if params.rows is None:
        params = dataclasses.replace(params, rows=max(8, params.t))
    if k < 1:
        raise ValueError("k must be >= 1")
    if params.t < 1 or params.rows < 1 or params.pool_cap < 1:
        raise ValueError("rows, pool_cap, t must all be >= 1")
    if int(getattr(params, "chunk_supersteps", 64)) < 1:
        raise ValueError("chunk_supersteps must be >= 1")
    if getattr(params, "cache_dtype", "float32") not in (
            "float32", "float16"):
        raise ValueError("cache_dtype must be float32 or float16")
    if params.snapshot_every > 0 and not params.snapshot_dir:
        raise ValueError("snapshot_every requires snapshot_dir")
    if k == 1:
        out = np.zeros(hg.n, dtype=np.int32)
        return (out, BatchedStats()) if return_stats else out
    fplan = resilience.resolve_fault_plan(params.fault_plan)
    if fplan is not None:
        params = dataclasses.replace(params, fault_plan=fplan)
    try:
        assignment, st = _run_device_loop(hg, k, params)
    except membudget.DeviceOOM as exc:
        # §4g: the device loop has no reduced-memory program variants —
        # fall down the host pipeline's rung ladder, warm-started from
        # the chunk boundary the failed attempt last synced. The ladder
        # keeps this engine's lock-step cadence (pipeline_depth=1): an
        # upload-time OOM then reruns fresh and lands on the same
        # golden schedule the device loop would have produced
        from .superstep import run_pipeline as superstep_pipeline
        params = dataclasses.replace(params, pipeline_depth=1)
        rung = 1 if exc.rung is None else int(exc.rung) + 1
        warm = (exc.partial if exc.partial is not None
                and (np.asarray(exc.partial) >= 0).any() else None)
        retries = 1
        while True:
            try:
                assignment, pst = superstep_pipeline(
                    hg, k, params, mem_rung=rung, mem_warm=warm,
                    mem_retries=retries)
                break
            except membudget.DeviceOOM as exc2:
                retries += 1
                rung = (rung if exc2.rung is None
                        else int(exc2.rung)) + 1
                if (exc2.partial is not None
                        and (exc2.partial >= 0).any()):
                    warm = exc2.partial
            except membudget.MemoryLadderExhausted as exc2:
                raise resilience.UnrecoverableFault(
                    f"device memory rungs exhausted: {exc2}") from exc2
        if assignment is None:
            from .batched import hype_batched_partition
            assignment, stats = hype_batched_partition(
                hg, k, params, return_stats=True)
            stats.fallbacks += 1
            return (assignment, stats) if return_stats else assignment
        pst.stats.fallbacks += 1
        assert (assignment >= 0).all()
        assignment = maybe_refine(hg, k, params, assignment, pst.stats)
        return (assignment, pst.stats) if return_stats else assignment
    if assignment is None:
        from .superstep import hype_superstep_partition
        assignment, stats = hype_superstep_partition(hg, k, params,
                                                     return_stats=True)
        stats.fallbacks += 1
        return (assignment, stats) if return_stats else assignment
    assert (assignment >= 0).all()
    assignment = maybe_refine(hg, k, params, assignment, st.stats)
    if return_stats:
        return assignment, st.stats
    return assignment


__all__ = ["DeviceParams", "hype_device_partition"]
