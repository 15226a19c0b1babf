"""Mesh-sharded superstep engine (DESIGN.md §4c).

Phase groups sharded over a 1-D JAX device mesh with ``shard_map``: the
CSR graph image, assignment vector and score cache are *replicated* per
device, each device runs the fused ``hype_score_select`` superstep for
its own contiguous phase group, and ONE ``all_gather`` per superstep
exchanges fresh scores and proposed admissions so every replica stays
globally consistent — including the exact-decrement score-cache
invalidations. Cross-device admission conflicts are resolved
deterministically (lowest phase id wins).

Shares the pipeline driver (``engines.runtime.run_pipeline``) and host
state (``engines.pipeline.PipelineState``) with the single-device
engine: only the device program, the per-device-group pool masks and
the collective counters differ.
"""
from __future__ import annotations

import dataclasses
import functools as _functools
from typing import Optional

import numpy as np

from ..core.hypergraph import Hypergraph
from ..core import membudget
from ..core.scoring import (_apply_host_injections, _gather_fresh_tiles,
                            _poison_guard, _stale_masked_prev,
                            gather_csr_rows)
from .pipeline import PipelineState, _CallArgs, _Superstep
from .runtime import (BatchedStats, maybe_refine, run_pipeline_budgeted
                      as _run_pipeline_budgeted)
from .superstep import SuperstepParams, hype_superstep_partition


@dataclasses.dataclass
class ShardedParams(SuperstepParams):
    """Knobs for the mesh-sharded superstep engine (DESIGN.md §4c).

    Inherits every superstep knob. ``devices`` sets the 1-D mesh size the
    k phase groups are sharded over; ``None`` uses every local JAX device
    (capped at ``k``), and more devices than exist is an error. On CPU,
    simulate a mesh with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=4``.
    """
    devices: Optional[int] = None


# ---------------------------------------------------------- sharded superstep
# Mesh-sharded superstep program: the per-superstep device work of the
# sharded engine, run under shard_map over a 1-D device mesh. The CSR
# image, assignment and score cache are *replicated* on every device;
# the k phase groups are sharded — each device gathers, scores and
# selects only its own contiguous group of phases, then ONE all_gather
# per superstep exchanges (fresh scores | admissions) so every replica
# applies the same cache writes, conflict resolution and exact-decrement
# invalidations. Replicas therefore stay bit-identical without ever
# shipping the (n,)-sized state between devices.


@_functools.lru_cache(maxsize=None)
def _sharded_mesh(num_devices: int):
    """1-D device mesh over the first ``num_devices`` local devices."""
    import jax
    import numpy as _np
    from jax.sharding import Mesh

    return Mesh(_np.asarray(jax.devices()[:num_devices]), ("shard",))


@_functools.lru_cache(maxsize=None)
def _sharded_program(num_devices: int, group_l: int, tile_l: int,
                     select_k: int, interpret: bool):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.kernels.hype_score.kernel import SELECT_PAD
    from repro.kernels.hype_score.ops import hype_score_select_shard

    kL = group_l

    def step(indptr, indices, assign, cache, acc, poison, delta_ids,
             delta_vals, dirty_ids, dirty_counts, fresh, bias, pool,
             fringe, targets, reset):
        n = assign.shape[0]
        G, R = fresh.shape
        t = select_k
        assign0, cache0, acc0 = assign, cache, acc
        # 1. host injections + dirty decrements — replicated inputs,
        #    applied identically on every replica (shared helper keeps
        #    this program bit-aligned with the single-device one)
        assign, cache, acc = _apply_host_injections(
            assign, cache, acc, delta_ids, delta_vals, dirty_ids,
            dirty_counts)
        # 2. this device's phase-group shard; the admission cap is each
        #    phase's remaining target per the *device* totals (the host
        #    view may lag the pipeline, the replicas never do)
        off = jax.lax.axis_index("shard") * kL
        fresh_l = jax.lax.dynamic_slice_in_dim(fresh, off, kL, 0)
        pool_l = jax.lax.dynamic_slice_in_dim(pool, off, kL, 0)
        cap = jnp.maximum(targets - acc, 0)
        cap_l = jax.lax.dynamic_slice_in_dim(cap, off, kL, 0)
        # 3. gather ONLY the shard's fresh-candidate tiles from the
        #    replicated CSR
        flat = fresh_l.reshape(-1)
        tile = _gather_fresh_tiles(indptr, indices, assign, flat, tile_l)
        # 4. held pool scores from the replicated cache, stale slots
        #    masked — computed on the *global* pool so the count is
        #    replicated
        prev, n_stale = _stale_masked_prev(pool, assign, cache)
        # 5. fused score + top-select on the local phase group
        scores_l, sel_idx, sel_val = hype_score_select_shard(
            tile.reshape(kL, R, tile_l), fringe, bias, prev,
            select_k=t, shard_offset=off, interpret=interpret)
        # 6. map selected slots to vertex ids and apply the per-phase
        #    admission cap (remaining target): slots are score-ascending,
        #    so the cap keeps the best ``cap`` admissible ones.
        slots = jnp.concatenate([fresh_l, pool_l], axis=1)
        cand = jnp.take_along_axis(slots, sel_idx, axis=1)
        ok = (sel_val < jnp.float32(SELECT_PAD)) & (cand >= 0)
        ok &= assign[jnp.where(cand >= 0, cand, 0)] < 0
        rank = jnp.cumsum(ok.astype(jnp.int32), axis=1)
        adm = ok & (rank <= cap_l[:, None])
        adm_ids = jnp.where(adm, cand, -1)              # (kL, t)
        # 7. the superstep's single collective: all devices exchange
        #    [fresh scores | proposed admissions] in one all_gather
        payload = jnp.concatenate(
            [jax.lax.bitcast_convert_type(scores_l, jnp.int32), adm_ids],
            axis=1)                                     # (kL, R + t)
        gathered = jax.lax.all_gather(payload, "shard", axis=0,
                                      tiled=True)       # (G, R + t)
        g_scores = jax.lax.bitcast_convert_type(gathered[:, :R],
                                                jnp.float32)
        g_adm = gathered[:, R:]                         # (G, t)
        # 8. fresh scores enter every replica's cache (fresh ids are a
        #    replicated input, so the write is identical everywhere)
        flat_g = fresh.reshape(-1)
        cache = cache.at[jnp.where(flat_g >= 0, flat_g, n)].set(
            g_scores.reshape(-1), mode="drop")
        # 9. deterministic conflict resolution: when several phases
        #    propose the same vertex in one superstep, the LOWEST phase
        #    id wins; losers keep the vertex out and redraw from their
        #    pools next superstep. Sort (id, phase) pairs and keep each
        #    id's first occurrence.
        ids_f = g_adm.reshape(-1)                       # (G * t,)
        phase_f = (jax.lax.iota(jnp.int32, G * t) // t)
        ids_key = jnp.where(ids_f >= 0, ids_f, n)
        order = jnp.lexsort((phase_f, ids_key))
        sorted_ids = ids_f[order]
        first = jnp.concatenate(
            [jnp.ones((1,), bool), sorted_ids[1:] != sorted_ids[:-1]])
        win_sorted = first & (sorted_ids >= 0)
        winner = jnp.zeros((G * t,), bool).at[order].set(win_sorted)
        n_conflicts = ((ids_f >= 0) & ~winner).sum().astype(jnp.int32)
        # 10. apply the winners to every replica's assignment + totals
        assign = assign.at[jnp.where(winner, ids_f, n)].set(
            phase_f, mode="drop")
        acc = acc.at[phase_f].add(winner.astype(acc.dtype))
        # 11. exact-decrement invalidation for the winners: every
        #     neighbor of a newly assigned vertex has one fewer
        #     unassigned neighbor. Gather width is the run's tile_l;
        #     the (rare) winners with more neighbors than that get their
        #     tail decrements queued by the host into the next
        #     superstep's dirty buffer, keeping the cache exact.
        wsafe = jnp.where(winner, ids_f, 0)
        wstart = indptr[wsafe]
        wdeg = jnp.minimum(indptr[wsafe + 1] - wstart, tile_l)
        wcol = jax.lax.broadcasted_iota(jnp.int32, (G * t, tile_l), 1)
        wvalid = (wcol < wdeg[:, None]) & winner[:, None]
        wnbr = indices[jnp.where(wvalid, wstart[:, None] + wcol, 0)]
        cache = cache.at[jnp.where(wvalid, wnbr, n)].add(
            -1.0, mode="drop")
        winners = jnp.where(winner, ids_f, -1).reshape(G, t)
        # 12. NaN/inf quarantine on the *gathered* scores — replicated
        #     input to the guard, so every replica takes the same revert
        #     branch and the replicas stay bit-identical. No-op when
        #     clean (fault-free runs unchanged).
        poisoned = _poison_guard(flat_g, g_scores.reshape(-1), poison,
                                 reset)
        assign = jnp.where(poisoned, assign0, assign)
        cache = jnp.where(poisoned, cache0, cache)
        acc = jnp.where(poisoned, acc0, acc)
        winners = jnp.where(poisoned, -1, winners)
        n_conflicts = jnp.where(poisoned, 0, n_conflicts)
        n_stale = jnp.where(poisoned, 0, n_stale)
        poison = poisoned.astype(jnp.int32)[None]
        return assign, cache, acc, poison, winners, n_conflicts, n_stale

    mesh = _sharded_mesh(num_devices)
    rep = P()     # every array is replicated; devices differ via axis_index
    # poison undonated for the same reason as _pipeline_program: older
    # in-flight handles must still be able to read their poison output.
    return jax.jit(jax.shard_map(
        step, mesh=mesh,
        in_specs=(rep,) * 16, out_specs=(rep,) * 7,
        check_vma=False), donate_argnums=(2, 3, 4))


def sharded_superstep_device(indptr, indices, assign, cache, acc,
                             poison, delta_ids, delta_vals, dirty_ids,
                             dirty_counts, fresh, bias, pool, fringe,
                             targets, reset, *, num_devices: int,
                             group_l: int, tile_l: int, select_k: int,
                             interpret: bool):
    """Run one mesh-sharded superstep; see ``_sharded_program``.

    ``fresh``/``bias``/``pool``/``fringe``/``targets`` stack all
    ``G = num_devices * group_l`` phases; each device processes the
    contiguous group ``[axis_index * group_l, ...)`` and ONE all_gather
    per call exchanges (fresh scores | proposed admissions), after which
    every replica applies identical cache writes, lowest-phase-wins
    conflict resolution and exact decrements. ``assign``/``cache``/
    ``acc``/``poison`` are DONATED — keep the returned arrays, never
    reuse the inputs. ``poison``/``reset`` are the (1,) int32 NaN
    quarantine flag and replay marker (see ``_poison_guard``); a
    poisoned superstep reverts every mutation on every replica and must
    be replayed by the host. Admission caps are each phase's remaining
    target computed against the device-resident ``acc`` totals, so they
    stay exact at any pipeline depth. Returns ``(assign', cache',
    acc', poison', winners (G, select_k) int32 ids (-1 = none),
    n_conflicts, n_stale)``.
    """
    return _sharded_program(num_devices, group_l, tile_l, select_k,
                            interpret)(
        indptr, indices, assign, cache, acc, poison, delta_ids,
        delta_vals, dirty_ids, dirty_counts, fresh, bias, pool, fringe,
        targets, reset)


# --------------------------------------------------------------------- #
class ShardedState(PipelineState):
    """Superstep state plus the mesh and per-device-group pool masks.

    The CSR image, assignment, score cache and admission totals are
    *replicated* on every mesh device; the phase groups are sharded.
    Pool membership is tracked per device group (``group_pool``) —
    groups draw candidates independently, so two groups may pool (and
    propose) the same vertex; the device program's lowest-phase-wins
    rule resolves it, and the host mirrors winners without re-queuing
    them as deltas. Shares the pipeline driver with the single-device
    engine: only ``dispatch`` (the shard_map program + collective
    counters) and the pool-mask hooks differ.
    """

    def __init__(self, hg: Hypergraph, k_padded: int, p: ShardedParams,
                 num_devices: int, mem_rung: int = 0):
        self.D = num_devices
        self.kL = k_padded // num_devices
        mesh = _sharded_mesh(num_devices)
        super().__init__(hg, k_padded, p, mesh=mesh, mem_rung=mem_rung)
        if self.dev is None:
            return
        self.mesh = mesh
        self.group_pool = np.zeros((num_devices, hg.n), dtype=bool)
        # the image lives once per device
        self.stats.device_image_bytes *= num_devices

    def group_of(self, g: int) -> int:
        return g // self.kL

    def _pmask(self, g: int) -> np.ndarray:
        return self.group_pool[g // self.kL]

    def _restart_mask(self) -> np.ndarray:
        # groups pool independently, so an injection-safe vertex must
        # sit in NO group's pool (it could be an in-flight slot there)
        return self.group_pool.any(axis=0)

    def release_pools(self) -> None:
        super().release_pools()
        self.group_pool[:] = False

    def _release_members(self, vs: np.ndarray, ph: np.ndarray) -> None:
        self.group_pool[ph // self.kL, vs] = False

    def _queue_decrements(self, vs: np.ndarray, exclude=()) -> None:
        """Sharded: the device program already decremented each winner's
        first ``tile_l`` neighbors; only the clipped tails of the (rare)
        wider winners ride the next dispatch's dirty pairs — with the
        same in-flight rescore exclusion as the single-device engine."""
        self.stats.cache_invalidations += int(
            np.minimum(self.deg[vs], self.tile_l).sum())
        wide = vs[self.deg[vs] > self.tile_l]
        if wide.size == 0:
            return
        indptr, indices = self.adj
        nbrs, owner = gather_csr_rows(indptr, indices, wide)
        lens = (indptr[wide + 1] - indptr[wide]).astype(np.int64)
        start = np.cumsum(lens) - lens
        off = np.arange(nbrs.size, dtype=np.int64) - start[owner]
        tail = self._filter_rescored(
            nbrs[off >= self.tile_l].astype(np.int64), exclude)
        if tail.size:
            self.pending_dirty.append(tail)

    def _to_device(self, arr: np.ndarray):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec
        return jax.device_put(jnp.asarray(arr),
                              NamedSharding(self.mesh, PartitionSpec()))

    # the sharded dispatch site owns the per-superstep all_gather, so a
    # failed collective is injected (and retried) there too
    _fault_kinds = ("dispatch", "collective", "oom")
    # no chunked/spill/paged program variants exist for the replicated
    # shard_map image — only width and depth shrink (DESIGN.md §4g)
    _mem_features = membudget.SHARDED_FEATURES

    def _call_program(self, args: _CallArgs, reset: np.ndarray):
        """One mesh-sharded superstep (async).

        Host->device traffic is the same id/bias buffers as the
        single-device engine; the host-side dirty pairs carry the
        injections' neighbor multisets *and* the decrement tails of
        earlier wider-than-tile winners (the device clips its own
        decrement gather at ``tile_l``), so the replicated cache stays
        exact.
        """
        (self.dev_assign, self.dev_cache, self.dev_acc, self.dev_poison,
         winners, ncf, n_stale) = sharded_superstep_device(
            self.dev[0], self.dev[1], self.dev_assign, self.dev_cache,
            self.dev_acc, self.dev_poison, args.delta, args.vals,
            args.dirty, args.dcnt, args.fresh, args.bias, args.pool_arr,
            args.fringe, args.targets, reset, num_devices=self.D,
            group_l=self.kL, tile_l=self.tile_l,
            select_k=args.select_k, interpret=self.interpret)
        return winners, n_stale, ncf, None

    def _count_dispatch(self, fresh: np.ndarray, select_k: int) -> None:
        kG, R = fresh.shape
        # one all_gather per superstep: every device materializes the
        # global (kG, R + t) int32 payload of fresh scores + admissions
        self.stats.collectives += 1
        self.stats.collective_bytes += self.D * kG * (R + select_k) * 4

    def _count_harvest(self, handle: _Superstep) -> None:
        # the conflict count rides the harvested superstep's results, so
        # reading it here never adds a block
        self.stats.admission_conflicts += int(handle.ncf)

    def capture_payload(self, acc: np.ndarray, cur_depth: int) -> dict:
        pay = super().capture_payload(acc, cur_depth)
        pay["group_pool"] = self.group_pool.copy()
        return pay

    def restore_exact(self, pay: dict):
        out = super().restore_exact(pay)
        self.group_pool = pay["group_pool"].copy()
        return out


def hype_sharded_partition(hg: Hypergraph, k: int,
                           params: Optional[ShardedParams] = None,
                           return_stats: bool = False):
    """Partition ``hg`` with the mesh-sharded superstep engine.

    Same contract as ``hype_superstep_partition`` (complete int32
    assignment, ``max - min <= 1`` vertex balance, all k phases grown
    concurrently) but the phase groups are sharded over a 1-D JAX device
    mesh with ``shard_map``: the CSR graph image, assignment vector and
    score cache are replicated per device, each device runs the fused
    ``hype_score_select`` superstep for its own contiguous phase group,
    and a single ``all_gather`` per superstep exchanges fresh scores and
    proposed admissions so every replica stays globally consistent —
    including the exact-decrement score-cache invalidations. Cross-device
    admission conflicts (two groups proposing the same vertex in one
    superstep) are resolved deterministically: the lowest phase id wins
    and losers redraw from their pools next superstep.

    ``params.devices`` picks the mesh size (default: all local devices,
    capped at ``k``; asking for more devices than exist raises
    ``ValueError``); on CPU simulate devices with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``. With one
    device the engine degenerates to (slightly reordered) single-device
    superstep growth. Supersteps run on the shared double-buffered
    pipeline (``params.pipeline_depth``, DESIGN.md §4d). Falls back to
    ``hype_superstep_partition``'s own fallback chain when the
    adjacency guard trips; each hand-off counts in ``stats.fallbacks``.
    """
    if params is None:
        params = ShardedParams()
    if params.rows is None:
        params = dataclasses.replace(params, rows=max(8, params.t))
    if k < 1:
        raise ValueError("k must be >= 1")
    if params.t < 1 or params.rows < 1 or params.pool_cap < 1:
        raise ValueError("rows, pool_cap, t must all be >= 1")
    if params.pipeline_depth < 1:
        raise ValueError("pipeline_depth must be >= 1")
    if params.snapshot_every > 0 and not params.snapshot_dir:
        raise ValueError("snapshot_every requires snapshot_dir")
    if params.devices is not None and params.devices < 1:
        raise ValueError("devices must be >= 1")
    if k == 1:
        out = np.zeros(hg.n, dtype=np.int32)
        return (out, BatchedStats()) if return_stats else out
    import jax
    avail = len(jax.devices())
    if params.devices is not None and params.devices > avail:
        raise ValueError(
            f"devices={params.devices} but only {avail} JAX devices "
            f"exist")
    num = min(params.devices or avail, k)
    kG = (-(-k // num)) * num       # phase groups padded to the mesh
    assignment, st = _run_pipeline_budgeted(
        hg, k, params,
        lambda p2, rung: ShardedState(hg, kG, p2, num, mem_rung=rung),
        "hype_sharded", devices=num)
    if assignment is None:
        assignment, stats = hype_superstep_partition(hg, k, params,
                                                     return_stats=True)
        stats.fallbacks += 1
        return (assignment, stats) if return_stats else assignment
    assert (assignment >= 0).all()
    assignment = maybe_refine(hg, k, params, assignment, st.stats)
    if return_stats:
        return assignment, st.stats
    return assignment


__all__ = ["ShardedParams", "ShardedState", "hype_sharded_partition",
           "sharded_superstep_device"]
