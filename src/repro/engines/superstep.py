"""Device-resident superstep engine (DESIGN.md §4b/§4d/§4g).

All ``k`` partitions grow *concurrently*: every superstep stacks the
fresh candidates of all growing phases into one fused
``hype_score_select`` device call against a graph image (CSR +
assignment + score cache) that was uploaded once. Scores survive across
refills and phases — admissions *decrement* their neighbors' cached
scores instead of wiping the cache. Supersteps run double-buffered on
the shared pipeline driver (``engines.runtime.run_pipeline``);
``pipeline_depth=1`` is the lock-step schedule, bit for bit.

The module co-locates the engine's jitted device programs with its
state: the default ``pipeline_superstep_device`` plus the memory-rung
variants of DESIGN.md §4g (``chunked`` / ``spill`` / ``paged``), all
built from the traced helpers in ``core/scoring.py`` so they stay
semantically identical to each other — and to the sharded engine's
program (``engines.sharded``).
"""
from __future__ import annotations

import dataclasses
import functools as _functools
from typing import Optional

import numpy as np

from ..core.hypergraph import Hypergraph
from ..core.scoring import (_apply_host_injections, _gather_fresh_tiles,
                            _poison_guard, _stale_masked_prev)
from .batched import BatchedParams
from .pipeline import PipelineState, _CallArgs
from .runtime import (BatchedStats, maybe_refine, partition_root, span,
                      run_pipeline as _run_pipeline,
                      run_pipeline_budgeted as _run_pipeline_budgeted)


@dataclasses.dataclass
class SuperstepParams(BatchedParams):
    """Knobs for the superstep engine (DESIGN.md §4).

    Inherits the batched knobs; ``t`` (admissions per phase per
    superstep), ``s``, ``pool_cap`` and ``seed`` keep their meaning.
    ``b``/``kernel_min``/``refill_lo`` are unused — refills are sized by
    ``rows`` and every score goes through the fused device call.
    """
    # fresh candidate rows per phase per superstep; None = max(8, t) so
    # refills keep up with the admission drain at any t
    rows: Optional[int] = None
    # in-flight supersteps of the double-buffered pipeline (DESIGN.md
    # §4d). 1 = lock-step (bit-identical to the pre-pipeline engine);
    # 2 = the default overlap: while the device runs superstep N the
    # host mirrors superstep N-1's admissions and packs superstep N+1.
    pipeline_depth: int = 2
    # device-memory budget (core/membudget.py, DESIGN.md §4g): bytes,
    # a "512MB"/"2GiB" string, or None = the REPRO_DEVICE_MEM_BUDGET
    # env var, falling back to the backend's reported allocator limit.
    # The engine plans its tile sizes against the budget before upload
    # and walks the memory-rung ladder on (real or injected) OOM.
    mem_budget: Optional[object] = None


# --------------------------------------------------------------------- #
# Device-resident superstep program: one jitted call performs the whole
# per-superstep device work — apply the host's injection delta (seeds /
# restarts), decrement-invalidate the cached scores of the delta's
# neighbors, gather the fresh candidate tiles from the device CSR, run
# the fused score+select kernel, write the fresh scores back into the
# device cache, and apply the per-phase admissions *on device*: stale
# proposals (candidates assigned by an interleaved superstep of the
# pipeline) are masked out, and the per-phase remaining-target cap is
# enforced against a device-resident admission counter. Winner-neighbor
# decrements ride the NEXT dispatch's host-preaggregated dirty pairs
# (the lock-step schedule). Only ids cross the host boundary, and the
# (n,)-sized assignment/cache (plus the (k,) counter) are *donated* —
# each superstep updates the image in place instead of copying it.


@_functools.lru_cache(maxsize=None)
def _pipeline_program():
    import jax
    import jax.numpy as jnp
    from repro.kernels.hype_score.kernel import SELECT_PAD
    from repro.kernels.hype_score.ops import hype_score_select

    # poison is NOT donated: at pipeline depth > 1 each in-flight handle
    # keeps a reference to its own poison output, which the next
    # dispatch would otherwise consume before harvest can read it —
    # and it is 4 bytes, so donation buys nothing.
    @_functools.partial(
        jax.jit, static_argnames=("tile_l", "select_k", "interpret"),
        donate_argnums=(2, 3, 4))
    def step(indptr, indices, assign, cache, acc, poison, delta_ids,
             delta_vals, dirty_ids, dirty_counts, fresh, bias, pool,
             fringe, targets, reset, *, tile_l, select_k, interpret):
        n = assign.shape[0]
        G, R = fresh.shape
        assign0, cache0, acc0 = assign, cache, acc
        # 1.-2. host injections (seeds / restarts — decrement-exact: the
        #    dirty pairs carry their pre-aggregated neighbor multiset
        #    plus earlier winners' queued decrements); the host only
        #    injects vertices that cannot sit in any in-flight slot, so
        #    the scatter is race-free at any pipeline depth.
        assign, cache, acc = _apply_host_injections(
            assign, cache, acc, delta_ids, delta_vals, dirty_ids,
            dirty_counts)
        # 3. gather fresh candidate tiles from the device CSR
        flat = fresh.reshape(-1)
        tile = _gather_fresh_tiles(indptr, indices, assign, flat, tile_l)
        # 4. held pool scores, stale slots masked (the redraw rule)
        prev, n_stale = _stale_masked_prev(pool, assign, cache)
        # 5. fused score + per-phase top-select
        scores, sel_idx, sel_val = hype_score_select(
            tile.reshape(G, R, tile_l), fringe, bias, prev,
            select_k=select_k, interpret=interpret)
        # 6. fresh scores enter the cache (pad rows dropped)
        cache = cache.at[jnp.where(flat >= 0, flat, n)].set(
            scores.reshape(-1), mode="drop")
        # 7. map selected slots to vertex ids; admissible = a real score
        #    on a still-unassigned id. The per-phase cap is the phase's
        #    remaining target, computed against the *device* totals —
        #    the host view may lag the pipeline, the device never does.
        slots = jnp.concatenate([fresh, pool], axis=1)
        cand = jnp.take_along_axis(slots, sel_idx, axis=1)
        ok = (sel_val < jnp.float32(SELECT_PAD)) & (cand >= 0)
        ok &= assign[jnp.where(cand >= 0, cand, 0)] < 0
        cap = jnp.maximum(targets - acc, 0)
        rank = jnp.cumsum(ok.astype(jnp.int32), axis=1)
        adm = ok & (rank <= cap[:, None])
        winners = jnp.where(adm, cand, -1)
        # 8. apply the winners on device (the host mirrors them at
        #    harvest time, possibly supersteps later). Their score-cache
        #    decrements stay HOST-side: the harvest pre-aggregates the
        #    winners' neighbor multiset into the next dispatch's dirty
        #    pairs — shipping (unique id, count) pairs is far cheaper
        #    than a (G*t, tile_l) gather+scatter here, and at depth 1 it
        #    reproduces the lock-step decrement schedule exactly.
        phase_row = jax.lax.broadcasted_iota(jnp.int32, adm.shape, 0)
        assign = assign.at[jnp.where(adm, cand, n)].set(
            phase_row, mode="drop")
        acc = acc + adm.sum(axis=1, dtype=acc.dtype)
        # 9. NaN/inf quarantine: a poisoned superstep reverts every
        #    mutation and admits nothing; the host replays it from the
        #    handle's buffers (reset=1). A no-op select when clean, so
        #    fault-free runs stay bit-identical.
        poisoned = _poison_guard(flat, scores.reshape(-1), poison, reset)
        assign = jnp.where(poisoned, assign0, assign)
        cache = jnp.where(poisoned, cache0, cache)
        acc = jnp.where(poisoned, acc0, acc)
        winners = jnp.where(poisoned, -1, winners)
        n_stale = jnp.where(poisoned, 0, n_stale)
        poison = poisoned.astype(jnp.int32)[None]
        return assign, cache, acc, poison, winners, n_stale

    return step


def pipeline_superstep_device(indptr, indices, assign, cache, acc,
                              poison, delta_ids, delta_vals, dirty_ids,
                              dirty_counts, fresh, bias, pool, fringe,
                              targets, reset, *, tile_l: int,
                              select_k: int, interpret: bool):
    """Run one device superstep; see ``_pipeline_program`` for the plan.

    All array arguments are device-resident jax arrays except the small
    per-superstep id buffers (delta, dirty, fresh, bias, pool, fringe,
    targets, reset), which are the only host->device traffic.
    ``assign``, ``cache``, ``acc`` and ``poison`` are DONATED — callers
    must keep the returned arrays and never touch the inputs again.
    ``poison`` is the sticky (1,) int32 quarantine flag threaded
    through the run (see ``scoring._poison_guard``); ``reset`` is the
    (1,) int32 replay marker. ``tile_l`` is a static gather width
    (bucketed by the caller so the program retraces only a handful of
    times); ``select_k`` is the per-phase admission count.
    Returns ``(assign', cache', acc', poison', winners, n_stale)``
    where ``winners`` is (G, select_k) int32 admitted ids (-1 = none),
    ``n_stale`` counts pool slots skipped because an interleaved
    superstep of the pipeline had already assigned them, and
    ``poison'[0] > 0`` means the superstep aborted (nothing applied)
    and must be replayed by the host.
    """
    return _pipeline_program()(
        indptr, indices, assign, cache, acc, poison, delta_ids,
        delta_vals, dirty_ids, dirty_counts, fresh, bias, pool, fringe,
        targets, reset, tile_l=tile_l, select_k=select_k,
        interpret=interpret)


# ------------------------------------------------- memory-rung variants
# Program variants for the memory-budget rung ladder (core/membudget.py,
# DESIGN.md §4g). Each shares the traced helpers of ``core/scoring.py``
# with ``_pipeline_program`` — the default program is deliberately left
# untouched (its depth-1 outputs are golden-hashed), and every variant
# is bit-exact to it on the single-device engine:
#
#   * ``_chunked_program``   — scores the G phases in ``g_chunk``
#     sequential slices (``lax.map``), dividing the peak (G·R, tile_l)
#     gather-tile footprint by ``g_chunk``. Phases are independent
#     until admission (selection runs against the pre-winner assignment
#     snapshot), so chunked scoring computes the same scores in the
#     same order.
#   * ``_spill_program``     — no device score cache: the host keeps a
#     float32 mirror, applies the dirty decrements itself (IEEE-
#     identical float32 adds of integer counts) and ships the held-pool
#     scores in; fresh scores return with the winners. Depth-1 only.
#   * ``_paged_program``     — takes the *pre-gathered raw* neighbor
#     tile (built chunk-by-chunk by ``membudget.PagedAdjacency``) and
#     applies the assignment masking in-program, reproducing
#     ``_gather_fresh_tiles``'s output exactly without a resident CSR.


@_functools.lru_cache(maxsize=None)
def _chunked_program():
    import jax
    import jax.numpy as jnp
    from repro.kernels.hype_score.kernel import SELECT_PAD
    from repro.kernels.hype_score.ops import hype_score_select

    @_functools.partial(
        jax.jit,
        static_argnames=("tile_l", "select_k", "interpret", "g_chunk"),
        donate_argnums=(2, 3, 4))
    def step(indptr, indices, assign, cache, acc, poison, delta_ids,
             delta_vals, dirty_ids, dirty_counts, fresh, bias, pool,
             fringe, targets, reset, *, tile_l, select_k, interpret,
             g_chunk):
        n = assign.shape[0]
        G, R = fresh.shape
        assign0, cache0, acc0 = assign, cache, acc
        assign, cache, acc = _apply_host_injections(
            assign, cache, acc, delta_ids, delta_vals, dirty_ids,
            dirty_counts)
        prev, n_stale = _stale_masked_prev(pool, assign, cache)
        # phase-chunked gather + score: pad G to a g_chunk multiple
        # (pad phases carry -1 candidates / +inf bias, so they select
        # nothing), then lax.map the gather + fused kernel over the
        # chunks — sequential execution divides the peak tile bytes by
        # g_chunk while computing the exact scores of the full call.
        Gc = -(-G // g_chunk)
        pad = g_chunk * Gc - G

        def padg(a, fill):
            if pad == 0:
                return a
            return jnp.concatenate(
                [a, jnp.full((pad,) + a.shape[1:], fill, a.dtype)])

        fresh_p = padg(fresh, -1).reshape(g_chunk, Gc, R)
        bias_p = padg(bias, jnp.inf).reshape(g_chunk, Gc, R)
        prev_p = padg(prev, jnp.inf).reshape(g_chunk, Gc, prev.shape[1])
        fringe_p = padg(fringe, -1).reshape(
            g_chunk, Gc, fringe.shape[1])

        def score_chunk(args):
            fr_c, bi_c, pr_c, fg_c = args
            flat_c = fr_c.reshape(-1)
            tile_c = _gather_fresh_tiles(indptr, indices, assign,
                                         flat_c, tile_l)
            return hype_score_select(
                tile_c.reshape(Gc, R, tile_l), fg_c, bi_c, pr_c,
                select_k=select_k, interpret=interpret)

        scores_c, sel_idx_c, sel_val_c = jax.lax.map(
            score_chunk, (fresh_p, bias_p, prev_p, fringe_p))
        scores = scores_c.reshape(g_chunk * Gc, R)[:G]
        sel_idx = sel_idx_c.reshape(g_chunk * Gc, select_k)[:G]
        sel_val = sel_val_c.reshape(g_chunk * Gc, select_k)[:G]
        # steps 6-9 of _pipeline_program, verbatim
        flat = fresh.reshape(-1)
        cache = cache.at[jnp.where(flat >= 0, flat, n)].set(
            scores.reshape(-1), mode="drop")
        slots = jnp.concatenate([fresh, pool], axis=1)
        cand = jnp.take_along_axis(slots, sel_idx, axis=1)
        ok = (sel_val < jnp.float32(SELECT_PAD)) & (cand >= 0)
        ok &= assign[jnp.where(cand >= 0, cand, 0)] < 0
        cap = jnp.maximum(targets - acc, 0)
        rank = jnp.cumsum(ok.astype(jnp.int32), axis=1)
        adm = ok & (rank <= cap[:, None])
        winners = jnp.where(adm, cand, -1)
        phase_row = jax.lax.broadcasted_iota(jnp.int32, adm.shape, 0)
        assign = assign.at[jnp.where(adm, cand, n)].set(
            phase_row, mode="drop")
        acc = acc + adm.sum(axis=1, dtype=acc.dtype)
        poisoned = _poison_guard(flat, scores.reshape(-1), poison, reset)
        assign = jnp.where(poisoned, assign0, assign)
        cache = jnp.where(poisoned, cache0, cache)
        acc = jnp.where(poisoned, acc0, acc)
        winners = jnp.where(poisoned, -1, winners)
        n_stale = jnp.where(poisoned, 0, n_stale)
        poison = poisoned.astype(jnp.int32)[None]
        return assign, cache, acc, poison, winners, n_stale

    return step


def chunked_superstep_device(indptr, indices, assign, cache, acc,
                             poison, delta_ids, delta_vals, dirty_ids,
                             dirty_counts, fresh, bias, pool, fringe,
                             targets, reset, *, tile_l: int,
                             select_k: int, interpret: bool,
                             g_chunk: int):
    """``pipeline_superstep_device`` with phase-chunked scoring.

    Identical contract and bit-identical outputs; ``g_chunk`` slices
    the gather + fused-kernel stage so only 1/g_chunk of the phases'
    tiles is materialized at a time (memory rung 1+, DESIGN.md §4g).
    """
    return _chunked_program()(
        indptr, indices, assign, cache, acc, poison, delta_ids,
        delta_vals, dirty_ids, dirty_counts, fresh, bias, pool, fringe,
        targets, reset, tile_l=tile_l, select_k=select_k,
        interpret=interpret, g_chunk=g_chunk)


@_functools.lru_cache(maxsize=None)
def _spill_program():
    import jax
    import jax.numpy as jnp
    from repro.kernels.hype_score.kernel import SELECT_PAD
    from repro.kernels.hype_score.ops import hype_score_select

    @_functools.partial(
        jax.jit, static_argnames=("tile_l", "select_k", "interpret"),
        donate_argnums=(2, 3))
    def step(indptr, indices, assign, acc, poison, delta_ids,
             delta_vals, fresh, bias, pool, prev_host, fringe, targets,
             reset, *, tile_l, select_k, interpret):
        n = assign.shape[0]
        G, R = fresh.shape
        assign0, acc0 = assign, acc
        # injections only — the dirty decrements were applied to the
        # HOST cache mirror at pack time (identical float32 arithmetic)
        inj = delta_ids >= 0
        assign = assign.at[jnp.where(inj, delta_ids, n)].set(
            delta_vals, mode="drop")
        acc = acc.at[jnp.where(inj, delta_vals, acc.shape[0])].add(
            1, mode="drop")
        flat = fresh.reshape(-1)
        tile = _gather_fresh_tiles(indptr, indices, assign, flat, tile_l)
        # held pool scores arrive from the host mirror; staleness is
        # still masked on device against the post-injection assignment
        psafe = jnp.where(pool >= 0, pool, 0)
        pool_ok = (pool >= 0) & (assign[psafe] < 0)
        prev = jnp.where(pool_ok, prev_host, jnp.inf).astype(jnp.float32)
        n_stale = ((pool >= 0) & ~pool_ok).sum().astype(jnp.int32)
        scores, sel_idx, sel_val = hype_score_select(
            tile.reshape(G, R, tile_l), fringe, bias, prev,
            select_k=select_k, interpret=interpret)
        slots = jnp.concatenate([fresh, pool], axis=1)
        cand = jnp.take_along_axis(slots, sel_idx, axis=1)
        ok = (sel_val < jnp.float32(SELECT_PAD)) & (cand >= 0)
        ok &= assign[jnp.where(cand >= 0, cand, 0)] < 0
        cap = jnp.maximum(targets - acc, 0)
        rank = jnp.cumsum(ok.astype(jnp.int32), axis=1)
        adm = ok & (rank <= cap[:, None])
        winners = jnp.where(adm, cand, -1)
        phase_row = jax.lax.broadcasted_iota(jnp.int32, adm.shape, 0)
        assign = assign.at[jnp.where(adm, cand, n)].set(
            phase_row, mode="drop")
        acc = acc + adm.sum(axis=1, dtype=acc.dtype)
        poisoned = _poison_guard(flat, scores.reshape(-1), poison, reset)
        assign = jnp.where(poisoned, assign0, assign)
        acc = jnp.where(poisoned, acc0, acc)
        winners = jnp.where(poisoned, -1, winners)
        n_stale = jnp.where(poisoned, 0, n_stale)
        poison = poisoned.astype(jnp.int32)[None]
        # fresh scores return to the host, which owns the cache now;
        # the host only writes them after the poison check
        return assign, acc, poison, winners, n_stale, scores

    return step


def spill_superstep_device(indptr, indices, assign, acc, poison,
                           delta_ids, delta_vals, fresh, bias, pool,
                           prev_host, fringe, targets, reset, *,
                           tile_l: int, select_k: int, interpret: bool):
    """``pipeline_superstep_device`` with the score cache spilled to host.

    The (n,) float32 cache lives on host (memory rung 4, depth-1 only):
    the caller applies dirty decrements to its mirror, ships the held
    pool's ``prev_host`` scores in, and writes the returned ``scores``
    back at harvest. All arithmetic the device skipped is IEEE-exact
    float32 on host, so results match the resident-cache program bit
    for bit at depth 1. ``assign``/``acc`` are DONATED.
    Returns ``(assign', acc', poison', winners, n_stale, scores)``.
    """
    return _spill_program()(
        indptr, indices, assign, acc, poison, delta_ids, delta_vals,
        fresh, bias, pool, prev_host, fringe, targets, reset,
        tile_l=tile_l, select_k=select_k, interpret=interpret)


@_functools.lru_cache(maxsize=None)
def _paged_program():
    import jax
    import jax.numpy as jnp
    from repro.kernels.hype_score.kernel import SELECT_PAD
    from repro.kernels.hype_score.ops import hype_score_select

    @_functools.partial(
        jax.jit, static_argnames=("select_k", "interpret"),
        donate_argnums=(0, 1, 2))
    def step(assign, cache, acc, poison, delta_ids, delta_vals,
             dirty_ids, dirty_counts, tile_raw, fresh, bias, pool,
             fringe, targets, reset, *, select_k, interpret):
        n = assign.shape[0]
        G, R = fresh.shape
        tile_l = tile_raw.shape[1]
        assign0, cache0, acc0 = assign, cache, acc
        assign, cache, acc = _apply_host_injections(
            assign, cache, acc, delta_ids, delta_vals, dirty_ids,
            dirty_counts)
        flat = fresh.reshape(-1)
        # the raw tile was gathered from the paged CSR before this call;
        # masking assigned neighbors here — against the post-injection
        # assignment — reproduces _gather_fresh_tiles's output exactly
        valid = tile_raw >= 0
        unassigned = assign[jnp.where(valid, tile_raw, 0)] < 0
        tile = jnp.where(valid & unassigned, tile_raw,
                         -1).astype(jnp.int32)
        prev, n_stale = _stale_masked_prev(pool, assign, cache)
        scores, sel_idx, sel_val = hype_score_select(
            tile.reshape(G, R, tile_l), fringe, bias, prev,
            select_k=select_k, interpret=interpret)
        cache = cache.at[jnp.where(flat >= 0, flat, n)].set(
            scores.reshape(-1), mode="drop")
        slots = jnp.concatenate([fresh, pool], axis=1)
        cand = jnp.take_along_axis(slots, sel_idx, axis=1)
        ok = (sel_val < jnp.float32(SELECT_PAD)) & (cand >= 0)
        ok &= assign[jnp.where(cand >= 0, cand, 0)] < 0
        cap = jnp.maximum(targets - acc, 0)
        rank = jnp.cumsum(ok.astype(jnp.int32), axis=1)
        adm = ok & (rank <= cap[:, None])
        winners = jnp.where(adm, cand, -1)
        phase_row = jax.lax.broadcasted_iota(jnp.int32, adm.shape, 0)
        assign = assign.at[jnp.where(adm, cand, n)].set(
            phase_row, mode="drop")
        acc = acc + adm.sum(axis=1, dtype=acc.dtype)
        poisoned = _poison_guard(flat, scores.reshape(-1), poison, reset)
        assign = jnp.where(poisoned, assign0, assign)
        cache = jnp.where(poisoned, cache0, cache)
        acc = jnp.where(poisoned, acc0, acc)
        winners = jnp.where(poisoned, -1, winners)
        n_stale = jnp.where(poisoned, 0, n_stale)
        poison = poisoned.astype(jnp.int32)[None]
        return assign, cache, acc, poison, winners, n_stale

    return step


def paged_superstep_device(assign, cache, acc, poison, delta_ids,
                           delta_vals, dirty_ids, dirty_counts,
                           tile_raw, fresh, bias, pool, fringe, targets,
                           reset, *, select_k: int, interpret: bool):
    """``pipeline_superstep_device`` without a resident CSR image.

    ``tile_raw`` is the (G·R, tile_l) *unmasked* neighbor-id tile
    assembled by ``membudget.PagedAdjacency.gather`` (memory rung 5);
    the program applies the assignment masking itself, so the scores —
    and therefore the whole run — are bit-identical to the
    resident-image engine. The single-device program's only other CSR
    use (winner decrements) already lives host-side, which is what
    makes this rung possible at all. ``assign``/``cache``/``acc`` are
    DONATED. Returns ``(assign', cache', acc', poison', winners,
    n_stale)``.
    """
    return _paged_program()(
        assign, cache, acc, poison, delta_ids, delta_vals, dirty_ids,
        dirty_counts, tile_raw, fresh, bias, pool, fringe, targets,
        reset, select_k=select_k, interpret=interpret)


# --------------------------------------------------------------------- #
class SuperstepState(PipelineState):
    """Pipeline state wired to this module's single-device programs."""

    def _call_program(self, args: _CallArgs, reset: np.ndarray):
        """Issue the fused superstep program; rotate the donated image.

        Returns ``(winners, n_stale, ncf, scores)`` futures (``ncf`` is
        None for the single-device engine; ``scores`` is None except on
        the spill rung, where the host owns the score cache and the
        fresh scores ride back with the winners). The memory plan picks
        the program variant (DESIGN.md §4g) — all of them bit-exact to
        the default on this engine.
        """
        if self.paged_adj is not None:
            tile_raw = self.paged_adj.gather(
                args.fresh.reshape(-1), self.tile_l)
            (self.dev_assign, self.dev_cache, self.dev_acc,
             self.dev_poison, winners, n_stale) = \
                paged_superstep_device(
                    self.dev_assign, self.dev_cache, self.dev_acc,
                    self.dev_poison, args.delta, args.vals, args.dirty,
                    args.dcnt, tile_raw, args.fresh, args.bias,
                    args.pool_arr, args.fringe, args.targets, reset,
                    select_k=args.select_k, interpret=self.interpret)
            return winners, n_stale, None, None
        if self.host_cache is not None:
            (self.dev_assign, self.dev_acc, self.dev_poison, winners,
             n_stale, scores) = spill_superstep_device(
                self.dev[0], self.dev[1], self.dev_assign, self.dev_acc,
                self.dev_poison, args.delta, args.vals, args.fresh,
                args.bias, args.pool_arr, args.prev, args.fringe,
                args.targets, reset, tile_l=self.tile_l,
                select_k=args.select_k, interpret=self.interpret)
            return winners, n_stale, None, scores
        if self.g_chunk > 1:
            (self.dev_assign, self.dev_cache, self.dev_acc,
             self.dev_poison, winners, n_stale) = \
                chunked_superstep_device(
                    self.dev[0], self.dev[1], self.dev_assign,
                    self.dev_cache, self.dev_acc, self.dev_poison,
                    args.delta, args.vals, args.dirty, args.dcnt,
                    args.fresh, args.bias, args.pool_arr, args.fringe,
                    args.targets, reset, tile_l=self.tile_l,
                    select_k=args.select_k, interpret=self.interpret,
                    g_chunk=self.g_chunk)
            return winners, n_stale, None, None
        (self.dev_assign, self.dev_cache, self.dev_acc, self.dev_poison,
         winners, n_stale) = pipeline_superstep_device(
            self.dev[0], self.dev[1], self.dev_assign, self.dev_cache,
            self.dev_acc, self.dev_poison, args.delta, args.vals,
            args.dirty, args.dcnt, args.fresh, args.bias, args.pool_arr,
            args.fringe, args.targets, reset, tile_l=self.tile_l,
            select_k=args.select_k, interpret=self.interpret)
        return winners, n_stale, None, None


def run_pipeline(hg: Hypergraph, k: int, p: SuperstepParams,
                 mem_rung: int = 0,
                 mem_warm: Optional[np.ndarray] = None,
                 mem_retries: int = 0):
    """One superstep-engine pipeline run (no memory-rung retry loop).

    ``engines.runtime.run_pipeline`` with this engine's state factory.
    Exposed for callers that drive the rung ladder themselves (the
    device engine's OOM fallback, the membudget test harness).
    """
    return _run_pipeline(
        hg, k, p,
        lambda p2, rung: SuperstepState(hg, k, p2, mem_rung=rung),
        "hype_superstep", devices=0, mem_rung=mem_rung,
        mem_warm=mem_warm, mem_retries=mem_retries)


def run_pipeline_budgeted(hg: Hypergraph, k: int, p: SuperstepParams):
    """``run_pipeline`` under the §4g memory-rung retry loop."""
    return _run_pipeline_budgeted(
        hg, k, p,
        lambda p2, rung: SuperstepState(hg, k, p2, mem_rung=rung),
        "hype_superstep", devices=0)


@partition_root
def hype_superstep_partition(hg: Hypergraph, k: int,
                             params: Optional[SuperstepParams] = None,
                             return_stats: bool = False):
    """Partition ``hg`` with the device-resident superstep engine.

    Same contract as ``hype_batched_partition`` (complete int32
    assignment, max - min <= 1 vertex balance) but all ``k`` partitions
    grow *concurrently*: every superstep stacks the fresh candidates of
    all growing phases into one fused ``hype_score_select`` device call
    against a graph image (CSR + assignment + score cache) that was
    uploaded once. Scores survive across refills and phases — admissions
    *decrement* their neighbors' cached scores instead of wiping the
    cache. ``params.pipeline_depth`` supersteps run double-buffered
    (DESIGN.md §4d): while the device computes superstep N the host
    mirrors N-1's admissions and packs N+1; ``pipeline_depth=1`` is the
    lock-step schedule, bit for bit. Falls back to
    ``hype_batched_partition`` when the adjacency guard trips
    (pathological hub expansion), counted in ``stats.fallbacks``.
    """
    if params is None:
        params = SuperstepParams()
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
    if k == 1:
        out = np.zeros(hg.n, dtype=np.int32)
        return (out, BatchedStats()) if return_stats else out
    assignment, st = run_pipeline_budgeted(hg, k, params)
    if assignment is None:
        from .batched import hype_batched_partition
        assignment, stats = hype_batched_partition(hg, k, params,
                                                   return_stats=True)
        stats.fallbacks += 1
        return (assignment, stats) if return_stats else assignment
    assert (assignment >= 0).all()
    with span(st, "hype.finish"):
        assignment = maybe_refine(hg, k, params, assignment, st.stats)
    if return_stats:
        return assignment, st.stats
    return assignment


__all__ = ["SuperstepParams", "SuperstepState",
           "hype_superstep_partition", "run_pipeline",
           "run_pipeline_budgeted", "pipeline_superstep_device",
           "chunked_superstep_device", "spill_superstep_device",
           "paged_superstep_device"]
