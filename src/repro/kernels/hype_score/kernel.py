"""Batched HYPE external-neighbors scoring kernel (Pallas TPU).

TPU adaptation of the paper's score computation (§III-B2c): instead of the
CPU hash-set intersection, the fringe (s <= 16 vertices — the paper fixes
s = 10) is broadcast-compared against a tile of candidate neighbor lists
on the VPU:

    score[b] = #valid(nbrs[b,:]) - #(valid & in-fringe)

No gather, no hash set — one (TB, L, s) compare + two reductions per tile,
which is exactly the shape of work the VPU's 8x128 lanes want. This kernel
is what makes the *batched-candidate* HYPE variant (score r >> 2
candidates per step, pick top ones) profitable on TPU; the sequential
paper algorithm scores 2 candidates at a time and is latency-bound.

Tiles: nbrs (TB, L) in VMEM; fringe is tiny and replicated per tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _score_kernel(fringe_ref, nbrs_ref, out_ref):
    nbrs = nbrs_ref[...]                      # (TB, L)
    fringe = fringe_ref[...]                  # (1, s)
    valid = nbrs >= 0
    member = jnp.zeros_like(valid)
    s = fringe.shape[-1]
    for j in range(s):                        # s is a small static constant
        member = jnp.logical_or(member, nbrs == fringe[0, j])
    member = jnp.logical_and(member, valid)
    score = valid.sum(axis=1) - member.sum(axis=1)
    out_ref[...] = score.astype(jnp.int32)[:, None]


def hype_scores_kernel(nbrs, fringe, *, tile_b: int = 256,
                       interpret: bool = False):
    """nbrs: (B, L) int32 (-1 pad, pre-deduped); fringe: (s,) int32."""
    B, L = nbrs.shape
    tile_b = min(tile_b, B)
    assert B % tile_b == 0, "pad B to a tile multiple"
    fringe2d = fringe[None, :]
    out = pl.pallas_call(
        _score_kernel,
        grid=(B // tile_b,),
        in_specs=[
            pl.BlockSpec((1, fringe.shape[0]), lambda i: (0, 0)),
            pl.BlockSpec((tile_b, L), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((tile_b, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, 1), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(fringe2d, nbrs)
    return out[:, 0]


# --------------------------------------------------------------------- #
# Fused score + select: the superstep engine's one-call-per-step kernel.
# --------------------------------------------------------------------- #

# Scores at or above this value are "not a candidate" (padded rows /
# empty pool slots). Finite so that exclusion during the running-argmin
# loop (set to +inf) stays distinguishable from a pad; any real score,
# including the 1e12 hub penalty, sits far below it.
SELECT_PAD = 1e30


def _score_select_kernel(fringe_ref, prev_ref, bias_ref, nbrs_ref,
                         score_ref, idx_ref, val_ref, rem_ref, *,
                         select_k: int, rows: int):
    """A *group* of growth phases per grid step: score + top-k select.

    The block stacks ``TG`` phases of ``rows`` fresh-candidate rows each.
    Scoring is exactly ``_score_kernel`` (fringe membership subtracted on
    the VPU, per-phase fringe rows) plus the per-row ``bias`` (hub
    penalty / +inf row pad). Selection then runs a running-argmin
    reduction in VMEM over each phase's scored rows *concatenated with*
    its held pool scores — vectorized across the TG phases of the block —
    so one kernel call performs refill-scoring plus the multi-admission
    selection the host used to argsort for. Selected indices < rows refer
    to fresh tile rows, >= rows to pool slots.

    Every value stays 2-D with phases on sublanes — Mosaic rejects the
    1-D <-> 2-D shape casts a flat row layout needs. Row ``r`` of all TG
    phases is one (TG, L) load, so the phase's fringe is a (TG, 1)
    column broadcast along lanes and the row's score lands in lane ``r``
    of a (TG, rows) tile; selection keeps the fresh (TG, rows) and pool
    (TG, P) tiles apart and writes each pick into its output lane.
    """
    fringe = fringe_ref[...]                  # (TG, s)
    tg = fringe.shape[0]
    fcols = [fringe[:, j:j + 1] for j in range(fringe.shape[1])]
    pos_f = jax.lax.broadcasted_iota(jnp.int32, (tg, rows), 1)

    def score_row(r, fresh):
        nbrs = nbrs_ref[:, r, :]              # (TG, L): row r per phase
        valid = nbrs >= 0
        member = jnp.zeros_like(valid)
        for fj in fcols:                      # s is a small static constant
            member = jnp.logical_or(member, nbrs == fj)
        member = jnp.logical_and(member, valid)
        score = (valid.sum(axis=1, keepdims=True)
                 - member.sum(axis=1, keepdims=True))
        return jnp.where(pos_f == r, score.astype(jnp.float32), fresh)

    fresh = jax.lax.fori_loop(0, rows, score_row,
                              jnp.zeros((tg, rows), jnp.float32))
    fresh = fresh + bias_ref[...]
    score_ref[...] = fresh

    # select over [fresh rows | pool slots]; clamp +inf pads to the
    # finite SELECT_PAD so the exclusion sentinel (+inf) stays unique.
    pad = jnp.float32(SELECT_PAD)
    fresh = jnp.minimum(fresh, pad)
    prev = jnp.minimum(prev_ref[...], pad)    # (TG, P)
    n_slots = rows + prev.shape[1]
    pos_p = jax.lax.broadcasted_iota(jnp.int32, prev.shape, 1) + rows
    out_col = jax.lax.broadcasted_iota(jnp.int32, (tg, select_k), 1)
    sel_i = jnp.zeros((tg, select_k), jnp.int32)
    sel_v = jnp.zeros((tg, select_k), jnp.float32)
    for i in range(select_k):                 # select_k is small and static
        mv = jnp.minimum(jnp.min(fresh, axis=1, keepdims=True),
                         jnp.min(prev, axis=1, keepdims=True))   # (TG, 1)
        am = jnp.minimum(
            jnp.min(jnp.where(fresh == mv, pos_f, n_slots), axis=1,
                    keepdims=True),
            jnp.min(jnp.where(prev == mv, pos_p, n_slots), axis=1,
                    keepdims=True))
        sel_i = jnp.where(out_col == i, am, sel_i)
        sel_v = jnp.where(out_col == i, mv, sel_v)
        fresh = jnp.where(pos_f == am, jnp.float32(jnp.inf), fresh)
        prev = jnp.where(pos_p == am, jnp.float32(jnp.inf), prev)
    idx_ref[...] = sel_i
    val_ref[...] = sel_v
    # refill trigger: real candidates left per phase AFTER selection
    # (selected slots are +inf, pads/empties sit at SELECT_PAD). The
    # device-resident loop reads this to decide which phases need a
    # pool refill next superstep without a host round-trip.
    rem_ref[...] = ((fresh < pad).sum(axis=1, keepdims=True)
                    + (prev < pad).sum(axis=1, keepdims=True)
                    ).astype(jnp.int32)


def hype_score_select_kernel(nbrs, fringe, bias, prev, *, select_k: int,
                             tile_g: int = 8, interpret: bool = False,
                             with_remaining: bool = False):
    """Fused scoring + per-phase top-``select_k`` selection.

    nbrs:   (G*R, L) int32, -1 padded — G stacked phase tiles of R rows.
    fringe: (G, s)   int32, -1 padded — one fringe row per phase.
    bias:   (G*R,)   float32 — additive per-row bias (TRUNC_PENALTY for
            truncated hubs, +inf for absent/pad rows).
    prev:   (G, P)   float32 — held pool scores per phase (+inf = empty).

    ``tile_g`` phases are processed per grid step (selection vectorized
    across them); G must be a multiple of it — the jitted ``ops`` wrapper
    pads. Returns ``(scores, sel_idx, sel_val)``: scores (G*R,) f32
    (fresh rows, bias included); sel_idx (G, select_k) int32 into the
    phase's [fresh rows | pool slots] concatenation; sel_val
    (G, select_k) f32 (>= SELECT_PAD means "nothing there"). With
    ``with_remaining`` a fourth output rides along: remaining (G,) int32,
    the count of real candidate slots left per phase after selection —
    the refill-trigger flag source for the device-resident loop.
    """
    G, s = fringe.shape
    B, L = nbrs.shape
    assert B % G == 0, "stacked tile rows must divide evenly into phases"
    R = B // G
    P = prev.shape[1]
    assert prev.shape[0] == G and bias.shape == (B,)
    assert 1 <= select_k <= R + P
    tile_g = min(tile_g, G)
    assert G % tile_g == 0, "pad the phase count to a tile_g multiple"
    scores, idx, val, rem = pl.pallas_call(
        functools.partial(_score_select_kernel, select_k=select_k,
                          rows=R),
        grid=(G // tile_g,),
        in_specs=[
            pl.BlockSpec((tile_g, s), lambda g: (g, 0)),
            pl.BlockSpec((tile_g, P), lambda g: (g, 0)),
            pl.BlockSpec((tile_g, R), lambda g: (g, 0)),
            pl.BlockSpec((tile_g, R, L), lambda g: (g, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tile_g, R), lambda g: (g, 0)),
            pl.BlockSpec((tile_g, select_k), lambda g: (g, 0)),
            pl.BlockSpec((tile_g, select_k), lambda g: (g, 0)),
            pl.BlockSpec((tile_g, 1), lambda g: (g, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((G, R), jnp.float32),
            jax.ShapeDtypeStruct((G, select_k), jnp.int32),
            jax.ShapeDtypeStruct((G, select_k), jnp.float32),
            jax.ShapeDtypeStruct((G, 1), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(fringe, prev, bias.reshape(G, R), nbrs.reshape(G, R, L))
    if with_remaining:
        return scores.reshape(B), idx, val, rem[:, 0]
    return scores.reshape(B), idx, val
