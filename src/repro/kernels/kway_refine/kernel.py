"""K-way move-gain kernel for the refinement subsystem (Pallas TPU).

Post-pass refinement (DESIGN.md §4e) screens every boundary vertex for
a profitable partition move. The screening score is the connectivity
gain over the vertex's *neighborhood* (the same unique-neighbor lists
the ``hype_score`` kernel tiles): for a vertex v in partition p,

    gain[v, q] = #(N(v) in q) - #(N(v) in p)

— how many more neighbors v would sit with after a move p -> q. Like
the scoring kernel, the tile is a dense (TB, L) block in VMEM, but the
rows hold the neighbors' *partition ids* (gathered on device against
the live assignment, -1 padded) instead of vertex ids, and the compare
loop runs over the k static partition ids instead of the s fringe
slots:

    cnt[b, q] = #(parts[b, :] == q)          one (TB, L) compare per q
    gain[b, q] = cnt[b, q] - cnt[b, own[b]]

No gather, no histogram scatter — k broadcast-compares + reductions per
tile, the same VPU shape as ``_score_kernel``. Pad rows (own = -1) and
pad lanes (parts = -1) never match a real partition id, so their gains
are all zero and the driver's positive-gain filter drops them for free.

The exact k-1 delta of a move needs per-hyperedge pin counts, which the
neighborhood image cannot provide; the driver verifies the screened
winners' exact gains on host before admitting any move (DESIGN.md §4e),
so this kernel only has to *rank* candidates, cheaply, for all of them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gain_kernel(own_ref, parts_ref, out_ref, *, k: int):
    parts = parts_ref[...]                    # (TB, L) neighbor partitions
    own = own_ref[...]                        # (TB, 1) the row's own part
    # the -1 pad lanes of a -1 pad ROW would match own == -1; mask them
    # so pad rows count zero everywhere (real q ids never match a pad)
    cnt_own = jnp.logical_and(parts == own, parts >= 0).sum(axis=1)
    cols = []
    for q in range(k):                        # k is a small static constant
        cnt_q = (parts == q).sum(axis=1)
        cols.append(cnt_q - cnt_own)
    out_ref[...] = jnp.stack(cols, axis=1).astype(jnp.float32)


def kway_gains_kernel(parts, own, *, k: int, tile_b: int = 256,
                      interpret: bool = False):
    """parts: (B, L) int32 (-1 pad); own: (B,) int32 (-1 = pad row).

    Returns (B, k) float32 move gains; column ``own[b]`` is 0 by
    construction.
    """
    B, L = parts.shape
    tile_b = min(tile_b, B)
    assert B % tile_b == 0, "pad B to a tile multiple"
    out = pl.pallas_call(
        functools.partial(_gain_kernel, k=k),
        grid=(B // tile_b,),
        in_specs=[
            pl.BlockSpec((tile_b, 1), lambda i: (i, 0)),
            pl.BlockSpec((tile_b, L), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((tile_b, k), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, k), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(own[:, None], parts)
    return out
