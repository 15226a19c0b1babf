"""EmbeddingBag gather-reduce kernel (Pallas TPU, scalar-prefetch DMA).

The recsys hot path: huge table in HBM, ragged multi-hot ids per example.
TPU adaptation: ids are *scalar-prefetched* so the BlockSpec index_map can
schedule the HBM->VMEM DMA of exactly the rows the bag needs (the Pallas
embedding pattern) — no host gather, no one-hot matmul. The grid walks
(example, bag-slot); a VMEM fp32 accumulator carries the partial sum
across the bag dimension and the mean lands in the output row on the last
slot. Padded slots (-1) are skipped via ``pl.when`` but still DMA row 0 —
the index map must return a valid row; the accumulate is masked.

This kernel is the fast path behind models/recsys.embedding_bag.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _bag_kernel(ids_ref, row_ref, out_ref, acc_ref, cnt_ref, *,
                combine: str):
    b = pl.program_id(0)
    j = pl.program_id(1)
    bag = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    valid = ids_ref[b, j] >= 0

    @pl.when(valid)
    def _acc():
        acc_ref[...] += row_ref[...].astype(jnp.float32)
        cnt_ref[...] += 1

    @pl.when(j == bag - 1)
    def _fin():
        total = acc_ref[...]
        if combine == "mean":
            denom = jnp.maximum(cnt_ref[0, 0], 1).astype(jnp.float32)
            total = total / denom
        out_ref[...] = total.astype(out_ref.dtype)


def embedding_bag_kernel(table, ids, *, combine: str = "mean",
                         interpret: bool = False):
    """table: (V, D); ids: (B, bag) -> (B, D)."""
    V, D = table.shape
    B, bag = ids.shape

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, bag),
        in_specs=[
            pl.BlockSpec((1, D),
                         lambda b, j, ids_ref: (
                             jnp.maximum(ids_ref[b, j], 0), 0)),
        ],
        out_specs=pl.BlockSpec((1, D), lambda b, j, ids_ref: (b, 0)),
        scratch_shapes=[
            pltpu.VMEM((1, D), jnp.float32),
            pltpu.VMEM((1, 1), jnp.int32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_bag_kernel, combine=combine),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, D), table.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(ids, table)
