"""Fused neighbor-gather + mean + projection kernel (Pallas TPU).

The sampled-GNN hot path (GraphSAGE minibatch regime): for each seed, mean
its K sampled neighbors' features and project. Same scalar-prefetch DMA
pattern as embedding_bag — the neighbor index matrix is prefetched so
BlockSpec index maps can stream exactly the needed feature rows
HBM->VMEM — then the per-seed mean is fed to the MXU against a
VMEM-resident (D, F) weight tile, fusing gather + reduce + GEMM in one
kernel (the FusedMM insight adapted to TPU: no materialized (B, K, D)
gather buffer in HBM).

Grid: (B, K) with the K dimension sequential; W stays resident.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _agg_kernel(nbrs_ref, row_ref, w_ref, out_ref, acc_ref, cnt_ref):
    b = pl.program_id(0)
    j = pl.program_id(1)
    K = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    valid = nbrs_ref[b, j] >= 0

    @pl.when(valid)
    def _acc():
        acc_ref[...] += row_ref[...].astype(jnp.float32)
        cnt_ref[...] += 1

    @pl.when(j == K - 1)
    def _fin():
        denom = jnp.maximum(cnt_ref[0, 0], 1).astype(jnp.float32)
        mean = acc_ref[...] / denom                      # (1, D)
        out_ref[...] = (mean @ w_ref[...].astype(jnp.float32)
                        ).astype(out_ref.dtype)


def neighbor_agg_kernel(x, nbrs, w, *, interpret: bool = False):
    """x: (N, D); nbrs: (B, K); w: (D, F) -> (B, F)."""
    N, D = x.shape
    B, K = nbrs.shape
    F = w.shape[1]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, K),
        in_specs=[
            pl.BlockSpec((1, D),
                         lambda b, j, nbrs_ref: (
                             jnp.maximum(nbrs_ref[b, j], 0), 0)),
            pl.BlockSpec((D, F), lambda b, j, nbrs_ref: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, F), lambda b, j, nbrs_ref: (b, 0)),
        scratch_shapes=[
            pltpu.VMEM((1, D), jnp.float32),
            pltpu.VMEM((1, 1), jnp.int32),
        ],
    )
    return pl.pallas_call(
        _agg_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, F), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(nbrs, x, w)
