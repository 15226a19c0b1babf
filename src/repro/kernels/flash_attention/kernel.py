"""Flash attention forward kernel (Pallas TPU).

Block-wise online softmax (Dao et al.) adapted to the TPU memory
hierarchy: Q/K/V tiles are staged HBM->VMEM by BlockSpec; the MXU consumes
(block_q x d) @ (d x block_k) tiles; running max/denominator/accumulator
live in VMEM scratch that persists across the innermost ("arbitrary")
grid dimension. Causal and sliding-window masks are applied in-kernel;
fully-masked K blocks are skipped with ``pl.when`` (this is what makes
SWA sub-quadratic on long contexts).

Grid: (B, Hq, nq, nk) — nk is the sequential dimension.
GQA: the K/V index map folds q-head -> kv-head (h // group).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, block_q: int, block_k: int, seq_len: int,
                  causal: bool, window: Optional[int]):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = iq * block_q
    k_start = ik * block_k

    # block-level skip: with causal masking K blocks strictly above the
    # diagonal contribute nothing; with a window, blocks entirely below
    # (q_start - window) are dead too.
    live = jnp.full((), True)
    if causal:
        live = jnp.logical_and(live, k_start <= q_start + block_q - 1)
    if window is not None:
        live = jnp.logical_and(live,
                               k_start + block_k - 1 > q_start - window)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # (block_q, d)
        k = k_ref[0, 0].astype(jnp.float32)          # (block_k, d)
        v = v_ref[0, 0].astype(jnp.float32)          # (block_k, d)
        s = (q @ k.T) * scale                        # (block_q, block_k)

        qi = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                (block_q, block_k), 0)
        kj = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                (block_q, block_k), 1)
        mask = kj < seq_len
        if causal:
            mask &= kj <= qi
        if window is not None:
            mask &= kj > qi - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                          # (block_q, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + p @ v
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_attention_fwd(q, k, v, *, causal: bool = True,
                        window: Optional[int] = None, block_q: int = 128,
                        block_k: int = 128, interpret: bool = False):
    """q: (B, S, Hq, D) -> (B, S, Hq, D); k/v: (B, S, Hkv, D)."""
    B, S, Hq, D = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    assert S % block_q == 0 and S % block_k == 0, "pad seq to block multiple"
    nq, nk = S // block_q, S // block_k
    scale = 1.0 / (D ** 0.5)

    # layout: (B, H, S, D) blocks
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(
        _flash_kernel, scale=scale, block_q=block_q, block_k=block_k,
        seq_len=S, causal=causal, window=window)

    out = pl.pallas_call(
        kernel,
        grid=(B, Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, iq, ik: (b, h // G, ik, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, iq, ik: (b, h // G, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max
            pltpu.VMEM((block_q, 1), jnp.float32),   # running denom
            pltpu.VMEM((block_q, D), jnp.float32),   # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)
