"""Faults planted in the scoring kernels, for the checks' upper readings.

    with faults.planted("zero_kernel"):
        partition(...)

Each fault replaces the jitted ``hype_score`` entries in
``repro.kernels.hype_score.ops`` for the length of the ``with`` block
(the engines look them up there when they build or call their
programs) and clears the superstep engine's cached programs on the way
in and out, so no program built with or without the fault outlives it:

* ``zero_kernel``: the kernels see an empty neighbour tile, so every
  score is 0 (the scoring left out);
* ``bf16_kernel``: the kernels' scores rounded to bfloat16 (8
  significant bits), the lower-precision control of integer counts;
* ``reversed_kernel``: the candidates ranked the other way round, the
  best-scored last (4096 minus the score; the fused select by minus the
  neighbour count).

Used by ``calibrate.py --fault`` on the chip and by ``tests/``.
"""
from __future__ import annotations

import contextlib

FAULTS = ("zero_kernel", "bf16_kernel", "reversed_kernel")

_PROGRAMS = ("_pipeline_program", "_chunked_program", "_spill_program",
             "_paged_program")


def _clear_programs() -> None:
    from repro.engines import superstep
    for name in _PROGRAMS:
        getattr(superstep, name).cache_clear()


@contextlib.contextmanager
def planted(fault: str):
    if fault not in FAULTS:
        raise ValueError(f"fault must be one of {FAULTS}")
    import jax.numpy as jnp
    from repro.kernels.hype_score import ops

    scores0, select0 = ops.hype_scores, ops.hype_score_select

    def bf16(x):
        return x.astype(jnp.bfloat16).astype(x.dtype)

    if fault == "zero_kernel":
        def scores(nbrs, fringe, **kw):
            return scores0(jnp.full_like(nbrs, -1), fringe, **kw)

        def select(nbrs, *args, **kw):
            return select0(jnp.full_like(nbrs, -1), *args, **kw)
    elif fault == "bf16_kernel":
        def scores(nbrs, fringe, **kw):
            return bf16(scores0(nbrs, fringe, **kw))

        def select(nbrs, *args, **kw):
            out = select0(nbrs, *args, **kw)
            return (bf16(out[0]),) + tuple(out[1:])
    else:
        def scores(nbrs, fringe, **kw):
            return 4096 - scores0(nbrs, fringe, **kw)

        def select(nbrs, fringe, bias, prev, **kw):
            count = (nbrs >= 0).sum(-1).astype(bias.dtype)
            flipped = jnp.where(jnp.isfinite(bias), -bias - 100.0 * count,
                                bias)
            return select0(nbrs, fringe, flipped, prev, **kw)

    _clear_programs()
    ops.hype_scores, ops.hype_score_select = scores, select
    try:
        yield
    finally:
        ops.hype_scores, ops.hype_score_select = scores0, select0
        _clear_programs()
