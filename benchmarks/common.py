"""Shared benchmark utilities: timing, CSV output, dataset cache."""
from __future__ import annotations

import functools
import os
import time

import numpy as np

from repro.kernels._compat import configure_compile_cache

QUICK = os.environ.get("BENCH_FULL", "0") != "1"

# Place JAX's persistent compilation cache (JAX_COMPILATION_CACHE_DIR,
# else <checkout>/.jax_cache) before any benchmark traces a program:
# repeat runs then skip the XLA compile entirely, which keeps
# quick-mode timings about the engines rather than about tracing.
configure_compile_cache()

# dataset scales: quick mode keeps the full suite ~ minutes on CPU;
# BENCH_FULL=1 runs the paper-scale graphs (github full scale).
GITHUB_SCALE = 1.0 if not QUICK else 0.25
STACKOVERFLOW_SCALE = 1.0 if not QUICK else 0.06
REDDIT_SCALE = 0.02 if not QUICK else 0.01

_ROWS = []


def emit(name: str, us_per_call: float, derived: str = ""):
    row = f"{name},{us_per_call:.1f},{derived}"
    _ROWS.append(row)
    print(row, flush=True)


def timed(fn, *args, repeats: int = 1, **kw):
    t0 = time.perf_counter()
    out = None
    for _ in range(repeats):
        out = fn(*args, **kw)
    dt = (time.perf_counter() - t0) / repeats
    return out, dt


@functools.lru_cache(maxsize=8)
def dataset(name: str, seed: int = 0):
    from repro.data.synthetic import (github_like, reddit_like,
                                      stackoverflow_like)
    if name == "github":
        return github_like(scale=GITHUB_SCALE, seed=seed)
    if name == "stackoverflow":
        return stackoverflow_like(scale=STACKOVERFLOW_SCALE, seed=seed)
    if name == "reddit":
        return reddit_like(scale=REDDIT_SCALE, seed=seed)
    raise ValueError(name)


def all_rows():
    return list(_ROWS)
