"""Runtime switches shared by all kernels and engines."""
import os
import pathlib

_TRUTHY = ("1", "true", "yes", "on")
_FALSY = ("0", "false", "no", "off")

# <checkout>/.jax_cache: fixed, because the directory is part of the
# cache key — a path that moved between runs would never hit
DEFAULT_CACHE_DIR = str(
    pathlib.Path(__file__).resolve().parents[3] / ".jax_cache")


def pallas_interpret() -> bool:
    """Single source of truth for Pallas interpret-mode selection.

    Default: interpret everywhere except on a real TPU backend (the
    kernels compile only there; interpret mode is the correct-but-slow
    path on CPU/GPU). The ``REPRO_PALLAS_INTERPRET`` env var overrides
    either way — ``1/true/yes/on`` forces interpret mode (e.g. to debug
    a kernel on TPU), ``0/false/no/off`` forces the compiled path (e.g.
    to exercise GPU/compiled-CPU lowering in CI) — so benchmarks and CI
    can pin the mode without touching call sites. Read per call, not
    cached: tests flip the env var at runtime. Callers must do the
    same — re-evaluate at every kernel invocation rather than stashing
    the value in long-lived engine state (the superstep engines expose
    it as a property for exactly this reason).
    """
    env = os.environ.get("REPRO_PALLAS_INTERPRET", "").strip().lower()
    if env in _TRUTHY:
        return True
    if env in _FALSY:
        return False
    import jax
    return jax.default_backend() != "tpu"


def configure_compile_cache() -> str:
    """Place JAX's persistent compilation cache; returns the directory.

    ``JAX_COMPILATION_CACHE_DIR`` wins when set; otherwise the cache
    lives at the fixed ``DEFAULT_CACHE_DIR`` inside the checkout. The
    engines retrace identical while_loop/kernel programs on every cold
    start, so the min compile-time / entry-size thresholds are zeroed
    to let the many small Pallas kernels qualify, not just the
    megakernel. ``JAX_ENABLE_COMPILATION_CACHE=false`` still turns the
    cache off (the test suite does). Called at every engine entry;
    repeated calls set the same values.
    """
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path
