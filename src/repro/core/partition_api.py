"""Unified partitioning API — the framework's entry point.

``partition(hg, k, method=...)`` returns an int32 assignment; every
distributed component (GNN halo sharding, embedding-table placement) takes
an assignment produced here, so partitioners are interchangeable.

Engine selection in one line each (see DESIGN.md for the full ladder):
``hype`` is the paper-faithful reference, ``hype_batched`` the
throughput default, ``hype_superstep`` the device-resident large-k
engine, ``hype_device`` the fully device-resident while_loop engine,
``hype_sharded`` the multi-device mesh engine,
``hype_multilevel`` the quality-first multilevel composition, and the
remaining methods are the paper's baselines. The batched-family
engines take a ``refine_passes`` knob — the k-way refinement post-pass
of DESIGN.md §4e. ``describe_methods()`` returns the one-liners
programmatically.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib
import time
from typing import Dict, Optional, Tuple

import numpy as np

from .hypergraph import Hypergraph
from .hype import HypeParams, hype_partition
from . import resilience
from .resilience import UnrecoverableFault
from .minmax import hashing_partition, minmax_partition, random_partition
from .shp import shp_partition
from .multilevel import hype_multilevel_partition, multilevel_partition
from . import metrics

# The fast-engine family lives in ``repro.engines`` (one module per
# engine); ``core`` never imports it at module level (layering,
# tools/check_layering.py) — dispatch resolves the modules lazily.
_FAST_ENGINES: Dict[str, Tuple[str, str, str]] = {
    "hype_batched": ("repro.engines.batched", "BatchedParams",
                     "hype_batched_partition"),
    "hype_superstep": ("repro.engines.superstep", "SuperstepParams",
                       "hype_superstep_partition"),
    "hype_device": ("repro.engines.device", "DeviceParams",
                    "hype_device_partition"),
    "hype_sharded": ("repro.engines.sharded", "ShardedParams",
                     "hype_sharded_partition"),
}


@functools.lru_cache(maxsize=None)
def _engine(method: str):
    """Resolve a fast engine's (ParamsClass, runner) pair lazily."""
    mod_name, cls_name, run_name = _FAST_ENGINES[method]
    mod = importlib.import_module(mod_name)
    return getattr(mod, cls_name), getattr(mod, run_name)


@functools.lru_cache(maxsize=None)
def _params_class(spec: Tuple[str, str]):
    """Load the params dataclass a METHOD_INFO ``params`` spec names."""
    mod_name, cls_name = spec
    return getattr(importlib.import_module(mod_name), cls_name)

# method -> one-line description, vertex-balance slack, notable knobs.
# The slack is the engine's documented guarantee on max(part size) -
# min(part size): the HYPE family and the random baseline are perfectly
# balanced (<= 1); the streaming/swap baselines run with their papers'
# slack-100 constraint; hashing and the recursive-bisection multilevel
# partitioner only promise proportional balance (a fraction of n/k),
# recorded here as callables of (n, k) so the registry test can enforce
# exactly what is documented. Engine-specific keyword knobs are
# SINGLE-SOURCED from each engine's params dataclass: a ``params`` entry
# names ``(module, class)`` and ``method_knobs()`` derives the knob
# tuple from its fields (minus ``seed`` and any ``knob_exclude`` names
# the method pins itself), so the registry cannot drift from the
# dataclass — the two-way drift test in tests/test_partition_registry.py
# enforces it. Methods without a params dataclass keep a hand-maintained
# ``knobs`` tuple checked against the callable's signature. ``presets``
# maps ``preset=fast|balanced|quality`` to the knob defaults
# ``partition()`` folds under explicit keywords.
_PRESETS_HOST = {"fast": {}, "balanced": {"refine_passes": 1},
                 "quality": {"refine_passes": 4}}
# the pipelined engines additionally pin the lock-step schedule at
# ``quality``: depth 1 is the canonical golden cadence, and with the
# refinement post-pass dominating runtime the overlap buys nothing
_PRESETS_PIPE = {"fast": {}, "balanced": {"refine_passes": 1},
                 "quality": {"refine_passes": 4, "pipeline_depth": 1}}
METHOD_INFO: Dict[str, dict] = {
    "hype": {
        "desc": "paper-faithful numpy HYPE: heap + per-vertex growth "
                "steps (fidelity reference, ablations)",
        "balance_slack": lambda n, k: 1,
        "params": ("repro.core.hype", "HypeParams"),
    },
    "hype_batched": {
        "desc": "batched-candidate HYPE on the Pallas hype_scores "
                "kernel (host tiles; bit-stable throughput default)",
        "balance_slack": lambda n, k: 1,
        "params": ("repro.engines.batched", "BatchedParams"),
        "presets": _PRESETS_HOST,
    },
    "hype_jax": {
        "desc": "sequential HYPE as one jitted lax.while_loop program "
                "on dense padded arrays (on-device validation, small n)",
        "balance_slack": lambda n, k: 1,
    },
    "hype_parallel": {
        "desc": "jitted parallel k-way growth (paper §VI future work; "
                "validation scale)",
        "balance_slack": lambda n, k: 1,
    },
    "hype_superstep": {
        "desc": "device-resident HYPE: fused score+select supersteps "
                "grow all k phases concurrently on a double-buffered "
                "pipeline (large-k choice; pipeline_depth=1 locks step)",
        "balance_slack": lambda n, k: 1,
        "params": ("repro.engines.superstep", "SuperstepParams"),
        "presets": _PRESETS_PIPE,
    },
    "hype_device": {
        "desc": "fully device-resident HYPE: the whole growth loop as "
                "one lax.while_loop megakernel with on-device pool "
                "maintenance; host syncs once per chunk (DESIGN.md §4i)",
        "balance_slack": lambda n, k: 1,
        "params": ("repro.engines.device", "DeviceParams"),
        "presets": _PRESETS_HOST,
    },
    "hype_sharded": {
        "desc": "mesh-sharded superstep HYPE: phase groups sharded over "
                "a JAX device mesh, one all_gather per pipelined "
                "superstep",
        "balance_slack": lambda n, k: 1,
        "params": ("repro.engines.sharded", "ShardedParams"),
        "presets": _PRESETS_PIPE,
    },
    "hype_stream": {
        "desc": "single-pass streaming HYPE: micro-batched arrivals "
                "scored against a partition sketch + fringe kernel "
                "with a FREIGHT-style balance penalty; apply_updates "
                "mutates assignments incrementally (DESIGN.md §4h)",
        # hard ceil(n/k) capacity cap, no final rebalance: the last
        # arrivals can leave up to a k-wide size gap
        "balance_slack": lambda n, k: k,
        "params": ("repro.core.hype_stream", "StreamParams"),
    },
    "hype_weighted": {
        "desc": "numpy HYPE with degree-weighted balancing (HypeParams"
                "(balance='weighted'))",
        "balance_slack": lambda n, k: n,    # balances weight, not counts
        "params": ("repro.core.hype", "HypeParams"),
        "knob_exclude": ("balance",),       # pinned to "weighted"
    },
    "minmax_nb": {
        "desc": "streaming MinMax, vertex-balanced variant (HYPE paper "
                "footnote 2: slack of up to 100 vertices)",
        "balance_slack": lambda n, k: 101,  # slack + the vertex placed
        "knobs": ("slack",),
    },
    "minmax_eb": {
        "desc": "streaming MinMax, hyperedge-balanced original "
                "(Alistarh et al., NIPS'15); vertex counts may skew",
        "balance_slack": lambda n, k: n,    # balances edges, not vertices
    },
    "shp": {
        "desc": "Social-Hash-style iterative balanced swaps from a "
                "random start (Kabiljo et al., VLDB'17)",
        "balance_slack": lambda n, k: 1,    # swaps preserve random init
        "knobs": ("iters", "swap_frac"),
    },
    "multilevel": {
        "desc": "coarsen + recursive bisection + FM refinement "
                "(group (I) baseline); ~5% bisection tolerance",
        "balance_slack": lambda n, k: max(1, int(0.35 * (n / k)) + k),
    },
    "hype_multilevel": {
        "desc": "direct k-way multilevel: coarsen + hype_superstep "
                "initial partition + kway_refine uncoarsening passes "
                "(DESIGN.md §4e)",
        "balance_slack": lambda n, k: 1,
        "knobs": ("refine_passes", "coarsest"),
    },
    "random": {
        "desc": "balanced random assignment (quality lower bound)",
        "balance_slack": lambda n, k: 1,
    },
    "hashing": {
        "desc": "deterministic multiplicative hashing (what production "
                "systems default to); only statistically balanced",
        "balance_slack": lambda n, k: n,
    },
}

METHODS = tuple(METHOD_INFO)


def describe_methods() -> Dict[str, str]:
    """One-line description per registered method, keyed like ``METHODS``.

    The strings are the engine table of DESIGN.md in programmatic form —
    surfaces (CLIs, dashboards, docs generators) render them instead of
    hard-coding an engine list that drifts from the registry.
    """
    return {name: info["desc"] for name, info in METHOD_INFO.items()}


def method_knobs(method: str) -> tuple:
    """Engine-specific keyword knobs ``partition()`` forwards.

    Methods with a ``params`` dataclass spec derive the tuple from the
    dataclass fields (minus ``seed``, which ``partition()`` owns, and
    any ``knob_exclude`` names the method pins itself), so the registry
    cannot drift from the engine. Methods without one return their
    hand-maintained ``knobs`` tuple; empty for methods whose only knob
    is ``seed``. Either way the registry drift test verifies every
    listed knob against the engine's signature, so this tuple is safe
    to render in docs and CLIs.
    """
    info = METHOD_INFO[method]
    spec = info.get("params")
    if spec is None:
        return tuple(info.get("knobs", ()))
    cls = _params_class(spec)
    hidden = {"seed"} | set(info.get("knob_exclude", ()))
    return tuple(f.name for f in dataclasses.fields(cls)
                 if f.name not in hidden)


def method_presets(method: str) -> Dict[str, dict]:
    """The ``preset`` vocabulary ``partition()`` accepts for ``method``.

    Maps preset name -> the knob defaults it folds in (explicit keywords
    still win). Empty for methods without presets; ``"fast"`` is always
    the empty dict, i.e. bit-identical to the engine's own defaults.
    """
    return {name: dict(knobs) for name, knobs
            in METHOD_INFO[method].get("presets", {}).items()}


def balance_slack(method: str, n: int, k: int) -> int:
    """Documented worst-case ``max - min`` partition-size gap.

    For the perfectly balancing engines this is 1; streaming baselines
    return their slack constant; hashing/multilevel return proportional
    bounds. Used by the registry drift test to enforce exactly what each
    engine documents.
    """
    return int(METHOD_INFO[method]["balance_slack"](n, k))


# Method-independent knobs ``partition()`` itself consumes (never
# forwarded to an engine), name -> default. Registered so the knob
# drift test can enforce the signature defaults the same way engine
# knobs are enforced against their params dataclasses.
#
# ``auto_validate_max_n``: above this vertex count ``validate="auto"``
# skips the O(pins) invariant sweep — it starts to rival the cheap
# engines' own runtime. Huge-graph runs opt back in with
# ``validate=True`` or a larger threshold.
PARTITION_KNOBS: Dict[str, object] = {
    "auto_validate_max_n": 1_000_000,
}


def _resolve_validate(hg: Hypergraph, validate,
                      auto_validate_max_n: int) -> bool:
    if validate == "auto":
        return hg.n < int(auto_validate_max_n)
    if not isinstance(validate, bool):
        raise ValueError(
            f"validate must be 'auto' or a bool, got {validate!r}")
    return validate


def _resolve_preset(method: str, preset: Optional[str],
                    kw: dict) -> dict:
    """Fold ``preset`` defaults under the explicit knobs in ``kw``."""
    if preset is None:
        return kw
    presets = METHOD_INFO.get(method, {}).get("presets")
    if not presets:
        raise ValueError(
            f"method {method!r} does not support presets")
    if preset not in presets:
        raise ValueError(
            f"unknown preset {preset!r} for method {method!r}; "
            f"choose from {tuple(presets)}")
    return {**presets[preset], **kw}


def partition(hg: Hypergraph, k: int, method: str = "hype", *,
              seed: int = 0, preset: Optional[str] = None,
              validate="auto",
              auto_validate_max_n: int = 1_000_000, **kw) -> np.ndarray:
    """Partition ``hg`` into ``k`` parts; the single entry point.

    Parameters
    ----------
    hg : Hypergraph
        The hypergraph to partition (see ``Hypergraph.from_pins`` /
        ``from_edge_lists`` for construction).
    k : int
        Number of partitions (>= 1).
    method : str
        One of ``METHODS``; see ``describe_methods()`` for one-line
        summaries. Engine choice rule of thumb: ``hype`` for fidelity,
        ``hype_batched`` (default engine of the HYPE family) for host
        throughput, ``hype_superstep`` for large k on one accelerator,
        ``hype_sharded`` for a multi-device mesh.
    seed : int
        Seeds every stochastic engine; equal seeds give identical
        assignments for the same method and knobs.
    preset : str, optional
        Named knob bundle for the fast engines (``method_presets``):
        ``"fast"`` keeps the engine's own defaults (bit-identical to
        passing no preset), ``"balanced"`` adds one refinement pass,
        ``"quality"`` runs four refinement passes (the pipelined
        engines also pin ``pipeline_depth=1``). Explicit knobs in
        ``**kw`` override the preset. Raises ``ValueError`` for an
        unknown preset or a method without presets.
    validate : "auto" | bool
        Run ``hg.validate()`` before dispatching so CSR corruption
        surfaces as a clear ``ValueError`` here rather than an opaque
        kernel failure after the device image upload. ``"auto"`` (the
        default) validates graphs below ``auto_validate_max_n``
        vertices and skips larger ones; pass an explicit bool to force
        either way.
    auto_validate_max_n : int
        The ``"auto"`` cutoff (default 1e6, see ``PARTITION_KNOBS``).
        Raise it to keep validating huge graphs, or lower it to skip
        validation sooner; ignored when ``validate`` is a bool.
    **kw
        Engine-specific knobs, forwarded to the engine's params
        (e.g. ``t=16`` for the batched engines, ``devices=4`` for
        ``hype_sharded``, ``iters=8`` for ``shp``).

    Returns
    -------
    np.ndarray
        Complete int32 assignment of shape ``(hg.n,)`` with values in
        ``[0, k)``. Balance is engine-specific (``balance_slack``): the
        HYPE family guarantees ``max - min <= 1`` vertex counts.
    """
    if _resolve_validate(hg, validate, auto_validate_max_n):
        hg.validate()
    kw = _resolve_preset(method, preset, kw)
    if method == "hype":
        return hype_partition(hg, k, HypeParams(seed=seed, **kw))
    if method in _FAST_ENGINES:
        params_cls, runner = _engine(method)
        return runner(hg, k, params_cls(seed=seed, **kw))
    if method == "hype_jax":
        from .hype_jax import hype_jax_partition
        return hype_jax_partition(hg, k, seed=seed, **kw)
    if method == "hype_parallel":
        from .hype_jax import hype_parallel_partition
        return hype_parallel_partition(hg, k, seed=seed, **kw)
    if method == "hype_stream":
        from .hype_stream import StreamParams, hype_stream_partition
        return hype_stream_partition(hg, k, StreamParams(seed=seed, **kw))
    if method == "hype_weighted":
        return hype_partition(hg, k, HypeParams(seed=seed, balance="weighted", **kw))
    if method == "minmax_nb":
        return minmax_partition(hg, k, mode="nb", seed=seed, **kw)
    if method == "minmax_eb":
        return minmax_partition(hg, k, mode="eb", seed=seed, **kw)
    if method == "shp":
        return shp_partition(hg, k, seed=seed, **kw)
    if method == "multilevel":
        return multilevel_partition(hg, k, seed=seed, **kw)
    if method == "hype_multilevel":
        return hype_multilevel_partition(hg, k, seed=seed, **kw)
    if method == "random":
        return random_partition(hg, k, seed=seed)
    if method == "hashing":
        return hashing_partition(hg, k)
    raise ValueError(f"unknown method {method!r}; choose from {METHODS}")


def partition_and_report(hg: Hypergraph, k: int, method: str = "hype", *,
                         seed: int = 0, preset: Optional[str] = None,
                         validate="auto",
                         **kw) -> Tuple[dict, np.ndarray]:
    """Partition and measure: returns ``(report, assignment)``.

    Parameters are exactly ``partition``'s. ``report`` is
    ``metrics.all_metrics`` (``k_minus_1``, ``hyperedge_cut``,
    ``imbalance``, ``replication_factor``, ...) plus ``method``/``k``/
    ``runtime_s``; ``assignment`` is the int32 array ``partition``
    produced (the pair, not just the dict — callers feed the assignment
    to placement code and the report to dashboards).
    """
    t0 = time.perf_counter()
    assignment = partition(hg, k, method, seed=seed, preset=preset,
                           validate=validate, **kw)
    dt = time.perf_counter() - t0
    rep = metrics.all_metrics(hg, assignment, k)
    rep.update(method=method, k=k, runtime_s=dt)
    return rep, assignment


# ----------------------------------------------------- degradation ladder

# Each engine's structured fallback when it raises UnrecoverableFault:
# shed one capability per rung (mesh -> single device -> host tiles ->
# pure numpy) rather than abandoning the run. The final ``hype`` rung
# has no device dependency at all, so the ladder always terminates.
_LADDER = {
    "hype_device": "hype_superstep",
    "hype_sharded": "hype_superstep",
    "hype_superstep": "hype_batched",
    "hype_batched": "hype",
}


def _run_rung(hg: Hypergraph, k: int, method: str, seed: int,
              resume, snapshot_dir, snapshot_every: int, keep_last: int,
              plan, kw: dict):
    """One ladder rung: run ``method`` and return ``(assignment, stats)``.

    ``kw`` is filtered down to the rung's registered knobs so that, say,
    ``devices=4`` survives the hop from ``hype_sharded`` to
    ``hype_superstep`` without a TypeError.
    """
    knobs = set(method_knobs(method))
    sub = {key: val for key, val in kw.items() if key in knobs}
    if method == "hype":
        warm = None
        if resume:
            ckpt = resilience.load_latest(resume)
            if ckpt is not None:
                resilience.check_checkpoint(ckpt, hg, k)
                warm = resilience.warm_assignment(ckpt)
        return hype_partition(hg, k, HypeParams(seed=seed, **sub),
                              return_stats=True, warm_start=warm)
    params_cls, runner = _engine(method)
    sub.update(snapshot_every=snapshot_every, snapshot_dir=snapshot_dir,
               keep_last=keep_last, resume=resume, fault_plan=plan)
    return runner(hg, k, params_cls(seed=seed, **sub), return_stats=True)


def partition_resilient(hg: Hypergraph, k: int,
                        method: str = "hype_sharded", *,
                        seed: int = 0,
                        snapshot_dir: Optional[str] = None,
                        snapshot_every: int = 0,
                        keep_last: int = 3,
                        resume: Optional[str] = None,
                        fault_plan=None,
                        validate="auto",
                        auto_validate_max_n: int = 1_000_000,
                        **kw) -> Tuple[np.ndarray, dict]:
    """Partition with retries, snapshots and the degradation ladder.

    Runs ``method``; if the engine raises
    :class:`~repro.core.resilience.UnrecoverableFault` (fatal injected
    fault, exhausted retry budget, failed device image upload, device
    failure after buffer donation), falls back one rung at a time —
    ``hype_sharded -> hype_superstep -> hype_batched -> hype`` — resuming
    each fallback from the last snapshot in ``snapshot_dir`` (cross-engine
    restores warm-start from the snapshotted assignment; the pure-numpy
    ``hype`` rung adopts it via ``warm_start=``). Transient faults are
    retried *inside* each engine (``max_retries``/``retry_backoff_s``
    knobs) and never reach the ladder.

    ``snapshot_every > 0`` requires ``snapshot_dir``. ``fault_plan``
    (a ``FaultPlan``, a spec string, or None for ``REPRO_FAULT_PLAN``)
    is resolved once and shared across rungs so a consumed fault does
    not re-fire after a fallback. Engine knobs in ``**kw`` are filtered
    per rung, so e.g. ``devices=4`` is dropped when the ladder leaves
    ``hype_sharded``.

    Returns ``(assignment, report)`` where ``report`` carries the
    quality metrics plus ``method`` (the rung that finished),
    ``requested_method``, ``degraded_from`` (one ``{"method", "error"}``
    record per abandoned rung), ``fallbacks`` and the finishing engine's
    ``stats`` dataclass.
    """
    if method not in ("hype", *_LADDER):
        raise ValueError(
            f"unknown resilient method {method!r}; choose from "
            f"{('hype', *_LADDER)}")
    if _resolve_validate(hg, validate, auto_validate_max_n):
        hg.validate()
    plan = resilience.resolve_fault_plan(fault_plan)
    t0 = time.perf_counter()
    attempted = []
    cur = method
    while True:
        try:
            assignment, stats = _run_rung(
                hg, k, cur, seed, resume, snapshot_dir, snapshot_every,
                keep_last, plan, kw)
            break
        except UnrecoverableFault as e:
            nxt = _LADDER.get(cur)
            if nxt is None:
                raise
            attempted.append({"method": cur, "error": str(e)})
            cur = nxt
            # Fallback rungs resume from whatever the failed rung last
            # published; with no snapshot_dir they cold-start instead.
            resume = snapshot_dir
    dt = time.perf_counter() - t0
    if hasattr(stats, "fallbacks"):     # plus the engine's own hand-offs
        stats.fallbacks += len(attempted)
    rep = metrics.all_metrics(hg, assignment, k)
    rep.update(method=cur, requested_method=method, k=k, runtime_s=dt,
               degraded_from=attempted, fallbacks=len(attempted),
               stats=stats)
    return assignment, rep
