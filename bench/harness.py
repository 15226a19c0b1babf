"""The benchmark's run: one cell, one seed, one measured window.

Everything a cell needs is found by name, so a later change adds a
configuration, a traffic mix, a cell or a per-layer metric as new files:

* ``BENCHMARK.json`` names the cell's configuration and traffic, and
  lists the per-layer metrics with the cells each is read in;
* ``bench/configs/<config>.json``: the graph and k (``graphs.py``);
* ``bench/traffic/<traffic>.json``: the engine ``partition()`` is called
  with, its keyword options, its documented balance slack and, where
  the engine keeps its scores on the host, where to sample them;
* ``bench/workloads/<cell>.json``: the cell's correctness limits;
* ``bench/metrics/<metric>.py``: a reader ``read(run) -> float | None``.

A run generates the graph from the seed, warms up with one partition of
that graph, then calls ``partition()`` back to back, each call on a new
``Hypergraph`` object (no memoised adjacency or device image carries
over), until ``seconds`` have passed. Afterwards it runs the sequential
HYPE reference on the same graph and checks every call of the window,
and a sample of the scores the engine stored along the way.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import multiprocessing
import os
import sys
import time
from pathlib import Path

import numpy as np

from graphs import config_pins
from reference import Csr, external_scores, hype_reference, km1, part_sizes
import trace_reduce as bench_trace

# environment switches of the program that would move it off the
# library's defaults (forced interpret mode, injected faults, a smaller
# memory budget); a run measures the defaults
PROGRAM_SWITCHES = ("REPRO_PALLAS_INTERPRET", "REPRO_FAULT_PLAN",
                    "REPRO_DEVICE_MEM_BUDGET")


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Cell:
    name: str
    root: Path
    entry: dict          # the cell's entry of BENCHMARK.json
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list     # metric entries of BENCHMARK.json for this cell
    per_layer: list


def _for_cell(metrics: list, cell: str) -> list:
    return [m for m in metrics if cell in m.get("workloads", [cell])]


def load_cell(root: Path, name: str) -> Cell:
    """Read the cell's entry and every file it names."""
    bm = json.loads((root / "BENCHMARK.json").read_text())
    entries = [w for w in bm["workloads"] if w["name"] == name]
    if not entries:
        raise SystemExit(f"unknown workload {name!r}")
    entry = entries[0]
    bench = root / "bench"

    def data(kind: str, key: str) -> dict:
        return json.loads((bench / kind / f"{key}.json").read_text())

    return Cell(name=name, root=root, entry=entry,
                config=data("configs", entry["config"]),
                traffic=data("traffic", entry["traffic"]),
                limits=data("workloads", name),
                end_to_end=_for_cell(bm["end_to_end"], name),
                per_layer=_for_cell(bm["per_layer"], name))


def load_reader(root: Path, metric: str):
    """The ``read`` function of ``bench/metrics/<metric>.py``."""
    path = root / "bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def device_record(chips: int) -> dict:
    """Platform, kind and count as JAX reports them; fails off a TPU."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX runs on {devs[0].platform!r}, not a TPU")
    if len(devs) < chips:
        raise NoChip(f"{len(devs)} chips, the cell asks for {chips}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest local device (0 if untracked)."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks, default=0))


class StatsTap:
    """Captures the engine's stats from the entry ``partition()`` calls.

    ``partition()`` returns only the assignment. The tap replaces the
    engine's runner in its module with one that asks for the stats too
    and keeps them, so the timed call is ``partition()`` itself. A call
    that does not reach the runner leaves no stats, which the checks
    count.
    """

    def __init__(self, module: str, runner: str):
        self.mod = importlib.import_module(module)
        self.name = runner
        self.orig = getattr(self.mod, runner)
        self.taken: list = []

    def __enter__(self):
        orig, taken = self.orig, self.taken

        def runner(hg, k, params=None, return_stats=False):
            assignment, stats = orig(hg, k, params, return_stats=True)
            taken.append(stats)
            return (assignment, stats) if return_stats else assignment

        setattr(self.mod, self.name, runner)
        # partition() resolves its runners once and keeps them
        from repro.core import partition_api
        partition_api._engine.cache_clear()
        return self

    def __exit__(self, *exc):
        setattr(self.mod, self.name, self.orig)
        from repro.core import partition_api
        partition_api._engine.cache_clear()

    def pop(self):
        return self.taken.pop() if self.taken else None


class CompileCounter:
    """Counts backend compilations inside its ``with`` block."""

    def __init__(self):
        self.count = 0

    def _on(self, event: str, duration: float, **kw) -> None:
        if "backend_compile" in event:
            self.count += 1

    def __enter__(self):
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        import jax.monitoring
        jax.monitoring.unregister_event_duration_listener(self._on)


class ScoreTap:
    """Samples the scores the engine stores, on the timed path itself.

    ``spec`` (the traffic's ``score_check``) names the engine method
    that scores a batch of candidates into the engine's score cache
    (``method``, as ``module:Class.method``) and the state attributes
    it reads (``cache``, ``assignment``, ``fringe_mask``). On about one
    call in ``every``, drawn from the run's seed, the tap copies the
    state the call starts from and, after it, the scores it stored for
    the candidates it had to score. Those are compared after the window
    (``score_mismatches``). Without a spec the tap does nothing.
    """

    def __init__(self, spec: dict | None, seed: int):
        self.spec = spec
        self.seed = seed
        self.taken: list = []

    def __enter__(self):
        if not self.spec:
            return self
        module, attr = self.spec["method"].split(":")
        cls_name, self.meth = attr.split(".")
        self.cls = getattr(importlib.import_module(module), cls_name)
        orig = self.orig = getattr(self.cls, self.meth)
        cache, assign, fringe = (self.spec[key] for key in
                                 ("cache", "assignment", "fringe_mask"))
        rng = np.random.default_rng(self.seed)
        p = 1.0 / float(self.spec["every"])
        gap = [int(rng.geometric(p))]
        taken = self.taken

        def scorer(st, cand, *args, **kw):
            gap[0] -= 1
            if gap[0] > 0:
                return orig(st, cand, *args, **kw)
            gap[0] = int(rng.geometric(p))
            cand = np.asarray(cand)
            miss = cand[getattr(st, cache)[cand] < 0.0]
            state = (getattr(st, assign).copy(), getattr(st, fringe).copy())
            out = orig(st, cand, *args, **kw)
            taken.append((miss, *state, getattr(st, cache)[miss].copy()))
            return out

        setattr(self.cls, self.meth, scorer)
        return self

    def __exit__(self, *exc):
        if self.spec:
            setattr(self.cls, self.meth, self.orig)

    def pop(self) -> list:
        out = self.taken[:]
        self.taken.clear()
        return out


def score_mismatches(samples: list, csr: Csr, spec: dict) -> tuple:
    """``(rows checked, rows whose stored score is wrong)``.

    A stored score is right where it equals the external-neighbour
    count ``reference.external_scores`` gives for the state the scoring
    call started from; for a hub with more than ``hub_width`` unassigned
    neighbours, which the engine may count over a truncated row, where
    it carries at least the documented ``hub_penalty``.
    """
    rows = bad = 0
    for miss, assignment, in_fringe, got in samples:
        want, wide = external_scores(csr, miss, assignment, in_fringe,
                                     int(spec["hub_width"]))
        wrong = np.where(wide, ~(got >= float(spec["hub_penalty"])),
                         got != want)
        rows += int(miss.size)
        bad += int(wrong.sum())
    return rows, bad


@dataclasses.dataclass
class Call:
    seconds: float
    n: int
    m: int
    assignment: np.ndarray | None
    stats: object | None
    error: str | None = None
    scores: list = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class RunData:
    """What a per-layer reader sees: the window's calls and the trace."""
    cell: str
    calls: list
    trace: dict | None


def timed_call(hg_arrays: dict, k: int, method: str, seed: int,
               options: dict, tap: StatsTap, scores: ScoreTap | None = None,
               annotate: bool = False) -> Call:
    """One ``partition()`` on a new ``Hypergraph`` built from the arrays."""
    import jax
    from repro.core.hypergraph import Hypergraph
    from repro.core.partition_api import partition

    hg = Hypergraph(**{key: (v.copy() if isinstance(v, np.ndarray) else v)
                       for key, v in hg_arrays.items()})
    err = None
    t0 = time.perf_counter()
    try:
        if annotate:
            with jax.profiler.TraceAnnotation(bench_trace.ANNOTATION):
                a = partition(hg, k, method, seed=seed, **options)
        else:
            a = partition(hg, k, method, seed=seed, **options)
        a = np.asarray(a)
    except Exception as exc:   # a failed call is counted, not fatal
        a, err = None, f"{type(exc).__name__}: {exc}"
    dt = time.perf_counter() - t0
    return Call(dt, hg.n, hg.m, a, tap.pop(), err,
                scores.pop() if scores is not None else [])


def check_calls(calls: list, csr: Csr, k: int, slack: int,
                ref_km1: int, score_spec: dict | None = None) -> dict:
    """The numbers compared, each the worst over the calls.

    With a ``score_spec`` also ``score_mismatch``, the sampled stored
    scores that are wrong, and ``score_unchecked``, 1 where the run
    sampled none at all (the engine no longer scores where the spec
    says).
    """
    worst = {"failed_calls": 0, "unassigned": 0, "balance_excess": 0,
             "stats_missing": 0, "fallbacks": 0, "retries": 0,
             "reduced_plan": 0, "km1_excess": 0.0}
    if score_spec:
        rows, bad = score_mismatches(
            [s for c in calls for s in c.scores], csr, score_spec)
        worst.update(score_mismatch=bad, score_unchecked=int(rows == 0))
    for c in calls:
        if c.assignment is None:
            worst["failed_calls"] += 1
            continue
        a = c.assignment
        bad = int(((a < 0) | (a >= k)).sum()) if a.shape == (csr.n,) \
            else csr.n
        worst["unassigned"] = max(worst["unassigned"], bad)
        if bad:
            continue
        sizes = part_sizes(a, k)
        worst["balance_excess"] = max(
            worst["balance_excess"], int(sizes.max() - sizes.min()) - slack)
        worst["km1_excess"] = max(worst["km1_excess"],
                                  km1(csr, a, k) / ref_km1 - 1.0)
        if c.stats is None:
            worst["stats_missing"] += 1
            continue
        worst["fallbacks"] = max(worst["fallbacks"], int(c.stats.fallbacks))
        worst["retries"] = max(worst["retries"], int(c.stats.retries))
        worst["reduced_plan"] = max(worst["reduced_plan"],
                                    int(c.stats.plan_rung))
    worst["balance_excess"] = max(worst["balance_excess"], 0)
    worst["reduced_plan"] = max(worst["reduced_plan"], 0)
    return worst


def _reference_km1(n: int, m: int, vertex_ids, edge_ids, k: int,
                   seed: int) -> tuple:
    """Sequential HYPE's k-1 and its seconds (runs in a worker)."""
    t0 = time.perf_counter()
    csr = Csr(n, m, vertex_ids, edge_ids)
    return km1(csr, hype_reference(csr, k, seed), k), \
        time.perf_counter() - t0


def judge(values: dict, limits: dict) -> tuple:
    """``(correct, checks)``: each number beside its limit."""
    checks = {name: {"value": v, "limit": limits.get(name, 0)}
              for name, v in values.items()}
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    return ok, checks


def end_to_end_values(calls: list, csr: Csr, k: int, setup_s: float):
    good = [c for c in calls if c.assignment is not None
            and c.assignment.shape == (csr.n,)
            and ((c.assignment >= 0) & (c.assignment < k)).all()]
    out = {"setup_s": setup_s}
    if good:
        out["vertices_per_s"] = (sum(c.n for c in good)
                                 / sum(c.seconds for c in good))
        out["km1_per_edge"] = (sum(km1(csr, c.assignment, k) for c in good)
                               / sum(c.m for c in good))
    return out


def run(cell: Cell, seed: int, seconds: float, traced: bool,
        t_process: float, require_tpu: bool = True) -> dict:
    """Set up, measure, check; returns the result line's object."""
    import jax

    device = (device_record(int(cell.entry["chips"])) if require_tpu
              else {"platform": jax.devices()[0].platform,
                    "kind": jax.devices()[0].device_kind,
                    "count": len(jax.devices())})
    from repro.core.hypergraph import Hypergraph

    g, k = cell.config["graph"], int(cell.config["k"])
    vertex_ids, edge_ids = config_pins(cell.config, seed)
    csr = Csr(g["n"], g["m"], vertex_ids, edge_ids)
    hg0 = Hypergraph.from_pins(g["n"], g["m"], vertex_ids, edge_ids)
    arrays = {f.name: getattr(hg0, f.name)
              for f in dataclasses.fields(Hypergraph)}
    del hg0
    tr = cell.traffic
    method, options = tr["method"], dict(tr.get("options", {}))
    module, runner = tr["engine_entry"].split(":")
    score_spec = tr.get("score_check")
    with StatsTap(module, runner) as tap, \
            ScoreTap(score_spec, seed) as scores:
        warm = timed_call(arrays, k, method, seed, options, tap, scores)
        setup_s = time.perf_counter() - t_process
        calls, trace_dir = [], None
        w0 = time.perf_counter()
        with CompileCounter() as compiles:
            while True:
                first_traced = traced and not calls
                if first_traced:
                    import tempfile
                    trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
                    jax.profiler.start_trace(trace_dir)
                try:
                    calls.append(timed_call(arrays, k, method, seed,
                                            options, tap, scores,
                                            annotate=first_traced))
                finally:
                    if first_traced:
                        jax.profiler.stop_trace()
                if time.perf_counter() - w0 >= seconds:
                    break
        window_s = time.perf_counter() - w0
    peak = memory_peak_bytes()
    device["memory_peak_bytes"] = peak
    # the reference runs after the window, on the host, outside set-up,
    # in a worker that never imports JAX, beside the trace's reduction
    with multiprocessing.get_context("spawn").Pool(1) as pool:
        ref_job = pool.apply_async(_reference_km1, (
            g["n"], g["m"], vertex_ids, edge_ids, k, seed))
        reduced, trace_s = None, 0.0
        if trace_dir is not None:
            t0 = time.perf_counter()
            import glob
            import shutil
            files = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
            if files:
                reduced = bench_trace.reduce_events(
                    bench_trace.load_events(files[0]),
                    kernels=tuple(tr.get("kernels", ())))
            shutil.rmtree(trace_dir, ignore_errors=True)
            trace_s = time.perf_counter() - t0
        ref_km1, ref_s = ref_job.get()
    values = check_calls([warm] + calls, csr, k, int(tr["balance_slack"]),
                         ref_km1, score_spec)
    correct, checks = judge(values, cell.limits)
    failed = sum(c.assignment is None for c in calls)
    result = {"correct": correct, "attempted": len(calls), "failed": failed}
    if traced:
        data = RunData(cell.name, calls, reduced)
        metrics = {}
        for m in cell.per_layer:
            v = load_reader(cell.root, m["name"])(data)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if reduced is not None:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            result["breakdown"] = {"device_ops": reduced["device_ops"],
                                   "idle_gaps": reduced["idle_gaps"]}
    else:
        e2e = end_to_end_values(calls, csr, k, setup_s)
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end if m["name"] in e2e}
    result.update(metrics=metrics, device=device)
    result["run"] = {"seed": seed, "calls": len(calls), "window_s": window_s,
                     "call_s": [c.seconds for c in calls],
                     "warmup_s": warm.seconds, "reference_s": ref_s,
                     "trace_s": trace_s,
                     "reference_km1": ref_km1,
                     "score_rows": sum(s[0].size for c in [warm] + calls
                                       for s in c.scores),
                     "compiles_in_window": compiles.count,
                     "errors": [c.error for c in [warm] + calls
                                if c.error][:3]}
    result["checks"] = checks
    return result


def print_result(result: dict) -> None:
    """Checks as the last lines of stderr, the result as stdout's last."""
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


def prepare_environment(root: Path) -> None:
    """Compile cache inside the checkout; the program's defaults."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(root / ".jax_cache")
    for var in PROGRAM_SWITCHES:
        os.environ.pop(var, None)
    src = str(root / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
