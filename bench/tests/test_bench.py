"""Tests of the benchmark itself, on the CPU, with no chip.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

They cover the calibrated generator, the reference, the trace
reduction, finding cells and metrics by name, the result line, the
refusal to run without a TPU, and that the control and the planted
faults come out not correct. Runs go through ``harness.run`` with the
look for a chip skipped, on a graph cut to a fiftieth of github.k32.
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import faults  # noqa: E402
import graphs  # noqa: E402
import harness  # noqa: E402
import reference  # noqa: E402
import trace_reduce  # noqa: E402

CONFIGS = ("github.k32", "stackoverflow.k128")


def _config(name: str) -> dict:
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", CONFIGS)
@pytest.mark.parametrize("seed", [7, 2**31 + 3])
def test_generator_hits_published_shape(name, seed):
    cfg = _config(name)
    g = cfg["graph"]
    v, e = graphs.config_pins(cfg, seed)
    st = graphs.graph_stats(g["n"], g["m"], v, e)
    assert (st["n"], st["m"], st["pins"]) == (g["n"], g["m"], g["pins"])
    assert st["isolated_vertices"] == 0
    assert st["empty_edges"] == 0
    assert st["repeated_pins"] == 0
    # under the superstep engine's expanded-adjacency guard, with room
    assert st["expanded_pairs"] < 40_000_000


def test_generator_same_sizes_other_order():
    cfg = _config("github.k32")
    g = cfg["graph"]
    (v1, e1), (v2, e2) = (graphs.config_pins(cfg, s) for s in (1, 2))
    s1 = np.sort(np.bincount(e1, minlength=g["m"]))
    s2 = np.sort(np.bincount(e2, minlength=g["m"]))
    assert (s1 == s2).all()
    assert not np.array_equal(v1, v2)
    v3, e3 = graphs.config_pins(cfg, 1)
    assert np.array_equal(v1, v3) and np.array_equal(e1, e3)


def _tiny_pins(seed=3):
    cfg = _config("github.k32")
    g = cfg["graph"]
    return graphs.calibrated_pins(
        g["n"] // 50, g["m"] // 50, g["pins"] // 50,
        alpha_edge=g["alpha_edge"], alpha_vertex=g["alpha_vertex"],
        max_edge=60, max_degree=60, locality=g["locality"], shape_seed=0,
        seed=seed)


def test_reference_is_sequential_hype():
    """Bit for bit the repository's numpy HYPE (a test-only import)."""
    sys.path.insert(0, str(ROOT / "src"))
    from repro.core.hype import HypeParams, hype_partition
    from repro.core.hypergraph import Hypergraph

    v, e = _tiny_pins()
    n, m = int(v.max()) + 1, int(e.max()) + 1
    csr = reference.Csr(n, m, v, e)
    for k in (4, 32):
        ours = reference.hype_reference(csr, k, seed=5)
        theirs = hype_partition(Hypergraph.from_pins(n, m, v, e), k,
                                HypeParams(seed=5))
        assert np.array_equal(ours, theirs)
        sizes = reference.part_sizes(ours, k)
        assert sizes.max() - sizes.min() <= 1


def test_external_scores_arithmetic():
    # edges {0,1,2}, {2,3}, {3,4}; vertex 1 assigned, vertex 4 in fringe
    csr = reference.Csr(5, 3, [0, 1, 2, 2, 3, 3, 4], [0, 0, 0, 1, 1, 2, 2])
    assign = np.array([-1, 0, -1, -1, -1])
    fringe = np.array([False, False, False, False, True])
    got, wide = reference.external_scores(csr, [0, 2, 3], assign, fringe,
                                          hub_width=2)
    # 0: {1, 2} less assigned 1 -> 1; 2: {0, 1, 3} -> 2 (3 free: wide)
    # 3: {2, 4} less fringe 4 -> 1
    assert got.tolist() == [1, 2, 1]
    assert wide.tolist() == [False, False, False]
    _, wide = reference.external_scores(csr, [2], assign, fringe,
                                        hub_width=1)
    assert wide.tolist() == [True]


def test_km1_arithmetic():
    # edges {0,1,2}, {2,3}, {4}; parts 0 0 1 1 0
    csr = reference.Csr(5, 3, [0, 1, 2, 2, 3, 4], [0, 0, 0, 1, 1, 2])
    a = np.array([0, 0, 1, 1, 0])
    assert reference.km1(csr, a, 2) == 1
    assert reference.km1(csr, np.array([0, 1, 0, 1, 0]), 2) == 2


# ------------------------------------------------------------- trace

def _ev(plane, line, name, a, b):
    return (plane, line, name, float(a), float(b))


def test_trace_reduction_known_shares():
    dev, host = "/device:TPU:0", "/host:CPU"
    kern = ("%_hype_score_select.1 = (f32[32,8]{1,0:T(8,128)S(1)}) "
            "custom-call(s32[32,8,2048]{2,1,0:T(8,128)} %p)")
    events = [
        _ev(host, "python", trace_reduce.ANNOTATION, 1000, 11000),
        _ev(host, "python", "partition", 1000, 11000),
        _ev(host, "python", "pack", 1000, 4000),       # covers gap 1
        _ev(host, "python", "harvest", 7000, 9500),    # covers gap 2
        _ev(host, "main", "Execute", 4000, 9000),      # another thread
        _ev(dev, "XLA Ops", kern, 4000, 5000),
        _ev(dev, "XLA Ops", "%fusion.1 = s32[8]{0} fusion()", 4500, 7000),
        _ev(dev, "XLA Ops", "%fusion.2 = s32[8]{0} fusion()", 9500, 10000),
        _ev(dev, "XLA Ops", "%fusion.3 = s32[8]{0} fusion()", 500, 900),
        _ev(dev, "XLA Modules", "jit_step", 4000, 10000),   # not an op
    ]
    r = trace_reduce.reduce_events(events)
    ns = 1e-9
    assert r["window_s"] == pytest.approx(10000 * ns)
    assert r["busy_s"] == pytest.approx((3000 + 500) * ns)   # 4000-7000
    assert r["kernel_s"] == pytest.approx(1000 * ns)
    gaps = dict(r["idle_gaps"])
    assert gaps == pytest.approx({"pack": 3000 * ns, "harvest": 2500 * ns,
                                  "partition": 1000 * ns})
    assert r["device_ops"][0] == ["fusion.1 = s32[8] fusion()",
                                  pytest.approx(2500 * ns)]


def test_self_segments_of_nested_spans():
    pieces = trace_reduce._self_segments(
        [(0, 10, "a"), (2, 5, "b"), (3, 4, "c"), (6, 8, "d")])
    assert pieces == [(0, 2, "a"), (2, 3, "b"), (3, 4, "c"), (4, 5, "b"),
                      (5, 6, "a"), (6, 8, "d"), (8, 10, "a")]


def test_trace_reduction_without_call_or_device():
    host = "/host:CPU"
    assert trace_reduce.reduce_events([]) is None
    assert trace_reduce.reduce_events(
        [_ev(host, "python", trace_reduce.ANNOTATION, 0, 10)]) is None


RECORDED = BENCH / "tests" / "recorded_trace.json"


@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded excerpt")
def test_trace_reduction_recorded_excerpt():
    """An excerpt of a chip trace of one github.k32 superstep call."""
    rec = json.loads(RECORDED.read_text())
    events = [tuple(e) for e in rec["events"]]
    r = trace_reduce.reduce_events(events)
    # the expected values come from a 1 ns timeline: each op edge may
    # round by half a nanosecond
    tol = 1e-9 * len(events)
    for key, want in rec["expect"].items():
        assert r[key] == pytest.approx(want, abs=tol), key


# --------------------------------------------- cells found by name, runs

def _tiny_root(tmp_path: Path, metric_src: str | None = None) -> Path:
    """A checkout with the benchmark plus a tiny cell added as files."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    (root / "src").symlink_to(ROOT / "src")
    bm = json.loads((root / "BENCHMARK.json").read_text())
    cfg = _config("github.k32")
    for key in ("n", "m", "pins"):
        cfg["graph"][key] //= 50
    cfg["graph"].update(max_edge=60, max_degree=60)
    (root / "bench/configs/tiny.json").write_text(json.dumps(cfg))
    bm["configs"].append({"name": "tiny", "source": "test", "reduced": [],
                          "file": "bench/configs/tiny.json", "why": "test"})
    limits = json.loads(
        (BENCH / "workloads/github.k32.superstep.json").read_text())
    # a graph fifty times smaller scores fifty times fewer batches
    tr = json.loads((BENCH / "traffic/batched.json").read_text())
    tr["score_check"]["every"] = 20
    (root / "bench/traffic/batched.json").write_text(json.dumps(tr))
    for t in ("superstep", "batched"):
        bm["workloads"].append({"name": f"tiny.{t}", "config": "tiny",
                                "traffic": t, "chips": 1, "why": "test"})
        (root / f"bench/workloads/tiny.{t}.json").write_text(
            json.dumps(limits))
        for m in bm["per_layer"]:
            m.setdefault("workloads", []).append(f"tiny.{t}")
    if metric_src is not None:
        (root / "bench/metrics/calls_in_window.py").write_text(metric_src)
        bm["per_layer"].append({
            "name": "calls_in_window", "unit": "calls", "better": "higher",
            "source": "host_clock", "layer": "test", "moves":
            "vertices_per_s", "workloads": ["tiny.superstep"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    return root


def _run(root: Path, cell: str, traced=False, seed=11, seconds=0.5):
    harness.prepare_environment(root)
    c = harness.load_cell(root, cell)
    return harness.run(c, seed, seconds, traced, time.perf_counter(),
                       require_tpu=False)


REQUIRED = ["correct", "attempted", "failed", "metrics", "device"]


def test_files_found_by_name_and_result_line(tmp_path, capsys):
    root = _tiny_root(tmp_path, metric_src=(
        "def read(run):\n    return float(len(run.calls))\n"))
    cell = harness.load_cell(root, "tiny.superstep")
    assert cell.config["graph"]["n"] == 177386 // 50
    assert cell.traffic["method"] == "hype_superstep"
    assert "calls_in_window" in [m["name"] for m in cell.per_layer]
    assert "calls_in_window" not in [
        m["name"] for m in harness.load_cell(root, "tiny.batched").per_layer]
    res = _run(root, "tiny.superstep", traced=True)
    assert res["correct"] is True
    assert res["metrics"]["calls_in_window"]["value"] == res["attempted"]
    # host-clock readers report; device-trace readers find no TPU plane
    # on the CPU and leave their metric out instead of reading 0
    assert "host_share.superstep" in res["metrics"]
    assert "device_idle_share" not in res["metrics"]
    harness.print_result(res)
    out, err = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    keys = list(line)
    assert keys[:5] == REQUIRED
    assert keys[-1] == "checks"
    assert set(keys) <= set(REQUIRED) | {"breakdown", "run", "checks"}
    assert err.strip().splitlines()[-1] == "correct: True"
    res0 = _run(root, "tiny.batched")
    assert list(res0)[:5] == REQUIRED and "breakdown" not in res0
    assert set(res0["metrics"]) == {"vertices_per_s", "km1_per_edge",
                                    "setup_s"}
    assert all(v["value"] > 0 for v in res0["metrics"].values())


def test_score_check_on_a_sound_run(tmp_path):
    """The batched engine's stored scores are sampled and all right."""
    root = _tiny_root(tmp_path)
    res = _run(root, "tiny.batched", seconds=1.0)
    assert res["correct"] is True
    assert res["run"]["score_rows"] > 0
    assert res["checks"]["score_mismatch"]["value"] == 0
    assert res["checks"]["score_unchecked"]["value"] == 0
    # the superstep engine keeps its scores on the device: not sampled
    assert "score_mismatch" not in _run(root, "tiny.superstep")["checks"]


def test_score_check_that_samples_nothing_is_not_correct(tmp_path):
    root = _tiny_root(tmp_path)
    path = root / "bench/traffic/batched.json"
    tr = json.loads(path.read_text())
    tr["score_check"]["every"] = 10**9
    path.write_text(json.dumps(tr))
    res = _run(root, "tiny.batched")
    assert res["correct"] is False
    assert res["checks"]["score_unchecked"]["value"] == 1


def test_no_tpu_no_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "github.k32.superstep", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "not a TPU" in proc.stderr


def test_only_benchmark_files_no_result(tmp_path):
    """Without the program (no src/) a run fails and prints no result."""
    root = tmp_path / "bare"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "github.k32.superstep", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=root, env=env, capture_output=True,
        text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


# ------------------------------------------- control and planted faults

def _broken(module: str, runner: str, fault: str, monkeypatch):
    """Break the engine entry ``partition()`` dispatches to."""
    import importlib
    sys.path.insert(0, str(ROOT / "src"))
    mod = importlib.import_module(module)
    orig = getattr(mod, runner)

    def broken(hg, k, params=None, return_stats=False):
        a, stats = orig(hg, k, params, return_stats=True)
        a = a.copy()
        rng = np.random.default_rng(0)
        if fault == "state_unchanged":      # nothing admitted
            a[:] = -1
        elif fault == "half_left_out":      # half the vertices dropped
            a[rng.permutation(a.size)[: a.size // 2]] = -1
        elif fault == "answer_altered":     # 1% moved to the next part
            pick = rng.permutation(a.size)[: a.size // 100]
            a[pick] = (a[pick] + 1) % k
        elif fault == "control_random":
            from repro.core.minmax import random_partition
            a = random_partition(hg, k, seed=params.seed)
        return (a, stats) if return_stats else a

    monkeypatch.setattr(mod, runner, broken)


@pytest.mark.parametrize("traffic", ["superstep", "batched"])
@pytest.mark.parametrize("fault", ["state_unchanged", "half_left_out",
                                   "answer_altered"])
def test_planted_fault_is_not_correct(tmp_path, monkeypatch, traffic,
                                      fault):
    root = _tiny_root(tmp_path)
    tr = json.loads((BENCH / "traffic" / f"{traffic}.json").read_text())
    _broken(*tr["engine_entry"].split(":"), fault, monkeypatch)
    res = _run(root, f"tiny.{traffic}")
    assert res["correct"] is False
    failing = [n for n, c in res["checks"].items()
               if c["value"] > c["limit"]]
    want = {"state_unchanged": "unassigned", "half_left_out": "unassigned",
            "answer_altered": "balance_excess"}[fault]
    assert want in failing


def test_zeroed_scores_are_not_correct(tmp_path):
    """The scoring kernel left out: every kernel score reads 0."""
    root = _tiny_root(tmp_path)
    with faults.planted("zero_kernel"):
        res = _run(root, "tiny.batched", seconds=1.0)
    assert res["correct"] is False
    assert res["checks"]["score_mismatch"]["value"] > 0


def test_control_random_is_not_correct(tmp_path, monkeypatch):
    """The program's balanced random method in the engine's place."""
    root = _tiny_root(tmp_path)
    _broken("repro.engines.superstep", "hype_superstep_partition",
            "control_random", monkeypatch)
    res = _run(root, "tiny.superstep")
    assert res["correct"] is False
    assert res["checks"]["km1_excess"]["value"] > \
        res["checks"]["km1_excess"]["limit"]
    assert res["checks"]["balance_excess"]["value"] == 0


def test_precision_control_reads_like_the_reference():
    """bf16 scores change no growth step at this size: small integers.

    Recorded so the reading is on file: the precision control cannot
    set an upper reading for ``km1_excess`` (see PERF.md).
    """
    v, e = _tiny_pins()
    n, m = int(v.max()) + 1, int(e.max()) + 1
    csr = reference.Csr(n, m, v, e)
    exact = reference.km1(csr, reference.hype_reference(csr, 32, 5), 32)
    low = reference.km1(
        csr, reference.hype_reference(csr, 32, 5, score="bf16"), 32)
    assert abs(low / exact - 1.0) < 0.05
