"""Named host spans of the superstep and batched engines.

``engines/runtime.span`` adds each span's wall time to
``BatchedStats.spans`` and, while a profiler records, puts the span on
the trace with the partition call's id. These tests run both engines on
a small graph, untraced and under ``jax.profiler.trace``, and read the
spans from the stats, from the trace, and through the benchmark's
per-layer readers (``bench/metrics``) and ``tools/span_gaps.py``.
"""
import dataclasses
import glob
import importlib.util
import sys
import time
import types
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core.hypergraph import Hypergraph
from repro.data.synthetic import github_like
from repro.engines.batched import hype_batched_partition
from repro.engines.runtime import BatchedStats
from repro.engines.superstep import hype_superstep_partition

REPO = Path(__file__).resolve().parents[1]
ENGINES = {"superstep": hype_superstep_partition,
           "batched": hype_batched_partition}
K = 8
# the root's children: together they cover the call
TOP = ("hype.setup.adjacency", "hype.setup.image", "hype.grow",
       "hype.finish")
# where each span sits: its parent on the trace
PARENT = {"hype.setup.adjacency": "hype.partition",
          "hype.setup.image": "hype.partition",
          "hype.grow": "hype.partition",
          "hype.finish": "hype.partition",
          "hype.superstep.pack": "hype.grow",
          "hype.superstep.dispatch": "hype.grow",
          "hype.superstep.wait": "hype.grow",
          "hype.superstep.mirror": "hype.grow",
          "hype.refill.draw": "hype.grow",
          "hype.refill.score": "hype.grow",
          "hype.refill.activate": "hype.grow",
          "hype.refill.kernel": "hype.refill.score"}
# each reader, and the engine whose cell lists it
READERS = {"adjacency_share": "superstep",
           "device_wait_share.superstep": "superstep",
           "tile_build_share.batched": "batched",
           "kernel_roundtrip_share.batched": "batched"}


def _fresh(hg):
    """A new Hypergraph: no memoised adjacency or device image."""
    return Hypergraph(**{f.name: getattr(hg, f.name)
                         for f in dataclasses.fields(Hypergraph)})


def _host_spans(path):
    """``(name, start_ns, end_ns, call id)`` of the trace's hype spans."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("hype."):
                    out.append((ev.name, ev.start_ns, ev.end_ns,
                                dict(ev.stats).get("call")))
    return sorted(out, key=lambda e: (e[1], -e[2]))


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """Per engine: a warm-up, an untraced call and a traced call."""
    hg = github_like(0.05)
    out = {}
    for name, fn in ENGINES.items():
        fn(_fresh(hg), K)                       # compiles
        t0 = time.perf_counter()
        a, st = fn(_fresh(hg), K, return_stats=True)
        wall = time.perf_counter() - t0
        trace_dir = tmp_path_factory.mktemp(f"trace_{name}")
        with jax.profiler.trace(str(trace_dir)):
            a_traced, st_traced = fn(_fresh(hg), K, return_stats=True)
        files = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
        out[name] = types.SimpleNamespace(
            n=hg.n, a=a, stats=st, wall=wall, a_traced=a_traced,
            stats_traced=st_traced, events=_host_spans(files[0]))
    return out


@pytest.mark.parametrize("engine", ENGINES)
def test_children_cover_the_call(runs, engine):
    r = runs[engine]
    root = r.stats.span_s("hype.partition")
    assert r.stats.spans["hype.partition"][1] == 1
    assert 0 < root <= r.wall
    assert r.stats.span_s(*TOP) >= 0.95 * root


@pytest.mark.parametrize("engine", ENGINES)
def test_spans_nest_inside_their_parents(runs, engine):
    events = runs[engine].events
    assert events[0][0] == "hype.partition"
    stack = []
    for name, start, end, _ in events:
        while stack and stack[-1][2] <= start:
            stack.pop()
        if stack:
            parent = stack[-1]
            assert end <= parent[2], (name, parent[0])
            assert PARENT.get(name, parent[0]) == parent[0], name
        else:
            assert name == "hype.partition"
        stack.append((name, start, end))


@pytest.mark.parametrize("engine", ENGINES)
def test_trace_holds_every_span_with_the_call_id(runs, engine):
    r = runs[engine]
    ids = {call for *_, call in r.events}
    assert len(ids) == 1 and ids.pop() > 0
    counts = {}
    for name, *_ in r.events:
        counts[name] = counts.get(name, 0) + 1
    assert counts == {n: c for n, (_, c) in r.stats_traced.spans.items()}
    # the untraced call opened the same spans, with no profiler to see them
    assert r.stats.spans.keys() == r.stats_traced.spans.keys()


@pytest.mark.parametrize("engine", ENGINES)
def test_profiler_leaves_the_assignment(runs, engine):
    np.testing.assert_array_equal(runs[engine].a, runs[engine].a_traced)


def test_host_s_is_pack_dispatch_mirror(runs):
    st = runs["superstep"].stats
    sp = st.spans
    assert st.host_s == pytest.approx(
        sp["hype.superstep.pack"][0] + sp["hype.superstep.dispatch"][0]
        + sp["hype.superstep.mirror"][0])
    assert st.device_s == sp["hype.superstep.wait"][0] > 0
    for name in ("dispatch", "wait", "mirror"):
        assert sp[f"hype.superstep.{name}"][1] == st.supersteps


def test_merge_sums_spans():
    a, b = BatchedStats(), BatchedStats()
    a.add_span("hype.grow", 1.5)
    a.add_span("hype.finish", 0.25)
    b.add_span("hype.grow", 0.5)
    b.add_span("hype.grow", 1.0)
    out = a.merge(b)
    assert out.spans == {"hype.grow": [3.0, 3], "hype.finish": [0.25, 1]}
    assert a.spans == {"hype.grow": [1.5, 1], "hype.finish": [0.25, 1]}
    snap = out.copy()
    out.add_span("hype.grow", 1.0)
    assert snap.spans["hype.grow"] == [3.0, 3]


def _reader(metric):
    path = REPO / "bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "reader_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _run_data(n, stats, seconds):
    call = types.SimpleNamespace(seconds=seconds, n=n, m=0, stats=stats)
    return types.SimpleNamespace(cell="test", calls=[call], trace=None)


@pytest.mark.parametrize("metric", READERS)
def test_reader_reads_a_real_call(runs, metric):
    r = runs[READERS[metric]]
    value = _reader(metric)(_run_data(r.n, r.stats, r.wall))
    assert value is not None and 0.0 < value < 100.0


@pytest.mark.parametrize("metric", READERS)
def test_reader_without_spans_reads_none(metric):
    """A program without named spans: the reader reads nothing."""
    stats = types.SimpleNamespace(supersteps=3, host_s=1.0)
    assert _reader(metric)(_run_data(10, stats, 2.0)) is None
    assert _reader(metric)(_run_data(10, None, 2.0)) is None


def test_span_gaps_attributes_idle_to_the_innermost_span():
    sys.path.insert(0, str(REPO / "tools"))
    import span_gaps

    host, dev = "/host:CPU", "/device:TPU:0"
    ms = 1e6
    events = [
        (host, "main", "bench.partition", 0, 100 * ms),
        (host, "main", "hype.partition", 0, 100 * ms),
        (host, "main", "hype.grow", 10 * ms, 90 * ms),
        (host, "main", "hype.refill.draw", 20 * ms, 30 * ms),
        (host, "main", "numpy.unique", 20 * ms, 25 * ms),
        (dev, "XLA Ops", "fusion.1 = f32[8] fusion()", 40 * ms, 50 * ms),
    ]
    red = span_gaps.trace_reduce.reduce_events(
        span_gaps.program_spans(events))
    gaps = dict(red["idle_gaps"])
    assert red["window_s"] == pytest.approx(0.1)
    assert red["busy_s"] == pytest.approx(0.01)
    assert gaps["hype.grow"] == pytest.approx(0.06)
    assert gaps["hype.refill.draw"] == pytest.approx(0.01)
    assert gaps["untraced host work"] == pytest.approx(0.02)
    assert "numpy.unique" not in gaps
