#!/usr/bin/env python
"""Attribute the device's idle time in a traced partition call to spans.

    python tools/span_gaps.py TRACE.xplane.pb [...]
    python tools/span_gaps.py --workload <cell> --seed <n> [--calls 2]
                              [--out FILE]

The engines' named spans (``hype.*``, ``engines/runtime.span``) land on
the profiler's host clock. This tool keeps, of a trace's host events,
only those spans, with the call's root ``hype.partition`` standing as
the call's interval, and hands them to ``bench/trace_reduce.py``'s
``reduce_events`` unchanged: each idle moment of the device is summed
by the innermost ``hype.*`` span open then, and idle time under no span
but the root as ``untraced host work``.

Given trace files it prints one table per file. Given a benchmark cell
it runs the cell's graph and engine as the benchmark does
(``bench/harness.py``): a warm-up call, ``--calls`` untraced calls, one
call under the profiler, ``--calls`` calls after it. It prints the
traced call's table and, for each group of calls, the mean wall time,
the mean span totals, the share of the root the root's children cover,
each call's adjacency build (chunks, threads), and the cell's per-layer
metrics that read the stats; the JSON summary
is the last line, and is written to ``--out`` too.
"""
from __future__ import annotations

import argparse
import dataclasses
import glob
import json
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "bench"))

import trace_reduce  # noqa: E402

ROOT_SPAN = "hype.partition"
# the root's children in the superstep and batched engines; their total
# over the root's is the share of the call the spans account for
TOP_SPANS = ("hype.setup.adjacency", "hype.setup.image", "hype.grow",
             "hype.finish", "hype.restore")
DEVICE = trace_reduce.DEVICE_PREFIX


def program_spans(events: list) -> list:
    """Device events, and of the host's only the ``hype.*`` spans.

    The root span is renamed to the annotation ``reduce_events`` takes
    as the call's interval; any other annotation is dropped.
    """
    out = []
    for plane, line, name, a, b in events:
        if plane.startswith(DEVICE):
            out.append((plane, line, name, a, b))
        elif name == ROOT_SPAN:
            out.append((plane, line, trace_reduce.ANNOTATION, a, b))
        elif name.startswith("hype."):
            out.append((plane, line, name, a, b))
    return out


def span_gaps(path: str) -> dict | None:
    """``reduce_events`` of one ``.xplane.pb`` file's program spans."""
    return trace_reduce.reduce_events(
        program_spans(trace_reduce.load_events(path)), top=32)


def print_table(red: dict | None, title: str) -> None:
    print(f"# {title}")
    if red is None:
        print("no hype.partition span with device ops inside it")
        return
    idle = red["window_s"] - red["busy_s"]
    print(f"call {red['window_s']:.3f} s, device busy {red['busy_s']:.3f}"
          f" s, idle {idle:.3f} s")
    print(f"{'innermost span at the idle moment':36s} {'idle s':>9s} "
          f"{'of idle':>8s}")
    for name, sec in red["idle_gaps"]:
        print(f"{name:36s} {sec:9.3f} {100 * sec / max(idle, 1e-12):7.1f}%")


def _group(calls: list, cell, harness) -> dict:
    """Means over a group of calls, and the cell's readers over it."""
    ok = [c for c in calls if c.stats is not None]
    names = sorted({n for c in ok for n in c.stats.spans})
    spans = {n: [sum(c.stats.spans.get(n, (0.0, 0))[i] for c in ok)
                 / len(ok) for i in (0, 1)] for n in names}
    cover = [sum(c.stats.span_s(n) for n in TOP_SPANS)
             / c.stats.span_s(ROOT_SPAN) for c in ok]
    data = harness.RunData(cell.name, calls, None)
    metrics = {}
    for m in cell.per_layer:
        v = harness.load_reader(cell.root, m["name"])(data)
        if v is not None:
            metrics[m["name"]] = v
    builds = [(c.stats.adjacency_chunks, c.stats.adjacency_workers)
              for c in ok]
    return {"calls": len(calls), "call_s": [c.seconds for c in calls],
            "mean_call_s": sum(c.seconds for c in calls) / len(calls),
            "spans": spans, "children_cover": cover, "metrics": metrics,
            "adjacency_builds": builds}


def run_cell(workload: str, seed: int, calls: int) -> dict:
    import harness
    from graphs import config_pins

    harness.prepare_environment(ROOT)
    import jax
    from repro.core.hypergraph import Hypergraph

    cell = harness.load_cell(ROOT, workload)
    g, k = cell.config["graph"], int(cell.config["k"])
    vertex_ids, edge_ids = config_pins(cell.config, seed)
    hg0 = Hypergraph.from_pins(g["n"], g["m"], vertex_ids, edge_ids)
    arrays = {f.name: getattr(hg0, f.name)
              for f in dataclasses.fields(Hypergraph)}
    del hg0
    tr = cell.traffic
    method, options = tr["method"], dict(tr.get("options", {}))
    module, runner = tr["engine_entry"].split(":")

    def call(tap, annotate=False):
        return harness.timed_call(arrays, k, method, seed, options, tap,
                                  annotate=annotate)

    trace_dir = tempfile.mkdtemp(prefix="span_gaps_")
    with harness.StatsTap(module, runner) as tap:
        call(tap)                                   # warm-up: compiles
        before = [call(tap) for _ in range(calls)]
        jax.profiler.start_trace(trace_dir)
        try:
            traced = call(tap, annotate=True)
        finally:
            jax.profiler.stop_trace()
        after = [call(tap) for _ in range(calls)]
    files = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    red = span_gaps(files[0]) if files else None
    shutil.rmtree(trace_dir, ignore_errors=True)
    errors = [c.error for c in before + [traced] + after if c.error]
    if errors:
        raise SystemExit(f"a partition call failed: {errors[0]}")
    print_table(red, f"{workload} seed {seed}: the traced call")
    return {"workload": workload, "seed": seed, "traced_gaps": red,
            "untraced": _group(before, cell, harness),
            "traced": _group([traced], cell, harness),
            "post_trace": _group(after, cell, harness)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("traces", nargs="*", help=".xplane.pb files")
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--calls", type=int, default=2,
                    help="untraced calls before and after the traced one")
    ap.add_argument("--out", help="also write the JSON summary here")
    args = ap.parse_args(argv)
    if bool(args.traces) == bool(args.workload):
        ap.error("give trace files or --workload, not both")
    if args.traces:
        for path in args.traces:
            print_table(span_gaps(path), path)
        return 0
    summary = run_cell(args.workload, args.seed, max(1, args.calls))
    text = json.dumps(summary)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(text + "\n")
    print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
