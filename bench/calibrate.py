"""Readings that the cells' correctness limits are set from.

    python bench/calibrate.py --config <config> --traffic superstep,batched \
        --seeds 1,2,3 [--controls] [--fault <fault>] [--workers 6]

For each seed, in one process that holds the chip: generate the
configuration's graph, partition it once through each traffic's timed
path (the same ``partition()`` call the window makes, on a new
``Hypergraph``), and compare it as a run does. The sequential reference
runs in numpy-only worker processes, in parallel, since it is the long
part. ``--controls`` adds, per seed, the controls' readings of
``km1_excess``:

* ``random``: the program's own ``random`` method in the engine's
  place, a balanced assignment without HYPE's neighbourhood expansion;
* ``bf16``: the reference with its scores rounded to bfloat16;
* ``none``: the reference ranking its fringe by arrival, no score.

``--fault`` plants one of ``faults.FAULTS`` in the scoring kernels for
the traffic's calls, for the readings of a fault.

One JSON line per seed and traffic, then a summary line per traffic.
Not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import multiprocessing
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def _reference(args):
    cfg, seed, score = args
    from graphs import config_pins
    from reference import Csr, hype_reference, km1
    g = cfg["graph"]
    v, e = config_pins(cfg, seed)
    csr = Csr(g["n"], g["m"], v, e)
    t0 = time.perf_counter()
    a = hype_reference(csr, int(cfg["k"]), seed, score=score)
    return seed, score, km1(csr, a, int(cfg["k"])), time.perf_counter() - t0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", action="store_true")
    ap.add_argument("--fault", default=None)
    ap.add_argument("--workers", type=int, default=6)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    cfg = json.loads((ROOT / "bench" / "configs"
                      / f"{args.config}.json").read_text())
    k = int(cfg["k"])
    scores = ["exact"] + (["bf16", "none"] if args.controls else [])
    # the workers never touch JAX, so the chip stays with this process
    os.environ["JAX_PLATFORMS"] = "cpu"
    pool = multiprocessing.get_context("spawn").Pool(args.workers)
    pending = pool.map_async(_reference,
                             [(cfg, s, sc) for s in seeds for sc in scores])
    os.environ.pop("JAX_PLATFORMS")

    import contextlib
    import dataclasses
    import faults
    import harness
    from graphs import config_pins
    from reference import Csr, km1

    harness.prepare_environment(ROOT)
    harness.device_record(1)
    from repro.core.hypergraph import Hypergraph
    from repro.core.partition_api import partition

    g = cfg["graph"]
    out = {}
    for traffic in args.traffic.split(","):
        tr = json.loads((ROOT / "bench" / "traffic"
                         / f"{traffic}.json").read_text())
        module, runner = tr["engine_entry"].split(":")
        with harness.StatsTap(module, runner) as tap:
            for seed in seeds:
                v, e = config_pins(cfg, seed)
                csr = Csr(g["n"], g["m"], v, e)
                hg0 = Hypergraph.from_pins(g["n"], g["m"], v, e)
                arrays = {f.name: getattr(hg0, f.name)
                          for f in dataclasses.fields(Hypergraph)}
                with harness.ScoreTap(tr.get("score_check"), seed) as sc, \
                        (faults.planted(args.fault) if args.fault
                         else contextlib.nullcontext()):
                    call = harness.timed_call(
                        arrays, k, tr["method"], seed,
                        dict(tr.get("options", {})), tap, sc)
                rnd = None
                if args.controls:
                    rnd = km1(csr, partition(hg0, k, "random", seed=seed), k)
                out[(traffic, seed)] = (call, csr, rnd)
    refs = {}
    for seed, score, value, secs in pending.get():
        refs[(seed, score)] = (value, secs)
    pool.close()
    pool.join()
    for traffic in args.traffic.split(","):
        tr = json.loads((ROOT / "bench" / "traffic"
                         / f"{traffic}.json").read_text())
        excess = []
        for seed in seeds:
            call, csr, rnd = out[(traffic, seed)]
            ref, ref_s = refs[(seed, "exact")]
            vals = harness.check_calls([call], csr, k,
                                       int(tr["balance_slack"]), ref,
                                       tr.get("score_check"))
            rec = {"config": args.config, "traffic": traffic, "seed": seed,
                   "fault": args.fault, "call_s": call.seconds,
                   "score_rows": sum(s[0].size for s in call.scores),
                   "reference_km1": ref,
                   "reference_s": ref_s, **vals}
            if args.controls:
                rec["control_random"] = rnd / ref - 1.0
                for sc in ("bf16", "none"):
                    rec[f"control_{sc}"] = refs[(seed, sc)][0] / ref - 1.0
            excess.append(vals["km1_excess"])
            print(json.dumps(rec), flush=True)
        print(json.dumps({"summary": traffic, "config": args.config,
                          "fault": args.fault,
                          "seeds": len(seeds),
                          "km1_excess_max": max(excess),
                          "km1_excess_min": min(excess)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.path.insert(0, str(Path(__file__).resolve().parent))
    sys.exit(main())
