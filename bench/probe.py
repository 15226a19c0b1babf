"""Probes of the program behind findings in PERF.md; not part of a run.

    python bench/probe.py score_free --config github.k32 --seeds 1,2,3
    python bench/probe.py interpret --seeds 1476999519

``score_free``: partitions the configuration's graph with
``hype_superstep`` at the library defaults, then again with each fault
of ``faults.py`` planted in the scoring kernels, and prints whether the
assignment came out the same, bit for bit, with each one's k-1.

``interpret``: partitions ``github_like(1.0)`` of the repository's own
generator with ``hype_superstep`` (k 32, pipeline depth 2) with the
kernel compiled and then in Pallas interpret mode
(``REPRO_PALLAS_INTERPRET=1``), and prints the part sizes of each.

One JSON line per partition. Needs the chip.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def _partition(hg, k: int, seed: int, **kw):
    from repro.engines.superstep import (SuperstepParams,
                                         hype_superstep_partition)
    t0 = time.perf_counter()
    a, st = hype_superstep_partition(hg, k, SuperstepParams(seed=seed, **kw),
                                     return_stats=True)
    return a, st, time.perf_counter() - t0


def score_free(cfg: dict, seeds: list) -> None:
    import numpy as np
    import faults
    from graphs import config_pins
    from reference import Csr, km1
    from repro.core.hypergraph import Hypergraph

    g, k = cfg["graph"], int(cfg["k"])
    for seed in seeds:
        v, e = config_pins(cfg, seed)
        csr = Csr(g["n"], g["m"], v, e)
        base, _, secs = _partition(Hypergraph.from_pins(g["n"], g["m"], v, e),
                                   k, seed)
        print(json.dumps({"seed": seed, "fault": None, "seconds": secs,
                          "km1": km1(csr, base, k)}), flush=True)
        for fault in faults.FAULTS:
            with faults.planted(fault):
                a, _, secs = _partition(
                    Hypergraph.from_pins(g["n"], g["m"], v, e), k, seed)
            print(json.dumps({"seed": seed, "fault": fault, "seconds": secs,
                              "km1": km1(csr, a, k),
                              "same_assignment": bool(np.array_equal(a, base))}),
                  flush=True)


def interpret(seed: int) -> None:
    import numpy as np
    from reference import Csr, km1, part_sizes
    from repro.core.hypergraph import Hypergraph
    from repro.data.synthetic import github_like

    hg = github_like(1.0, seed=seed)
    v, e = hg.e2v_indices, np.repeat(np.arange(hg.m), hg.edge_sizes)
    csr = Csr(hg.n, hg.m, v, e)
    out = {}
    for mode in ("compiled", "interpret"):
        if mode == "interpret":
            os.environ["REPRO_PALLAS_INTERPRET"] = "1"
        a, st, secs = _partition(Hypergraph.from_pins(hg.n, hg.m, v, e),
                                 32, seed)
        os.environ.pop("REPRO_PALLAS_INTERPRET", None)
        sizes = part_sizes(a, 32)
        out[mode] = a
        print(json.dumps({"mode": mode, "seed": seed, "seconds": secs,
                          "max_minus_min": int(sizes.max() - sizes.min()),
                          "sizes": sizes.tolist(), "km1": km1(csr, a, 32),
                          "supersteps": st.supersteps, "retries": st.retries,
                          "fallbacks": st.fallbacks}), flush=True)
    print(json.dumps({"identical": bool(np.array_equal(out["compiled"],
                                                       out["interpret"]))}))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("probe", choices=("score_free", "interpret"))
    ap.add_argument("--config", default="github.k32")
    ap.add_argument("--seeds", default="1476999519")
    args = ap.parse_args(argv)
    import harness
    harness.prepare_environment(ROOT)
    harness.device_record(1)
    seeds = [int(s) for s in args.seeds.split(",")]
    if args.probe == "score_free":
        cfg = json.loads((ROOT / "bench" / "configs"
                          / f"{args.config}.json").read_text())
        score_free(cfg, seeds)
    else:
        interpret(seeds[0])
    return 0


if __name__ == "__main__":
    sys.path.insert(0, str(Path(__file__).resolve().parent))
    sys.exit(main())
