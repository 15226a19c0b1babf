"""Bring-up smoke test: the partitioner's main path on a TPU.

Drives ``repro.core.partition_api`` once at the published size of the
paper's Github dataset (``github_like(scale=1.0)``: 177,386 vertices,
56,519 hyperedges, k = 32) and checks what comes out:

* the compiled Pallas kernels reproduce the assignments the CPU tests
  pin in interpret mode (golden digests, bit for bit);
* every engine returns a complete, balanced assignment whose k-1 is
  within the documented bound of the numpy ``hype`` reference;
* every engine ran as itself: no retry, no hand-off to another engine,
  no reduced-memory plan.

Usage, from the repository root::

    python chip_smoke.py               # one chip: every engine
    python chip_smoke.py --four-chips  # hype_sharded on four chips only

One process holds the chip for the whole run. The last line of standard
output is ``{"ok": true, "device": {...}}``; any failed check raises, so
the exit code is non-zero and that line is never printed. Without a TPU
(or without the repository's ``src/``) the script fails the same way.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

K = 32
GITHUB_SCALE = 1.0
GITHUB_SHAPE = (177_386, 56_519, 309_011)   # n, m, pins at scale 1.0
OFFLINE_KM1_BOUND = 1.20    # offline engines' k-1 over numpy hype
SHARDED_KM1_TOL = 0.01      # four-chip k-1 over single-chip superstep

# The digests tests/test_pipeline.py and tests/test_hype_device.py pin
# for hype_superstep at pipeline_depth=1 (and hype_device) in interpret
# mode: (k, t) on powerlaw_hypergraph(600, 400, seed=11, ...), and
# reddit_like(scale=0.005) at k=32, t=16.
GOLD_PL600 = {(5, 8): "9e8abe668aa53a74",
              (16, 8): "bbcd2f732e03af91",
              (16, 16): "e67c679d4029b7d0"}
GOLD_REDDIT = "13f232f653c9c752"


class SmokeFailure(AssertionError):
    """A check of the smoke test did not hold."""


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def digest(a) -> str:
    import numpy as np
    return hashlib.sha256(
        np.ascontiguousarray(a, dtype=np.int32).tobytes()).hexdigest()[:16]


def log(**fields) -> None:
    print(json.dumps(fields, default=str), flush=True)


def device_info(want_count: int) -> dict:
    """Fail unless JAX runs on a TPU with compiled (not interpreted)
    Pallas kernels; returns the device record of the last line."""
    import jax
    from repro.kernels._compat import pallas_interpret

    check(jax.default_backend() == "tpu",
          f"no TPU: JAX backend is {jax.default_backend()!r}")
    forced = os.environ.get("REPRO_PALLAS_INTERPRET", "").strip().lower()
    check(forced in ("", "0", "false", "no", "off"),
          f"REPRO_PALLAS_INTERPRET={forced!r} forces interpret mode")
    check(not pallas_interpret(), "Pallas kernels would run interpreted")
    devs = jax.devices()
    check(len(devs) >= want_count,
          f"{len(devs)} devices, this phase needs {want_count}")
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    log(phase="device", **info)
    return info


# ------------------------------------------------------ golden digests

def golden_phase() -> None:
    """Compiled kernels give the interpret-mode assignments bit for bit."""
    from repro.data.synthetic import powerlaw_hypergraph, reddit_like
    from repro.engines.device import DeviceParams, hype_device_partition
    from repro.engines.superstep import (SuperstepParams,
                                         hype_superstep_partition)

    pl600 = powerlaw_hypergraph(600, 400, seed=11, max_edge=30,
                                max_degree=20)
    cases = [(f"pl600_k{k}_t{t}", pl600, k, t, want)
             for (k, t), want in sorted(GOLD_PL600.items())]
    cases.append(("reddit_0.005_k32_t16", reddit_like(scale=0.005, seed=0),
                  32, 16, GOLD_REDDIT))
    for name, hg, k, t, want in cases:
        got_pd1 = digest(hype_superstep_partition(
            hg, k, SuperstepParams(seed=0, t=t, pipeline_depth=1)))
        got_dev = digest(hype_device_partition(
            hg, k, DeviceParams(seed=0, t=t)))
        log(phase="golden", case=name, want=want,
            hype_superstep_pd1=got_pd1, hype_device=got_dev)
        check(got_pd1 == want, f"{name}: hype_superstep pd1 digest "
                               f"{got_pd1} != {want}")
        check(got_dev == want, f"{name}: hype_device digest "
                               f"{got_dev} != {want}")


# ------------------------------------------------------ full-width runs

def github_graph():
    from repro.data.synthetic import github_like

    t0 = time.perf_counter()
    hg = github_like(scale=GITHUB_SCALE, seed=0)
    adj = hg.vertex_adjacency()
    check(adj is not None, "github adjacency guard tripped")
    shape = (hg.n, hg.m, int(hg.e2v_indices.size))
    if GITHUB_SCALE == 1.0:
        check(shape == GITHUB_SHAPE, f"github shape {shape}")
    log(phase="graph", dataset="github_like", scale=GITHUB_SCALE,
        n=shape[0], m=shape[1], pins=shape[2],
        adjacency_entries=int(adj[1].size),
        build_s=round(time.perf_counter() - t0, 3))
    return hg


def reference_km1(hg) -> int:
    """k-1 of the paper-faithful numpy ``hype`` on the same graph."""
    from repro.core import metrics
    from repro.core.partition_api import partition

    t0 = time.perf_counter()
    km1 = int(metrics.k_minus_1(hg, partition(hg, K, "hype", seed=0), K))
    log(phase="reference", engine="hype", k=K, k_minus_1=km1,
        wall_s=round(time.perf_counter() - t0, 3))
    return km1


def _engine(method: str):
    """(params class, runner) of a fast engine or the stream engine."""
    if method == "hype_stream":
        from repro.core.hype_stream import StreamParams, hype_stream_partition
        return StreamParams, hype_stream_partition
    from repro.core.partition_api import _engine as fast_engine
    return fast_engine(method)


def timed_run(hg, label: str, method: str, km1_ref: int,
              ref_engine: str = "hype", **knobs):
    """One engine twice: cold through ``partition_and_report``, warm
    through the engine's own entry with its counters; k-1 is compared
    with ``km1_ref``, the k-1 of ``ref_engine`` on the same graph.
    Returns the assignment after checking both runs."""
    import numpy as np
    from repro.core import metrics
    from repro.core.partition_api import partition_and_report

    rep, cold = partition_and_report(hg, K, method, seed=0, **knobs)
    params_cls, runner = _engine(method)
    t0 = time.perf_counter()
    if method == "hype_stream":
        warm, state = runner(hg, K, params_cls(seed=0, **knobs),
                             return_state=True)
        stats = state.stats
    else:
        warm, stats = runner(hg, K, params_cls(seed=0, **knobs),
                             return_stats=True)
    warm_s = time.perf_counter() - t0

    n = hg.n
    sizes = metrics.partition_sizes(warm, K)
    km1 = int(metrics.k_minus_1(hg, warm, K))
    ratio = km1 / km1_ref
    line = dict(phase="run", run=label, engine=method, n=n, m=hg.m,
                pins=int(hg.e2v_indices.size), k=K,
                cold_s=round(rep["runtime_s"], 3), warm_s=round(warm_s, 3),
                k_minus_1=km1, km1_ratio=round(ratio, 4),
                km1_ratio_over=ref_engine,
                imbalance=round(float(metrics.vertex_imbalance(warm, K)), 6),
                size_spread=int(sizes.max() - sizes.min()))
    if method == "hype_stream":
        line.update(micro_batches=stats.micro_batches,
                    device_calls=stats.device_calls,
                    plan_micro_batch=stats.plan_micro_batch,
                    retries=stats.retries,
                    vertices_per_s=round(stats.vertices_per_s, 1))
    else:
        line.update(supersteps=stats.supersteps,
                    kernel_calls=stats.kernel_calls,
                    loop_chunks=stats.loop_chunks,
                    plan_rung=stats.plan_rung,
                    peak_bytes_observed=stats.peak_bytes_observed,
                    retries=stats.retries, fallbacks=stats.fallbacks)
    log(**line)

    check(np.array_equal(cold, warm), f"{label}: cold and warm runs differ")
    check(warm.shape == (n,) and warm.min() >= 0 and warm.max() < K,
          f"{label}: incomplete assignment")
    if method == "hype_stream":
        from repro.core.hype_stream import STREAM_KM1_BOUND
        check(sizes.max() <= -(-n // K), f"{label}: over the ceil(n/k) cap")
        check(ratio <= STREAM_KM1_BOUND,
              f"{label}: k-1 ratio {ratio:.3f} > {STREAM_KM1_BOUND}")
        check(stats.retries == 0, f"{label}: retried")
        check(stats.device_calls > 0, f"{label}: no device call")
        check(stats.plan_micro_batch == knobs["micro_batch"],
              f"{label}: memory plan cut the micro-batch")
        return warm
    check(sizes.max() - sizes.min() <= 1, f"{label}: unbalanced {sizes}")
    check(ratio <= OFFLINE_KM1_BOUND,
          f"{label}: k-1 ratio {ratio:.3f} > {OFFLINE_KM1_BOUND}")
    check(stats.retries == 0, f"{label}: retried")
    check(stats.fallbacks == 0, f"{label}: handed off to another engine")
    check(stats.kernel_calls > 0, f"{label}: no kernel call")
    # the host-tile batched engine never plans device memory (-1);
    # every device-image engine must run its full-size plan (rung 0)
    want_rung = -1 if method == "hype_batched" else 0
    check(stats.plan_rung == want_rung,
          f"{label}: plan rung {stats.plan_rung} != {want_rung}")
    if method == "hype_device":
        check(stats.loop_chunks > 0, f"{label}: the device loop never ran")
    return warm


def one_chip() -> dict:
    info = device_info(1)
    golden_phase()
    hg = github_graph()
    km1_ref = reference_km1(hg)
    import numpy as np

    timed_run(hg, "superstep_pd2", "hype_superstep", km1_ref)
    pd1 = timed_run(hg, "superstep_pd1", "hype_superstep", km1_ref,
                    pipeline_depth=1)
    dev = timed_run(hg, "device", "hype_device", km1_ref)
    check(np.array_equal(dev, pd1), "hype_device != hype_superstep pd1")
    timed_run(hg, "batched_t8", "hype_batched", km1_ref, t=8)
    timed_run(hg, "superstep_refine1", "hype_superstep", km1_ref,
              refine_passes=1)
    timed_run(hg, "stream_mb64", "hype_stream", km1_ref, micro_batch=64)
    return info


def four_chips() -> dict:
    """hype_sharded on a four-device mesh against single-chip superstep."""
    from repro.core import metrics
    from repro.core.partition_api import partition_and_report
    from repro.engines import sharded

    info = device_info(4)
    hg = github_graph()
    single, _ = partition_and_report(hg, K, "hype_superstep", seed=0)
    log(phase="run", run="superstep_device0", engine="hype_superstep",
        k=K, cold_s=round(single["runtime_s"], 3),
        k_minus_1=single["k_minus_1"])
    shard = timed_run(hg, "sharded_d4", "hype_sharded",
                      single["k_minus_1"], "hype_superstep", devices=4)
    mesh = sharded._sharded_mesh(4)
    mesh_ids = [d.id for d in mesh.devices.flat]
    image = hg.device_adjacency(mesh=mesh)[1]
    placed = sorted(d.id for d in image.sharding.device_set)
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in mesh.devices.flat]
    log(phase="mesh", devices=[str(d) for d in mesh.devices.flat],
        image_devices=placed, peak_bytes_in_use=peaks)
    check(len(set(mesh_ids)) == 4, f"mesh devices {mesh_ids}")
    check(placed == sorted(mesh_ids), f"image placed on {placed}")
    km1 = int(metrics.k_minus_1(hg, shard, K))
    drift = (km1 - single["k_minus_1"]) / single["k_minus_1"]
    log(phase="compare", sharded_k_minus_1=km1,
        superstep_k_minus_1=single["k_minus_1"], rel_diff=round(drift, 5))
    # one-sided: the sharded engine's conflict rule reorders admissions,
    # which may cut better than the single chip but must not cut worse
    check(drift <= SHARDED_KM1_TOL,
          f"sharded k-1 {km1} vs single-chip {single['k_minus_1']}")
    return info


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only hype_sharded on a four-chip mesh")
    args = ap.parse_args(argv)
    t0 = time.perf_counter()
    info = four_chips() if args.four_chips else one_chip()
    log(phase="done", wall_s=round(time.perf_counter() - t0, 3))
    print(json.dumps({"ok": True, "device": info}), flush=True)


if __name__ == "__main__":
    main()
