"""Device-resident superstep engine suite: validity, determinism,
quality regime, stats counters and the exact-decrement score cache
(repro.engines.superstep; the pipeline driver itself is covered by
test_pipeline.py)."""
import numpy as np
import pytest

from repro.core import metrics, scoring
from repro.core.hype import HypeParams, hype_partition
from repro.core.hypergraph import Hypergraph
from repro.core.partition_api import METHODS, partition
from repro.data.synthetic import powerlaw_hypergraph
from repro.engines.superstep import (SuperstepParams, SuperstepState,
                                     hype_superstep_partition)


@pytest.fixture(scope="module")
def hg():
    return powerlaw_hypergraph(600, 400, seed=11, max_edge=30,
                               max_degree=20)

# ------------------------------------------------------ superstep engine

@pytest.mark.parametrize("k", [2, 5, 16])
def test_superstep_complete_and_balanced(hg, k):
    a = hype_superstep_partition(hg, k, SuperstepParams(seed=0))
    assert a.shape == (hg.n,)
    assert a.dtype == np.int32
    assert a.min() >= 0 and a.max() < k
    sizes = metrics.partition_sizes(a, k)
    assert sizes.max() - sizes.min() <= 1


@pytest.mark.parametrize("method,handoffs", [
    ("hype_superstep", 1),      # superstep -> batched
    ("hype_device", 2),         # device -> superstep -> batched
    ("hype_sharded", 2),        # sharded -> superstep -> batched
])
def test_engine_handoff_counts_fallbacks(monkeypatch, method, handoffs):
    """With no device adjacency image (the hub-expansion guard tripped)
    every device engine hands the run down the engine chain; each
    hand-off must show in ``stats.fallbacks`` so a caller can tell the
    engine it asked for from the one that ran."""
    from repro.core.partition_api import _engine
    hg = powerlaw_hypergraph(300, 200, seed=5, max_edge=20,
                             max_degree=10)
    monkeypatch.setattr(Hypergraph, "vertex_adjacency",
                        lambda *a, **kw: None)
    params_cls, runner = _engine(method)
    a, st = runner(hg, 4, params_cls(seed=0), return_stats=True)
    sizes = metrics.partition_sizes(a, 4)
    assert sizes.max() - sizes.min() <= 1
    assert st.fallbacks == handoffs


def test_superstep_deterministic(hg):
    a1 = hype_superstep_partition(hg, 6, SuperstepParams(seed=3))
    a2 = hype_superstep_partition(hg, 6, SuperstepParams(seed=3))
    np.testing.assert_array_equal(a1, a2)


def test_superstep_registered_in_api(hg):
    assert "hype_superstep" in METHODS
    a = partition(hg, 4, "hype_superstep", seed=0)
    assert a.min() >= 0 and a.max() < 4


def test_superstep_quality_regime(hg):
    """Concurrent k-way growth stays in the sequential engines' quality
    regime (same tolerance as the batched engine's agreement tests)."""
    k = 8
    a_s = hype_superstep_partition(hg, k, SuperstepParams(seed=0))
    a_n = hype_partition(hg, k, HypeParams(seed=0))
    km_s = metrics.k_minus_1(hg, a_s)
    km_n = metrics.k_minus_1(hg, a_n)
    assert km_s <= 1.35 * km_n + 20


def test_superstep_edge_cases():
    hg = Hypergraph.from_edge_lists(6, [[0, 1], [1, 2, 3], []])
    for k in (1, 2, 3, 8):
        a = hype_superstep_partition(hg, k, SuperstepParams(seed=0))
        assert (a >= 0).all() and (a < k).all()
        sizes = np.bincount(a, minlength=min(k, 6))
        assert sizes.max() - sizes.min() <= 1


def test_superstep_stats_counters(hg):
    """The superstep/transfer counters must measure the device traffic."""
    _, stt = hype_superstep_partition(hg, 8, SuperstepParams(seed=0),
                                      return_stats=True)
    assert stt.supersteps > 0
    assert stt.kernel_calls == stt.supersteps
    assert stt.kernel_rows > 0
    assert stt.device_image_bytes > 0
    assert stt.host_to_device_bytes > 0
    assert stt.cache_invalidations > 0
    assert stt.host_rows == 0            # no host-scoring fallback path
    # per-superstep traffic is ids + small bias buffers, not (B, L) tiles
    per_step = (stt.host_to_device_bytes / stt.supersteps)
    assert per_step < 8 * 64 * scoring.L_BUCKETS[-1]


def test_superstep_cache_exact_after_admissions():
    """Property check for decrement-based invalidation: after ANY
    admission sequence — device-selected winners (clipped decrements +
    host-queued tails) and host injections alike — every cached score
    equals a fresh ``batched_dext_adj`` recompute: the stale-score
    drift the old per-phase wipe was hiding cannot exist."""
    for seed in (0, 1, 2):
        hg = powerlaw_hypergraph(300, 200, seed=10 + seed, max_edge=18,
                                 max_degree=12)
        k, R, t = 4, 8, 2
        rng = np.random.default_rng(seed)
        st = SuperstepState(hg, k, SuperstepParams(seed=seed))
        fringe = np.full((k, 1), -1, np.int32)
        empty_pool = np.full((k, 4), -1, np.int32)
        acc = np.zeros(k, dtype=np.int64)
        targets = np.full(k, hg.n, dtype=np.int64)
        for step in range(10):
            # score a random batch of never-scored vertices; the device
            # admits up to a random per-phase cap of them (cap 0 phases
            # exercise the selection-without-admission path) ...
            cand = np.flatnonzero(~st.cache_scored & (st.assignment < 0))
            fresh = np.full((k, R), -1, np.int32)
            if cand.size:
                pick = rng.choice(cand, size=min(k * R, cand.size),
                                  replace=False)
                fresh.reshape(-1)[:pick.size] = pick
            bias = np.where(fresh >= 0, 0, np.inf).astype(np.float32)
            cap = rng.integers(0, t + 1, size=k)
            tgt = (acc + cap).astype(np.int32)
            handle = st.dispatch(fresh, bias, empty_pool, fringe,
                                 fresh[fresh >= 0].astype(np.int64),
                                 tgt, 32, t)
            st.harvest(handle, acc, targets)
            # ... then admit a random batch by host injection too
            un = np.flatnonzero(st.assignment < 0)
            if un.size == 0:
                break
            vs = rng.choice(un, size=min(int(rng.integers(1, 8)),
                                         un.size), replace=False)
            g = int(rng.integers(0, k))
            st.assign_now(vs, g)
            acc[g] += vs.size
        while st.delta_ids or st.pending_dirty:    # flush tails + deltas
            handle = st.dispatch(np.full((k, 1), -1, np.int32),
                                 np.full((k, 1), np.inf, np.float32),
                                 np.full((k, 1), -1, np.int32), fringe,
                                 np.empty(0, dtype=np.int64),
                                 acc.astype(np.int32), 32, 1)
            st.harvest(handle, acc, targets)
        cache = np.asarray(st.dev_cache, dtype=np.float64)
        # rows wider than the run's tile width are truncated hubs parked
        # at ~1e12 — the exactness contract covers everything else
        scored = np.flatnonzero(st.cache_scored & (st.deg <= st.tile_l))
        assert scored.size > 50
        ref = scoring.batched_dext_adj(st.adj, scored,
                                       np.zeros(hg.n, dtype=bool),
                                       st.assignment)
        assert (ref > 0).any()           # the recompute is not trivial
        np.testing.assert_allclose(cache[scored], ref)
        # device/host assignment + totals parity after the flush
        np.testing.assert_array_equal(np.asarray(st.dev_assign),
                                      st.assignment)
        np.testing.assert_array_equal(
            np.asarray(st.dev_acc),
            np.bincount(st.assignment[st.assignment >= 0],
                        minlength=k))


def test_superstep_cross_phase_cache_reuse():
    """Scores survive phase completion: when a finished phase releases
    its pool and another phase redraws those vertices, they are cache
    hits — impossible under the old per-phase wipe."""
    for seed in range(3):
        hg = powerlaw_hypergraph(300, 500, seed=21 + seed, max_edge=10,
                                 max_degree=30)
        _, stt = hype_superstep_partition(
            hg, 24, SuperstepParams(seed=seed, pool_cap=16),
            return_stats=True)
        assert stt.cache_hits > 0


