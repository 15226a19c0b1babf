"""Mesh-sharded superstep engine: validity, determinism, parity with the
single-device superstep engine, lowest-phase-wins conflict resolution,
collective counters, and exactness of the replicated score cache
(device-side decrements + host-queued tails)."""
import numpy as np
import pytest

from repro.core import metrics, scoring
from repro.engines import sharded
from repro.engines.sharded import (ShardedParams, ShardedState,
                                   hype_sharded_partition)
from repro.engines.superstep import (SuperstepParams,
                                     hype_superstep_partition)
from repro.core.hypergraph import Hypergraph
from repro.core.partition_api import METHODS, partition
from repro.data.synthetic import powerlaw_hypergraph


def _devices() -> int:
    import jax
    return len(jax.devices())


needs_multi = pytest.mark.skipif(
    "_devices() < 2",
    reason="needs >= 2 devices (XLA_FLAGS=--xla_force_host_platform_"
           "device_count, set by tests/conftest.py)")


@pytest.fixture(scope="module")
def hg():
    return powerlaw_hypergraph(600, 400, seed=11, max_edge=30,
                               max_degree=20)


# ------------------------------------------------------------- validity

@needs_multi
@pytest.mark.parametrize("k", [2, 5, 16])
def test_sharded_complete_and_balanced(hg, k):
    a = hype_sharded_partition(hg, k, ShardedParams(seed=0, devices=2))
    assert a.shape == (hg.n,)
    assert a.dtype == np.int32
    assert a.min() >= 0 and a.max() < k
    sizes = metrics.partition_sizes(a, k)
    assert sizes.max() - sizes.min() <= 1


@needs_multi
def test_sharded_deterministic(hg):
    a1 = hype_sharded_partition(hg, 6, ShardedParams(seed=3, devices=2))
    a2 = hype_sharded_partition(hg, 6, ShardedParams(seed=3, devices=2))
    np.testing.assert_array_equal(a1, a2)


def test_sharded_registered_in_api(hg):
    assert "hype_sharded" in METHODS
    a = partition(hg, 4, "hype_sharded", seed=0)
    assert a.min() >= 0 and a.max() < 4


def test_sharded_single_device_degenerates(hg):
    """devices=1 must still satisfy the full contract (no mesh needed)."""
    a = hype_sharded_partition(hg, 5, ShardedParams(seed=0, devices=1))
    sizes = metrics.partition_sizes(a, 5)
    assert sizes.max() - sizes.min() <= 1


def test_sharded_rejects_more_devices_than_exist(hg):
    """Asking for a mesh wider than the machine raises instead of
    silently running on fewer devices."""
    with pytest.raises(ValueError, match="devices"):
        hype_sharded_partition(
            hg, 16, ShardedParams(seed=0, devices=_devices() + 1))


def test_sharded_edge_cases():
    hg = Hypergraph.from_edge_lists(6, [[0, 1], [1, 2, 3], []])
    for k in (1, 2, 3, 8):
        a = hype_sharded_partition(hg, k, ShardedParams(seed=0))
        assert (a >= 0).all() and (a < k).all()
        sizes = np.bincount(a, minlength=min(k, 6))
        assert sizes.max() - sizes.min() <= 1


# ------------------------------------------------------------- parity

@needs_multi
def test_sharded_quality_parity_small(hg):
    """2- and 4-device runs stay in the single-device quality regime."""
    k = 16
    a_s = hype_superstep_partition(hg, k, SuperstepParams(seed=0))
    km_s = metrics.k_minus_1(hg, a_s)
    for d in (2, min(4, _devices())):
        a = hype_sharded_partition(hg, k, ShardedParams(seed=0,
                                                        devices=d))
        sizes = metrics.partition_sizes(a, k)
        assert sizes.max() - sizes.min() <= 1
        km = metrics.k_minus_1(hg, a)
        assert km <= 1.15 * km_s + 20


@needs_multi
def test_sharded_km1_within_5pct_at_scale():
    """Acceptance bound at benchmark scale: the quick reddit generator at
    k=32, sharded over 2 and 4 devices, must land within 5% of the
    single-device superstep engine's k-1 (same seed, same t)."""
    from repro.data.synthetic import reddit_like
    hg = reddit_like(scale=0.01, seed=0)
    k, t = 32, 16
    a_ref = hype_superstep_partition(hg, k, SuperstepParams(seed=0, t=t))
    km_ref = metrics.k_minus_1(hg, a_ref)
    for d in (2, min(4, _devices())):
        a = hype_sharded_partition(
            hg, k, ShardedParams(seed=0, t=t, devices=d))
        km = metrics.k_minus_1(hg, a)
        assert km <= 1.05 * km_ref, (d, km, km_ref)


# ------------------------------------------------- conflict resolution

@needs_multi
def test_conflict_lowest_phase_wins_program():
    """Two phases (on different devices) proposing the same vertex in one
    superstep: the lowest phase id must win, the loser gets nothing, and
    the conflict is counted — deterministically."""
    import jax.numpy as jnp
    hg = powerlaw_hypergraph(120, 90, seed=3, max_edge=12, max_degree=8)
    adj = hg.vertex_adjacency()
    dev = hg.device_adjacency()
    n = hg.n
    v = int(np.argmax(np.diff(adj[0])[: n // 2]))    # any real vertex
    D, kL, R, t = 2, 1, 4, 2
    kG = D * kL
    fresh = np.full((kG, R), -1, np.int32)
    fresh[0, 0] = v
    fresh[1, 0] = v                                  # phase 1, device 1
    bias = np.where(fresh >= 0, 0, np.inf).astype(np.float32)
    pool = np.full((kG, 4), -1, np.int32)
    fringe = np.full((kG, 1), -1, np.int32)
    targets = np.full(kG, t, np.int32)       # cap = t admissions each
    assign = jnp.full((n,), -1, jnp.int32)
    cache = jnp.full((n,), -1.0, jnp.float32)
    acc = jnp.zeros((kG,), jnp.int32)
    empty_i = np.full(4, -1, np.int32)
    poison = jnp.zeros((1,), jnp.int32)
    (a2, c2, acc2, poison2, winners, ncf,
     n_stale) = sharded.sharded_superstep_device(
        dev[0], dev[1], assign, cache, acc, poison, empty_i,
        np.zeros(4, np.int32), empty_i, np.zeros(4, np.float32),
        fresh, bias, pool, fringe, targets, np.zeros(1, np.int32),
        num_devices=D, group_l=kL, tile_l=32, select_k=t,
        interpret=True)
    assert int(np.asarray(poison2)[0]) == 0          # finite scores
    winners = np.asarray(winners)
    assert winners[0, 0] == v                        # lowest phase won
    assert v not in winners[1]                       # loser redraws later
    assert int(ncf) == 1
    assert int(n_stale) == 0                         # nothing in flight
    assert int(np.asarray(a2)[v]) == 0
    assert int(np.asarray(acc2)[0]) >= 1             # winner counted


@needs_multi
def test_sharded_conflicts_happen_and_are_counted(hg):
    """Device groups draw pools independently, so overlapping proposals
    must occur on a clustered graph — and be resolved, not double-
    assigned (completeness + balance above already guarantee that)."""
    _, st = hype_sharded_partition(hg, 8, ShardedParams(seed=0,
                                                        devices=2),
                                   return_stats=True)
    assert st.admission_conflicts > 0


# ------------------------------------------------- collective counters

@needs_multi
def test_sharded_collective_counters(hg):
    _, st = hype_sharded_partition(hg, 8, ShardedParams(seed=0,
                                                        devices=2),
                                   return_stats=True)
    assert st.supersteps > 0
    assert st.collectives == st.supersteps
    assert st.collective_bytes > 0
    assert st.collective_bytes % st.collectives == 0
    assert st.host_rows == 0             # every score is device-side
    assert st.device_image_bytes > 0     # counted once per replica
    # the gathered payload is ids + scores, not (n,)-sized state
    per_step = st.collective_bytes / st.collectives
    assert per_step < 4 * hg.n


# --------------------------------------------------- cache exactness

@needs_multi
def test_sharded_cache_exact_after_admissions():
    """The replicated cache stays *exact* under mixed admission paths:
    device-selected winners (clipped decrement + host-queued tails) and
    host injections. After any sequence, every cached score equals a
    fresh ``batched_dext_adj`` recompute."""
    for seed in (0, 1):
        hg = powerlaw_hypergraph(300, 200, seed=10 + seed, max_edge=18,
                                 max_degree=12)
        k, D, R, t = 4, 2, 8, 2
        rng = np.random.default_rng(seed)
        p = ShardedParams(seed=seed, t=t, rows=R, devices=D)
        st = ShardedState(hg, k, p, D)
        fringe = np.full((k, 1), -1, np.int32)
        empty_pool = np.full((k, 4), -1, np.int32)
        acc = np.zeros(k, dtype=np.int64)
        targets = np.full(k, hg.n, dtype=np.int64)
        # make sure the tail path runs: the widest vertex, if wider than
        # the run's tile, must be admitted at least once
        wide_v = int(np.argmax(st.deg))
        for step in range(10):
            cand = np.flatnonzero(~st.cache_scored & (st.assignment < 0))
            fresh = np.full((k, R), -1, np.int32)
            if cand.size:
                pick = rng.choice(cand, size=min(k * R - 1, cand.size),
                                  replace=False)
                if st.assignment[wide_v] < 0 \
                        and wide_v not in pick:
                    pick = np.concatenate([[wide_v], pick])
                fresh.reshape(-1)[:pick.size] = pick
            # zero bias everywhere: wide rows stay admissible, so the
            # clipped-decrement + tail machinery actually executes
            bias = np.where(fresh >= 0, 0, np.inf).astype(np.float32)
            cap = rng.integers(0, t + 1, size=k)
            tgt = (acc + cap).astype(np.int32)
            handle = st.dispatch(fresh, bias, empty_pool, fringe,
                                 fresh[fresh >= 0].astype(np.int64),
                                 tgt, 32, t)
            st.harvest(handle, acc, targets)   # mirror, like the runner
            # host-injection path too
            un = np.flatnonzero(st.assignment < 0)
            if un.size and step % 3 == 0:
                vs = rng.choice(un, size=min(3, un.size), replace=False)
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
        scored = np.flatnonzero(st.cache_scored & (st.deg <= st.tile_l))
        assert scored.size > 50
        ref = scoring.batched_dext_adj(st.adj, scored,
                                       np.zeros(hg.n, dtype=bool),
                                       st.assignment)
        assert (ref > 0).any()
        np.testing.assert_allclose(cache[scored], ref)
        # device/host assignment + totals parity after the flush
        np.testing.assert_array_equal(np.asarray(st.dev_assign),
                                      st.assignment)
        np.testing.assert_array_equal(
            np.asarray(st.dev_acc),
            np.bincount(st.assignment[st.assignment >= 0],
                        minlength=k))


# ------------------------------------------------- kernel shard offsets

def test_score_select_shard_matches_full():
    """The per-shard phase-group offset wrapper must reproduce the full
    fused call on the corresponding slice."""
    import jax.numpy as jnp
    from repro.kernels.hype_score.ops import (hype_score_select,
                                              hype_score_select_shard)
    rng = np.random.default_rng(0)
    G, R, L, P, s, t = 4, 3, 32, 5, 4, 2
    nbrs = rng.integers(-1, 50, size=(G, R, L)).astype(np.int32)
    fringe = rng.integers(-1, 50, size=(G, s)).astype(np.int32)
    bias = np.zeros((G, R), np.float32)
    prev = np.where(rng.random((G, P)) < 0.5,
                    rng.random((G, P)) * 10, np.inf).astype(np.float32)
    full = hype_score_select(jnp.asarray(nbrs), jnp.asarray(fringe),
                             jnp.asarray(bias), jnp.asarray(prev),
                             select_k=t, interpret=True)
    for off, gl in ((0, 2), (2, 2), (1, 3)):
        shard = hype_score_select_shard(
            jnp.asarray(nbrs[off:off + gl]), jnp.asarray(fringe),
            jnp.asarray(bias), jnp.asarray(prev), select_k=t,
            shard_offset=off, interpret=True)
        for a, b in zip(shard, (full[0][off:off + gl],
                                full[1][off:off + gl],
                                full[2][off:off + gl])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
