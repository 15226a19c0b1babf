"""Unit + property tests for the hypergraph structure and metrics."""
import numpy as np
import pytest
from _hyp_compat import given, settings, st

from repro.core.hypergraph import Hypergraph
from repro.core import metrics


def tiny():
    # fig-4-like: three edges, one big
    return Hypergraph.from_edge_lists(6, [[0, 1, 2, 3], [3, 4], [4, 5], [0, 5]])


def test_csr_roundtrip():
    hg = tiny()
    hg.validate()
    assert hg.n == 6 and hg.m == 4
    assert hg.n_pins == 10
    assert list(hg.edge_pins(1)) == [3, 4]
    assert set(hg.vertex_edges(3)) == {0, 1}
    assert set(hg.neighbors(3)) == {0, 1, 2, 4}


def test_duplicate_pins_removed():
    hg = Hypergraph.from_pins(3, 1, np.array([0, 0, 1, 2]), np.array([0, 0, 0, 0]))
    assert hg.n_pins == 3
    assert hg.edge_sizes[0] == 3


def test_flip_involution():
    hg = tiny()
    f2 = hg.flip().flip()
    assert f2.n == hg.n and f2.m == hg.m
    np.testing.assert_array_equal(np.sort(f2.edge_pins(0)), np.sort(hg.edge_pins(0)))


def test_k_minus_1_hand_checked():
    hg = tiny()
    # all in one partition
    assert metrics.k_minus_1(hg, np.zeros(6, np.int32)) == 0
    # split {0,1,2} | {3,4,5}: e0 spans 2 -> 1; e1 spans 1... pins(e1)={3,4} both p1 -> 0
    a = np.array([0, 0, 0, 1, 1, 1], np.int32)
    # e0={0,1,2,3} spans {0,1} -> 1; e1={3,4} -> 0; e2={4,5} -> 0; e3={0,5} spans -> 1
    assert metrics.k_minus_1(hg, a) == 2
    assert metrics.hyperedge_cut(hg, a) == 2
    assert metrics.sum_external_degree(hg, a) == 4


def test_imbalance():
    a = np.array([0, 0, 0, 1], np.int32)
    assert metrics.vertex_imbalance(a, 2) == pytest.approx((3 - 1) / 3)
    assert metrics.vertex_imbalance(np.array([0, 1], np.int32), 2) == 0.0


def _corrupt(hg, **overrides):
    """Rebuild ``hg`` with raw (possibly invalid) arrays swapped in."""
    fields = dict(n=hg.n, m=hg.m, v2e_indptr=hg.v2e_indptr,
                  v2e_indices=hg.v2e_indices, e2v_indptr=hg.e2v_indptr,
                  e2v_indices=hg.e2v_indices)
    fields.update(overrides)
    return Hypergraph(**fields)


@pytest.mark.parametrize("corruption,match", [
    # each case violates exactly one validate() invariant
    (lambda hg: _corrupt(hg, v2e_indptr=hg.v2e_indptr[:-1]),
     "v2e_indptr shape"),
    (lambda hg: _corrupt(hg, e2v_indptr=hg.e2v_indptr[:-1]),
     "e2v_indptr shape"),
    (lambda hg: _corrupt(hg, v2e_indices=hg.v2e_indices[:-1],
                         e2v_indices=hg.e2v_indices[:-1]),
     "v2e_indptr\\[-1\\]"),
    (lambda hg: _corrupt(
        hg, e2v_indptr=np.concatenate([hg.e2v_indptr[:-1],
                                       [hg.n_pins + 1]])),
     "e2v_indptr\\[-1\\]"),
    (lambda hg: _corrupt(
        hg, v2e_indices=np.concatenate([hg.v2e_indices,
                                        hg.v2e_indices[:1]]),
        v2e_indptr=hg.v2e_indptr + (np.arange(hg.n + 1) >= 1)),
     "pin-count mismatch"),
    (lambda hg: _corrupt(
        hg, e2v_indices=np.where(np.arange(hg.n_pins) == 0, -1,
                                 hg.e2v_indices)),
     "negative vertex id"),
    (lambda hg: _corrupt(
        hg, e2v_indices=np.where(np.arange(hg.n_pins) == 0, hg.n,
                                 hg.e2v_indices)),
     "vertex id .* out of range"),
    (lambda hg: _corrupt(
        hg, v2e_indices=np.where(np.arange(hg.n_pins) == 0, -2,
                                 hg.v2e_indices)),
     "negative edge id"),
    (lambda hg: _corrupt(
        hg, v2e_indices=np.where(np.arange(hg.n_pins) == 0, hg.m + 3,
                                 hg.v2e_indices)),
     "edge id .* out of range"),
])
def test_validate_raises_on_corruption(corruption, match):
    """validate() must RAISE (not assert — `python -O` strips asserts,
    silently no-opping validation) on every corrupted invariant."""
    hg = tiny()
    hg.validate()                       # sane baseline passes
    with pytest.raises(ValueError, match=match):
        corruption(hg).validate()


# ------------------------------------------------------- metrics / spans

def test_metrics_explicit_k_equivalence():
    """Threading k and sharing one spans computation must not change any
    metric — including when high partitions are unoccupied (the old
    keying hashed on assignment.max() + 2)."""
    hg = tiny()
    a = np.array([0, 0, 0, 1, 1, 1], np.int32)
    for k in (2, 3, 7):                 # k=3,7: partitions 2.. unoccupied
        spans = metrics.spans_per_edge(hg, a, k)
        np.testing.assert_array_equal(spans, metrics.spans_per_edge(hg, a))
        assert metrics.k_minus_1(hg, a, k) == metrics.k_minus_1(hg, a) == 2
        assert metrics.hyperedge_cut(hg, a, k) == 2
        assert metrics.sum_external_degree(hg, a, k) == 4
        assert metrics.k_minus_1(hg, a, k, spans=spans) == 2
        assert metrics.hyperedge_cut(hg, a, k, spans=spans) == 2
        assert metrics.sum_external_degree(hg, a, k, spans=spans) == 4
        rep = metrics.all_metrics(hg, a, k)
        assert rep["k_minus_1"] == 2 and rep["hyperedge_cut"] == 2
        assert rep["soed"] == 4


def test_metrics_reject_out_of_range_k():
    hg = tiny()
    a = np.array([0, 0, 0, 1, 1, 1], np.int32)
    with pytest.raises(ValueError, match=">= k"):
        metrics.k_minus_1(hg, a, 1)


def test_metrics_reject_incomplete_assignment():
    hg = tiny()
    a = np.array([0, 0, 0, 1, 1, -1], np.int32)
    with pytest.raises(ValueError, match="complete"):
        metrics.k_minus_1(hg, a, 2)


@st.composite
def hypergraphs(draw):
    n = draw(st.integers(min_value=2, max_value=40))
    m = draw(st.integers(min_value=1, max_value=30))
    n_pins = draw(st.integers(min_value=1, max_value=120))
    vs = draw(st.lists(st.integers(0, n - 1), min_size=n_pins, max_size=n_pins))
    es = draw(st.lists(st.integers(0, m - 1), min_size=n_pins, max_size=n_pins))
    return Hypergraph.from_pins(n, m, np.array(vs), np.array(es))


@given(hypergraphs(), st.integers(min_value=1, max_value=8), st.integers(0, 3))
@settings(max_examples=50, deadline=None)
def test_property_metric_bounds(hg, k, seed):
    """(k-1) bounds: 0 <= k-1 <= sum(min(|e|, k) - 1); flip preserves pins."""
    rng = np.random.default_rng(seed)
    a = rng.integers(0, k, size=hg.n).astype(np.int32)
    km1 = metrics.k_minus_1(hg, a)
    sizes = hg.edge_sizes
    ub = int(np.sum(np.maximum(np.minimum(sizes, k) - 1, 0)))
    assert 0 <= km1 <= ub
    assert metrics.hyperedge_cut(hg, a) <= km1 or km1 == 0
    assert hg.flip().n_pins == hg.n_pins
    hg.flip().validate()


# ------------------------------------------------ index-dtype boundaries

def test_csr_index_dtype_boundary():
    """The int32->int64 flip happens exactly at max(n, m) == 2**31 —
    tested on the extracted decision function, no giant allocations."""
    from repro.core.hypergraph import csr_index_dtype
    lim = 2**31
    assert csr_index_dtype(10, 10) is np.int32
    assert csr_index_dtype(lim - 1, 1) is np.int32
    assert csr_index_dtype(1, lim - 1) is np.int32
    assert csr_index_dtype(lim, 1) is np.int64
    assert csr_index_dtype(1, lim) is np.int64
    assert csr_index_dtype(lim + 7, lim + 7) is np.int64


def test_from_pins_uses_decision_dtype():
    hg = tiny()
    from repro.core.hypergraph import csr_index_dtype
    want = csr_index_dtype(hg.n, hg.m)
    assert hg.v2e_indices.dtype == want
    assert hg.e2v_indices.dtype == want
    # indptr stays int64 regardless: pin counts overflow before ids do
    assert hg.v2e_indptr.dtype == np.int64
    assert hg.e2v_indptr.dtype == np.int64


def test_device_ptr_dtype_boundary():
    """Device indptr narrows on the flat *indices* length (pin count),
    flipping at 2**31 like the host decision."""
    import jax.numpy as jnp
    from repro.core.hypergraph import device_ptr_dtype
    lim = 2**31
    assert device_ptr_dtype(0) is jnp.int32
    assert device_ptr_dtype(lim - 1) is jnp.int32
    assert device_ptr_dtype(lim) is jnp.int64
    assert device_ptr_dtype(lim + 1) is jnp.int64


def test_device_adjacency_ptr_dtype_propagation():
    """device_adjacency must upload its indptr with the dtype the
    decision function picks for the actual indices length."""
    import jax.numpy as jnp
    from repro.core.hypergraph import device_ptr_dtype
    hg = tiny()
    dev = hg.device_adjacency()
    assert dev is not None
    indptr_dev, indices_dev = dev
    host = hg.vertex_adjacency(80_000_000)
    assert indptr_dev.dtype == device_ptr_dtype(host[1].size)
    assert indptr_dev.dtype == jnp.int32          # tiny graph fits
    np.testing.assert_array_equal(np.asarray(indptr_dev), host[0])


def _adjacency_oracle(hg):
    """The single-pass formulation: one global unique over every pair."""
    from repro.core.scoring import gather_csr_rows
    sizes = hg.edge_sizes.astype(np.int64)
    edge_of_pin = np.repeat(np.arange(hg.m, dtype=np.int64), sizes)
    nbr, owner_pin = gather_csr_rows(hg.e2v_indptr, hg.e2v_indices,
                                     edge_of_pin)
    nbr = nbr.astype(np.int64)
    owner = hg.e2v_indices[owner_pin].astype(np.int64)
    keys = np.unique(owner * np.int64(hg.n) + nbr)
    ov, nb = keys // hg.n, keys % hg.n
    keep = ov != nb
    ov, nb = ov[keep], nb[keep]
    indptr = np.zeros(hg.n + 1, dtype=np.int64)
    indptr[1:] = np.cumsum(np.bincount(ov, minlength=hg.n))
    return indptr, nb.astype(np.int32)


def _adjacency_graphs():
    from repro.data.synthetic import powerlaw_hypergraph
    return {
        "powerlaw": powerlaw_hypergraph(600, 400, seed=3, max_edge=30,
                                        max_degree=20),
        # vertices 0, 4 and 7 in no edge; edges 1 and 3 empty
        "isolated_and_empty": Hypergraph.from_edge_lists(
            8, [[1, 2, 3], [], [5, 6, 2], [], [3, 5]]),
        "one_edge_holds_all": Hypergraph.from_edge_lists(
            9, [list(range(9)), [2, 4]]),
        # 0, 1 and 2 share three edges: their pairs repeat across edges
        "shared_edges": Hypergraph.from_edge_lists(
            5, [[0, 1, 2], [2, 1, 0, 3], [4, 0, 1, 2], [1, 3]]),
        "one_vertex": Hypergraph.from_edge_lists(1, [[0], [0], []]),
    }


@pytest.mark.parametrize("workers", [1, 3, 64])
@pytest.mark.parametrize("chunk_pairs", [1 << 40, 7, 1])
@pytest.mark.parametrize("graph", sorted(_adjacency_graphs()))
def test_vertex_adjacency_matches_global_unique(monkeypatch, graph,
                                                chunk_pairs, workers):
    """The chunked build equals one global unique, bit for bit, for any
    chunk size and worker count (64 workers: more than chunks)."""
    from repro.core import hypergraph
    monkeypatch.setattr(hypergraph, "_ADJ_CHUNK_PAIRS", chunk_pairs)
    monkeypatch.setattr(hypergraph.os, "sched_getaffinity",
                        lambda pid: set(range(workers)))
    hg = _adjacency_graphs()[graph]
    want = _adjacency_oracle(hg)
    got = hg.vertex_adjacency()
    assert got[0].dtype == np.int64 and got[1].dtype == np.int32
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])
    chunks, used = hg._adj_build
    assert chunks >= 1 and 1 <= used <= min(workers, chunks)
    if chunk_pairs == 1 << 40:
        assert chunks == 1
    # a memo hit builds nothing
    assert hg.vertex_adjacency() is got
    assert hg._adj_build == (0, 0)


def test_vertex_adjacency_guard_returns_none():
    hg = Hypergraph.from_edge_lists(6, [[0, 1, 2, 3], [3, 4, 5]])
    assert hg.vertex_adjacency(max_expanded=24) is None     # 16 + 9 pairs
    assert hg._adj_build == (0, 0)
    assert hg.vertex_adjacency(max_expanded=25) is not None
