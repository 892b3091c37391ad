"""Correctness of the paper's core: PKT and every baseline vs the oracle."""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st, HealthCheck

from repro.graphs.csr import build_csr, edges_from_arrays, relabel, \
    degeneracy_order
from repro.graphs.datasets import (paper_fig1_edges, k4_edges, triangle_edges,
                                   path_edges, karate_like_edges)
from repro.graphs.gen import rmat_edges, ring_of_cliques_edges
from repro.core import (pkt, truss_pkt, truss_wc, truss_ros, truss_numpy,
                        truss_trilist, compute_support, compute_support_ros,
                        triangle_count)
from repro.kernels.ops import compute_support_kernel

SETTINGS = dict(max_examples=25, deadline=None,
                suppress_health_check=[HealthCheck.too_slow])


def _er_edges(n, p, seed):
    rng = np.random.default_rng(seed)
    mask = rng.random((n, n)) < p
    src, dst = np.nonzero(np.triu(mask, 1))
    return edges_from_arrays(src, dst, n)


# ---------------------------------------------------------------- fixed ----

def test_paper_fig1():
    """The paper's Figure 1 example: two trussness-2 edges, rest 3."""
    g = build_csr(paper_fig1_edges())
    t = pkt(g).trussness
    assert sorted(t) == [2, 2] + [3] * 10


@pytest.mark.parametrize("edges_fn,expected", [
    (triangle_edges, [3, 3, 3]),
    (k4_edges, [4] * 6),
    (path_edges, [2] * 4),
])
def test_small_known(edges_fn, expected):
    g = build_csr(edges_fn())
    assert list(pkt(g).trussness) == expected


def test_ring_of_cliques():
    """Intra-clique edges have trussness = clique size; bridges 2."""
    k = 6
    g = build_csr(ring_of_cliques_edges(5, k))
    t = pkt(g).trussness
    n_bridge = 5
    assert (t == 2).sum() == n_bridge
    assert (t == k).sum() == g.m - n_bridge


# ----------------------------------------------------------- vs oracles ----

@pytest.mark.parametrize("seed", range(6))
def test_pkt_matches_oracle_er(seed):
    E = _er_edges(10 + 7 * seed, 0.1 + 0.06 * seed, seed)
    if E.size == 0:
        return
    g = build_csr(E)
    ref = truss_numpy(g.El)
    assert np.array_equal(pkt(g).trussness, ref)
    assert np.array_equal(truss_wc(g), ref)
    assert np.array_equal(truss_ros(g), ref)
    assert np.array_equal(truss_trilist(g), ref)


def test_pkt_dense_mode_and_chunks():
    E = _er_edges(40, 0.3, 3)
    g = build_csr(E)
    ref = truss_numpy(g.El)
    for mode in ("chunked", "dense"):
        for chunk in (16, 128, 1 << 14):
            assert np.array_equal(pkt(g, mode=mode, chunk=chunk).trussness,
                                  ref), (mode, chunk)


def test_reorder_invariance():
    """Trussness is label-invariant; KCO reorder must not change results."""
    E = _er_edges(50, 0.2, 4)
    t_nat = truss_pkt(E, reorder=False)
    t_kco = truss_pkt(E, reorder=True)
    assert np.array_equal(t_nat, t_kco)


def test_karate_like_all_algorithms():
    g = build_csr(karate_like_edges())
    ref = truss_numpy(g.El)
    assert np.array_equal(pkt(g).trussness, ref)
    assert np.array_equal(truss_trilist(g), ref)


def test_rmat_medium_consistency():
    """PKT == triangle-list on a skewed RMAT graph (oracle too slow here)."""
    E = rmat_edges(9, edge_factor=6, seed=1)
    perm = degeneracy_order(E, int(E.max()) + 1)
    g = build_csr(relabel(E, perm))
    t1 = pkt(g).trussness
    t2 = truss_trilist(g)
    assert np.array_equal(t1, t2)


# ------------------------------------------- input-validation bugfix sweep ----

def test_truss_pkt_swapped_and_duplicate_rows_align():
    """truss_pkt used to silently return wrong trussness for
    endpoint-swapped or duplicate rows; now rows are canonicalized like
    TrussEngine.submit and results align to the caller's rows."""
    canon = np.array([[0, 1], [0, 2], [1, 2], [2, 3]], np.int64)
    messy = np.array([[1, 0], [0, 1], [2, 1], [2, 0], [3, 2]], np.int64)
    t_canon = truss_pkt(canon)
    t_messy = truss_pkt(messy)
    assert list(t_messy) == [t_canon[0], t_canon[0], t_canon[2],
                             t_canon[1], t_canon[3]]


def test_truss_pkt_rejects_malformed_input():
    with pytest.raises(ValueError, match="self-loop"):
        truss_pkt(np.array([[1, 1]], np.int64))
    with pytest.raises(ValueError, match="negative"):
        truss_pkt(np.array([[-1, 2]], np.int64))
    with pytest.raises(ValueError, match=r"\(k, 2\)"):
        truss_pkt(np.array([[0, 1, 2]], np.int64))
    with pytest.raises(ValueError, match="integer"):
        truss_pkt(np.array([[0.5, 1.0]]))
    # int64 key-packing / int32 CSR overflow guard on huge vertex ids
    with pytest.raises(ValueError, match="exceeds"):
        truss_pkt(np.array([[0, 2**31]], np.int64))


def test_align_to_input_missing_edge_raises():
    """align_to_input used to misalign silently (searchsorted insertion
    point) or IndexError (pos == len) for edges absent from g.El."""
    from repro.core.pkt import align_to_input, pkt
    E = np.array([[0, 1], [0, 2], [1, 2]], np.int64)
    g = build_csr(E)
    t = pkt(g).trussness
    # absent edge whose key falls between present keys
    with pytest.raises(ValueError, match=r"not present.*\(1, 3\)"):
        align_to_input(t, g, np.array([[1, 3]], np.int64), 4)
    # absent edge whose key sorts past the end (old IndexError path)
    with pytest.raises(ValueError, match="not present"):
        align_to_input(t, g, np.array([[3, 4]], np.int64), 5)
    # empty graph
    g0 = build_csr(np.zeros((0, 2), np.int64))
    with pytest.raises(ValueError, match="empty graph"):
        align_to_input(np.zeros(0), g0, np.array([[0, 1]], np.int64), 2)


def test_edge_key_packing_guard():
    from repro.graphs.csr import MAX_PACK_N, edge_keys
    lo = np.array([0], np.int64)
    hi = np.array([1], np.int64)
    assert edge_keys(lo, hi, 10)[0] == 1
    with pytest.raises(ValueError, match="overflows"):
        edge_keys(lo, hi, MAX_PACK_N + 1)


# -------------------------------------------------------------- support ----

@pytest.mark.parametrize("seed", range(4))
def test_support_equals_naive(seed):
    from repro.core.ref import support_naive
    E = _er_edges(12 + 9 * seed, 0.25, 10 + seed)
    if E.size == 0:
        return
    g = build_csr(E)
    S = compute_support(g)
    S_ros = compute_support_ros(g)
    S_naive = support_naive(g.El, np.ones(g.m, bool))
    assert np.array_equal(S, S_naive)
    assert np.array_equal(S_ros, S_naive)
    assert np.array_equal(compute_support_kernel(g), S_naive)


def test_oriented_search_bound_covers_out_degrees_and_holds_under_churn():
    """The support search's bound covers every N⁺ range, and on the scale-11
    Graph Challenge Kronecker graph it stays put while batches of 64 edges
    leave, though the largest out-degree crosses 64 (the bound is a static
    argument: a change compiles a new program)."""
    from repro.core import support as support_mod

    rng = np.random.default_rng(0)
    E = rmat_edges(11, 16, seed=0)
    n = int(E.max()) + 1
    iters, dmax = set(), set()
    for r in range(6):
        keep = np.ones(E.shape[0], bool)
        if r:
            keep[rng.choice(E.shape[0], 64, replace=False)] = False
        g = build_csr(relabel(E[keep], degeneracy_order(E[keep], n)), n)
        deg = g.degrees
        h = max(h for h in range(n + 1) if (deg >= h).sum() >= h)
        assert support_mod._h_index(deg) == h
        iters.add(support_mod._search_iters(g, oriented=True))
        dmax.add(int(g.dplus.max()))
        assert 2 ** (min(iters) - 1) > max(dmax)
    assert min(dmax) < 64 < max(dmax) and len(iters) == 1


def test_triangle_count_invariants():
    E = _er_edges(60, 0.2, 42)
    g = build_csr(E)
    S = compute_support(g)
    assert int(S.sum()) % 3 == 0
    assert triangle_count(g) == int(S.sum()) // 3


# ------------------------------------------------------------ hypothesis ----

@st.composite
def graphs(draw):
    n = draw(st.integers(4, 28))
    density = draw(st.floats(0.05, 0.6))
    seed = draw(st.integers(0, 2**31 - 1))
    rng = np.random.default_rng(seed)
    mask = rng.random((n, n)) < density
    src, dst = np.nonzero(np.triu(mask, 1))
    return edges_from_arrays(src, dst, n)


@given(graphs())
@settings(**SETTINGS)
def test_property_pkt_equals_oracle(E):
    if E.size == 0:
        return
    g = build_csr(E)
    ref = truss_numpy(g.El)
    assert np.array_equal(pkt(g, chunk=64).trussness, ref)


@given(graphs())
@settings(**SETTINGS)
def test_property_trussness_invariants(E):
    """System invariants: trussness ≥ 2; trussness ≤ support+2;
    trussness(e) ≤ min coreness of endpoints + 1 (Cohen)."""
    if E.size == 0:
        return
    from repro.core.kcore import kcore_numpy
    g = build_csr(E)
    res = pkt(g)
    t = res.trussness
    assert (t >= 2).all()
    assert (t <= res.support + 2).all()
    core = kcore_numpy(g)
    cap = np.minimum(core[g.El[:, 0]], core[g.El[:, 1]]) + 1
    assert (t <= cap).all()


@given(graphs())
@settings(**SETTINGS)
def test_property_wc_equals_pkt(E):
    if E.size == 0:
        return
    g = build_csr(E)
    assert np.array_equal(truss_wc(g), pkt(g).trussness)
