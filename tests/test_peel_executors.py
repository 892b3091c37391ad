"""Peel executors: the dense and chunked modes agree bitwise with each other
and with the numpy oracle, on random and adversarial graphs; plus the
chunk-clamp regression for tiny graphs and a level / sub-level recount."""

import numpy as np
import pytest

from repro.graphs.csr import build_csr, edges_from_arrays
from repro.graphs.gen import ring_of_cliques_edges, rmat_edges
from repro.core.pkt import pkt, prepare_peel, PEEL_MODES
from repro.core import support as support_mod
from repro.core.ref import truss_numpy


def _er_edges(n, p, seed):
    rng = np.random.default_rng(seed)
    mask = rng.random((n, n)) < p
    src, dst = np.nonzero(np.triu(mask, 1))
    return edges_from_arrays(src, dst, n)


def _star_edges(k=12):
    """Hub + k spokes: zero triangles, every edge trussness 2."""
    return np.stack([np.zeros(k, np.int64), np.arange(1, k + 1)], axis=1)


def _disconnected_edges():
    """Clique ⊔ path ⊔ isolated triangle ⊔ single edge."""
    parts = [
        [(0, 1), (0, 2), (0, 3), (1, 2), (1, 3), (2, 3)],   # K4
        [(10, 11), (11, 12), (12, 13)],                     # path
        [(20, 21), (20, 22), (21, 22)],                     # triangle
        [(30, 31)],                                         # lone edge
    ]
    e = np.array([p for part in parts for p in part], dtype=np.int64)
    return e


ADVERSARIAL = {
    "star": _star_edges(),
    "clique": edges_from_arrays(*np.nonzero(np.triu(np.ones((8, 8)), 1)), 8),
    "disconnected": _disconnected_edges(),
    "ring_of_cliques": ring_of_cliques_edges(4, 6),
    "rmat": rmat_edges(6, edge_factor=5, seed=9),
}


# ---------------------------------------------------------------- parity ----

def assert_executors_agree(g, **kw):
    """``dense`` and ``chunked`` give the same result, field by field, and
    the oracle's trussness.  ``chunk_visits`` counts each executor's own
    work: dense visits every chunk a sub-level, chunked only the
    frontier's, so chunked never visits more."""
    dense = pkt(g, mode="dense", **kw)
    chunked = pkt(g, mode="chunked", **kw)
    assert np.array_equal(dense.trussness, chunked.trussness)
    assert np.array_equal(dense.support, chunked.support)
    assert (dense.levels, dense.sublevels, dense.compactions,
            dense.phases) == (chunked.levels, chunked.sublevels,
                              chunked.compactions, chunked.phases)
    assert chunked.chunk_visits <= dense.chunk_visits
    assert np.array_equal(chunked.trussness, truss_numpy(g.El))
    return dense, chunked


@pytest.mark.parametrize("seed", range(5))
def test_executor_parity_random(seed):
    E = _er_edges(12 + 8 * seed, 0.15 + 0.08 * seed, 100 + seed)
    assert E.size
    assert_executors_agree(build_csr(E), chunk=8)


@pytest.mark.parametrize("name", sorted(ADVERSARIAL))
def test_executor_parity_adversarial(name):
    assert_executors_agree(build_csr(ADVERSARIAL[name]), chunk=8)


def test_all_modes_agree_multi_chunk():
    g = build_csr(_er_edges(40, 0.3, 7))
    ref = truss_numpy(g.El)
    for mode in PEEL_MODES:
        for chunk in (16, 128):
            assert np.array_equal(pkt(g, mode=mode, chunk=chunk).trussness,
                                  ref), (mode, chunk)


def test_invalid_mode_rejected():
    g = build_csr(np.array([[0, 1]], np.int64))
    with pytest.raises(ValueError, match="mode"):
        pkt(g, mode="warp")


# ------------------------------------------------- chunk-clamp regression ----

@pytest.mark.parametrize("edges", [
    np.array([[0, 1]], np.int64),                     # m == 1
    np.array([[0, 1], [1, 2]], np.int64),             # m == 2, no triangle
    np.array([[0, 1], [0, 2], [1, 2]], np.int64),     # smallest triangle
])
@pytest.mark.parametrize("chunk", [1, 3, 1 << 20])
def test_tiny_graph_huge_chunk(edges, chunk):
    """chunk >> table size must clamp, not produce n_chunks == 0."""
    g = build_csr(edges)
    ref = truss_numpy(g.El)
    for mode in PEEL_MODES:
        assert np.array_equal(pkt(g, mode=mode, chunk=chunk).trussness, ref), \
            (mode, chunk)


def test_prepare_peel_always_one_chunk():
    g = build_csr(np.array([[0, 1], [1, 2]], np.int64))
    rows = support_mod.resolve_peel_table(g)
    assert rows.size == 0 and rows.wedges > 0
    for chunk in (1, rows.wedges, rows.wedges + 1, 1 << 20):
        tabs, c, n_chunks = prepare_peel(rows, g.m, chunk)
        assert n_chunks >= 1
        assert c >= 1
        assert tabs.e1.shape[0] == n_chunks * c


def test_prepare_peel_empty_graph_explicit():
    """m == 0: the explicit early-exit yields one all-padding chunk."""
    from repro.core.pkt import chunk_ranges

    g = build_csr(np.zeros((0, 2), np.int64))
    rows = support_mod.resolve_peel_table(g)
    assert rows.size == rows.wedges == 0
    tabs, chunk, n_chunks = prepare_peel(rows, g.m, 1 << 14)
    assert (chunk, n_chunks) == (1, 1)
    for col in (tabs.e1, tabs.e2, tabs.e3):               # edge sentinel
        assert np.asarray(col).tolist() == [g.m]
    assert tabs.c_start.shape == (0,) and tabs.has_entries.shape == (0,)
    # chunk_ranges itself: empty offset array, with and without m_out
    has, cs, ce = chunk_ranges(np.zeros(1, np.int64), 4)
    assert has.shape == cs.shape == ce.shape == (0,)
    has, cs, ce = chunk_ranges(np.zeros(1, np.int64), 4, m_out=5)
    assert not has.any() and (cs == 0).all() and (ce == 0).all()


def test_prepare_peel_entryless_support_table():
    """A triangle-free graph (star) has an *empty* support table and a peel
    table whose wedges all miss: the peel tables must be all sentinel rows
    with no edge entered, and the support executor must return all-zero
    support."""
    g = build_csr(_star_edges())
    stab = support_mod.build_support_table(g)
    assert stab.size == 0
    rows = support_mod.resolve_peel_table(g)
    assert rows.size == 0 and rows.wedges == g.m
    tabs, chunk, n_chunks = prepare_peel(rows, g.m, 8)
    assert chunk * n_chunks >= rows.wedges
    for col in (tabs.e1, tabs.e2, tabs.e3):
        assert (np.asarray(col) == g.m).all()
    assert not np.asarray(tabs.has_entries).any()
    S = support_mod.compute_support(g, stab)
    assert S.shape == (g.m,) and (S == 0).all()


@pytest.mark.parametrize("mode", PEEL_MODES)
def test_triangle_free_graph_all_modes(mode):
    """Triangle-free graphs peel in one level; no executor may choke on the
    all-zero support vector."""
    for edges in (_star_edges(5),
                  np.array([[0, 1], [1, 2], [2, 3], [3, 4]], np.int64)):
        g = build_csr(edges)
        res = pkt(g, mode=mode)
        assert (res.trussness == 2).all()
        assert (res.support == 0).all()


# ------------------------------------------- levels and sub-levels ----

def _levels_recount(g):
    """Plain numpy level / sub-level peel over the triangle list: (final S,
    levels, sub-levels), the counts the peel's rule gives."""
    from repro.core.truss_inc import triangle_list

    tri = triangle_list(g)
    S = np.bincount(tri.ravel(), minlength=g.m).astype(np.int64)
    processed = np.zeros(g.m, bool)
    levels = subs = 0
    while not processed.all():
        levels += 1
        l = S[~processed].min()
        curr = ~processed & (S == l)
        while curr.any():
            subs += 1
            live = ~processed[tri].any(axis=1) & curr[tri].any(axis=1)
            t = tri[live]
            hit = ~curr[t] & (S[t] > l)
            dec = np.bincount(t[hit], minlength=g.m)
            d = ~processed & ~curr & (dec > 0)
            S[d] = np.maximum(S[d] - dec[d], l)
            processed |= curr
            curr = ~processed & (S == l)
    return S, levels, subs


@pytest.mark.parametrize("table_mode", ["device", "numpy"])
@pytest.mark.parametrize("compact", ["off", "on"])
@pytest.mark.parametrize("mode", PEEL_MODES)
def test_peel_levels_and_sublevels_match_a_recount(mode, compact,
                                                   table_mode):
    """Every executor over the resolved rows, compacting or not: trussness
    bitwise the oracle's, levels and sub-levels those of a plain recount."""
    g = build_csr(ADVERSARIAL["rmat"])
    kw = (dict(compact_frac=None) if compact == "off"
          else dict(compact_frac=0.99, compact_min=0))
    res = pkt(g, mode=mode, chunk=16, table_mode=table_mode, **kw)
    S, levels, subs = _levels_recount(g)
    assert np.array_equal(res.trussness, truss_numpy(g.El))
    assert np.array_equal(res.trussness, S + 2)
    assert (res.levels, res.sublevels) == (levels, subs)
    assert (res.compactions > 0) == (compact == "on")
