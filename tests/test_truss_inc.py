"""Incremental maintenance: parity with from-scratch PKT under arbitrary
insert/delete sequences, across repair paths and executor modes."""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st, HealthCheck

from repro.graphs.csr import edges_from_arrays
from repro.graphs.gen import ring_of_cliques_edges, rmat_edges
from repro.core.pkt import truss_pkt
from repro.core.support import compute_support
from repro.core.truss_inc import (INSERT_MODES, IncrementalTruss, _Incidence,
                                  _host_peel, triangle_list,
                                  triangles_through, wedge_subtable)

SETTINGS = dict(max_examples=12, deadline=None,
                suppress_health_check=[HealthCheck.too_slow])


def _er_edges(n, p, seed):
    rng = np.random.default_rng(seed)
    mask = rng.random((n, n)) < p
    src, dst = np.nonzero(np.triu(mask, 1))
    return edges_from_arrays(src, dst, n)


def _assert_state_exact(inc, ctx=None):
    """Bitwise agreement with a from-scratch decomposition of the current
    edge set, plus support- and triangle-state invariants."""
    if inc.m == 0:
        assert inc.trussness.shape == (0,)
        return
    ref = truss_pkt(inc.edges)
    assert np.array_equal(inc.trussness, ref), ctx
    S_ref = compute_support(inc.g)
    assert np.array_equal(inc.support, S_ref), ctx
    assert inc.triangles.shape[0] == int(S_ref.sum()) // 3, ctx


# ------------------------------------------------------------- hypothesis ----

@st.composite
def update_scripts(draw):
    """An initial graph plus a script of insert/delete batches."""
    n = draw(st.integers(6, 20))
    density = draw(st.floats(0.08, 0.5))
    seed = draw(st.integers(0, 2**31 - 1))
    E = _er_edges(n, density, seed)
    batches = []
    for _ in range(draw(st.integers(1, 3))):
        n_rm = draw(st.integers(0, 6))
        n_add = draw(st.integers(0, 6))
        batches.append((n_add, n_rm))
    return n, E, batches, seed


def _apply_script(inc, n, batches, seed):
    _apply_script.history = []
    rng = np.random.default_rng(seed + 1)
    for n_add, n_rm in batches:
        cur = inc.edges
        m = cur.shape[0]
        rm = cur[rng.choice(m, size=min(n_rm, m), replace=False)] \
            if m else np.zeros((0, 2), np.int64)
        add = np.stack([rng.integers(0, n + 2, n_add),
                        rng.integers(0, n + 2, n_add)], axis=1)
        add = add[add[:, 0] != add[:, 1]]
        st_ = inc.update(add_edges=add, remove_edges=rm)
        _apply_script.history.append(st_)
        assert st_.mode in ("noop", "local", "full")
        _assert_state_exact(inc, (n_add, n_rm, st_.mode))


@given(update_scripts())
@settings(**SETTINGS)
def test_property_incremental_parity(script):
    """Any insert/delete sequence ends bitwise-equal to from-scratch pkt."""
    n, E, batches, seed = script
    if E.shape[0] == 0:
        return
    inc = IncrementalTruss(E, local_frac=1.0)
    _assert_state_exact(inc, "init")
    _apply_script(inc, n, batches, seed)


@given(update_scripts())
@settings(max_examples=6, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
def test_property_full_fallback_parity(script):
    """local_frac=0 forces the full-recompute fallback on every non-noop
    update; parity must hold through that path too."""
    n, E, batches, seed = script
    if E.shape[0] == 0:
        return
    inc = IncrementalTruss(E, local_frac=0.0)
    _apply_script(inc, n, batches, seed)
    # any update that had actual repair work must have taken the full path
    # (an update with an empty repair set may legitimately stay local)
    assert all(s.affected == 0 for s in _apply_script.history
               if s.mode == "local")


@given(update_scripts())
@settings(max_examples=6, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
def test_property_jax_masked_peel_parity(script):
    """host_peel_max=0 routes every insertion region through the masked
    ``_peel_loop`` (pinned-boundary) path instead of the host mirror."""
    n, E, batches, seed = script
    if E.shape[0] == 0:
        return
    inc = IncrementalTruss(E, local_frac=1.0, host_peel_max=0)
    _apply_script(inc, n, batches, seed)


# ------------------------------------------------------------ fixed cases ----

def test_insert_increase_cascade():
    """Completing K4 raises every edge 3 -> 4 — the increase side must
    propagate beyond the inserted edge's own triangles."""
    E = np.array([[0, 1], [0, 2], [0, 3], [1, 2], [1, 3]], np.int64)
    inc = IncrementalTruss(E, local_frac=1.0)
    st_ = inc.update(add_edges=np.array([[2, 3]]))
    assert st_.mode == "local" and st_.inserted == 1
    assert (inc.trussness == 4).all()
    _assert_state_exact(inc)


def test_delete_decrease_cascade():
    """Breaking K4 drops the survivors back to 3."""
    inc = IncrementalTruss(np.array(
        [[0, 1], [0, 2], [0, 3], [1, 2], [1, 3], [2, 3]], np.int64),
        local_frac=1.0)
    st_ = inc.update(remove_edges=np.array([[2, 3]]))
    assert st_.mode == "local" and st_.deleted == 1
    assert (inc.trussness == 3).all()
    _assert_state_exact(inc)


def test_empty_transitions_and_vertex_growth():
    inc = IncrementalTruss(np.array([[0, 1], [0, 2], [1, 2]], np.int64))
    inc.update(remove_edges=inc.edges)
    assert inc.m == 0 and inc.trussness.shape == (0,)
    st_ = inc.update(add_edges=np.array([[5, 9], [9, 11], [5, 11]], np.int64))
    assert st_.inserted == 3 and inc.n == 12
    assert (inc.trussness == 3).all()
    _assert_state_exact(inc)


def test_noop_and_setwise_semantics():
    inc = IncrementalTruss(np.array([[0, 1], [0, 2], [1, 2]], np.int64))
    # inserting an existing edge / removing a missing one is a no-op
    st_ = inc.update(add_edges=np.array([[1, 0]]),
                     remove_edges=np.array([[5, 6]]))
    assert st_.mode == "noop" and st_.inserted == 0 and st_.deleted == 0
    # an edge in both batches ends up present (add wins set-wise)
    st_ = inc.update(add_edges=np.array([[1, 2], [0, 3]]),
                     remove_edges=np.array([[1, 2]]))
    assert inc.m == 4 and st_.inserted == 1 and st_.deleted == 0
    _assert_state_exact(inc)


def test_ring_of_cliques_bridge_churn():
    inc = IncrementalTruss(ring_of_cliques_edges(4, 5), local_frac=1.0)
    rng = np.random.default_rng(3)
    for _ in range(4):
        cur = inc.edges
        rm = cur[rng.choice(cur.shape[0], size=2, replace=False)]
        add = np.stack([rng.integers(0, 20, 3), rng.integers(0, 20, 3)], 1)
        add = add[add[:, 0] != add[:, 1]]
        inc.update(add_edges=add, remove_edges=rm)
        _assert_state_exact(inc)


@pytest.mark.parametrize("mode", ["chunked", "dense"])
def test_masked_peel_executor_modes(mode):
    """The pinned-boundary jax re-peel agrees across both peel executors
    (the pinned mask is threaded through each)."""
    inc = IncrementalTruss(ring_of_cliques_edges(3, 4), mode=mode,
                           local_frac=1.0, host_peel_max=0)
    inc.update(add_edges=np.array([[0, 2], [1, 9]]),
               remove_edges=np.array([[0, 1]]))
    _assert_state_exact(inc, mode)


def test_update_validation_matches_submit():
    inc = IncrementalTruss(np.array([[0, 1], [0, 2], [1, 2]], np.int64))
    with pytest.raises(ValueError, match="self-loop"):
        inc.update(add_edges=np.array([[3, 3]]))
    with pytest.raises(ValueError, match="negative"):
        inc.update(remove_edges=np.array([[-1, 2]]))
    with pytest.raises(ValueError, match="integer"):
        IncrementalTruss(np.array([[0.5, 1.0]]))
    with pytest.raises(ValueError, match="local_frac"):
        IncrementalTruss(np.zeros((0, 2), np.int64), local_frac=1.5)


def test_query_alignment_and_missing_edge():
    inc = IncrementalTruss(np.array([[0, 1], [0, 2], [1, 2]], np.int64))
    assert list(inc.query(np.array([[2, 0], [1, 0], [0, 1]]))) == [3, 3, 3]
    with pytest.raises(ValueError, match="not present"):
        inc.query(np.array([[0, 9]]))
    with pytest.raises(ValueError, match="not present"):
        inc.query(np.array([[1, 2], [0, 3]][::-1]))


def test_update_stats_bookkeeping():
    inc = IncrementalTruss(_er_edges(16, 0.3, 5), local_frac=1.0)
    m0 = inc.m
    st_ = inc.update(add_edges=np.array([[0, 15], [1, 14]]),
                     remove_edges=inc.edges[:2])
    assert st_.m_before == m0 and st_.m_after == inc.m
    assert st_.seconds >= 0 and st_.mode == "local"
    assert inc.stats["updates"] == 1 and inc.stats["last"] is st_


# ------------------------------------------------------- building blocks ----

def test_wedge_subtable_matches_full_table():
    from repro.graphs.csr import build_csr
    from repro.core.support import build_peel_table
    g = build_csr(_er_edges(14, 0.4, 7))
    full = build_peel_table(g)
    sub = wedge_subtable(g, np.arange(g.m))
    assert np.array_equal(sub.e1, full.e1)
    assert np.array_equal(sub.cand_slot, full.cand_slot)
    assert np.array_equal(sub.off, full.off)


def test_triangle_list_each_once():
    from repro.graphs.csr import build_csr
    g = build_csr(_er_edges(15, 0.4, 8))
    tri = triangle_list(g)
    S = compute_support(g)
    assert tri.shape[0] == int(S.sum()) // 3
    # rows sorted and unique
    assert (tri[:, 0] < tri[:, 1]).all() and (tri[:, 1] < tri[:, 2]).all()
    keys = (tri[:, 0] * g.m + tri[:, 1]) * g.m + tri[:, 2]
    assert np.unique(keys).shape[0] == tri.shape[0]
    # per-edge membership counts reproduce the support vector
    assert np.array_equal(np.bincount(tri.ravel(), minlength=g.m), S)


def test_incidence_roundtrip():
    from repro.graphs.csr import build_csr
    g = build_csr(_er_edges(12, 0.5, 9))
    tri = triangle_list(g)
    inc = _Incidence(tri, g.m)
    for e in range(g.m):
        rows = np.unique(inc.rows_of(np.array([e])))
        assert set(rows) == set(np.nonzero((tri == e).any(axis=1))[0])


def test_host_peel_matches_pkt_on_whole_graph():
    """With the whole graph as the region and no pins, the host mirror IS a
    full peel — it must reproduce pkt exactly."""
    from repro.graphs.csr import build_csr
    from repro.core.pkt import pkt
    g = build_csr(_er_edges(18, 0.35, 11))
    tri = triangle_list(g)
    S = compute_support(g)
    out = _host_peel(g.m, tri, S.astype(np.int64),
                     np.ones(g.m, bool), np.zeros(g.m, bool))
    assert np.array_equal(out + 2, pkt(g).trussness)


def test_triangles_through_subset_anchors():
    from repro.graphs.csr import build_csr
    g = build_csr(_er_edges(14, 0.45, 13))
    anchors = np.array([0, g.m // 2, g.m - 1])
    a, e2, e3 = triangles_through(g, anchors)
    tri = triangle_list(g)
    for x in anchors:
        got = {tuple(sorted((int(p), int(q))))
               for aa, p, q in zip(a, e2, e3) if aa == x}
        want = {tuple(sorted(int(y) for y in row if y != x))
                for row in tri if (row == x).any()}
        assert got == want, x


def _lexsorted(tri):
    return tri[np.lexsort((tri[:, 2], tri[:, 1], tri[:, 0]))]


#: graphs a full rebuild's triangle list is checked on (edges, handle
#: options): random, a clique, triangle-free (a path and a star), and a
#: Graph500 Kronecker graph whose rebuild peel compacts
REBUILD_GRAPHS = {
    "er": lambda: (_er_edges(24, 0.35, 21), {}),
    "k6": lambda: (edges_from_arrays(*np.triu_indices(6, 1), 6), {}),
    "path-star": lambda: (np.array([[i, i + 1] for i in range(8)]
                                   + [[20, 20 + j] for j in range(1, 9)]), {}),
    "kron8": lambda: (rmat_edges(8, edge_factor=16),
                      dict(compact_frac=0.9, compact_min=1)),
}


@pytest.mark.parametrize("graph", sorted(REBUILD_GRAPHS))
@pytest.mark.parametrize("table_mode", ["device", "numpy"])
def test_full_rebuild_takes_triangles_from_the_peel_table(table_mode, graph):
    """The triangle list a full rebuild commits, selected from ``pkt``'s
    peel table, is the host enumerator's list in lexicographic order, and
    its per-edge row counts are the support — after open and after a batch
    forced to a full rebuild (``local_frac=0``)."""
    from repro import spans

    E, kw = REBUILD_GRAPHS[graph]()
    spans.drain()
    inc = IncrementalTruss(E, table_mode=table_mode, local_frac=0.0, **kw)
    if graph == "kron8":
        assert any(r.name == "pkt.compact" for r in spans.drain())

    def check():
        assert np.array_equal(inc.triangles, _lexsorted(triangle_list(inc.g)))
        assert np.array_equal(np.bincount(inc.triangles.ravel(),
                                          minlength=inc.m),
                              compute_support(inc.g))

    check()
    rng = np.random.default_rng(7)
    n = int(inc.edges.max()) + 1
    add = np.stack([rng.integers(0, n, 6), rng.integers(0, n, 6)], axis=1)
    add = np.concatenate([add[add[:, 0] != add[:, 1]], [[0, 2]]])
    rm = inc.edges[rng.choice(inc.m, 3, replace=False)]
    assert inc.update(add_edges=add, remove_edges=rm).mode == "full"
    check()
    assert inc.triangles.shape[0] > 0


# ------------------------------------------------ batched insertions (§13) --

#: Region-size regimes × executors × table modes the batched insertion path
#: must agree across, bitwise: host-mirror regions, masked-device regions,
#: forced mid-peel compaction, both peel executors, both wedge-table
#: builders, and the forced full-recompute fallback.
BATCH_AXES = {
    "host-region": dict(local_frac=1.0),
    "device-region": dict(local_frac=1.0, host_peel_max=0),
    "compacting": dict(local_frac=1.0, host_peel_max=0,
                       compact_frac=0.9, compact_min=1),
    "dense": dict(local_frac=1.0, host_peel_max=0, mode="dense"),
    "numpy-table": dict(local_frac=1.0, host_peel_max=0, table_mode="numpy"),
    "forced-fallback": dict(local_frac=0.0),
}


def _tri_set(inc):
    tri = inc.triangles
    return np.unique(tri, axis=0) if tri.size else tri


def _paired_script(seq, bat, n, batches, seed):
    """Drive identical scripts through the sequential oracle and the batched
    instance, asserting bitwise agreement (trussness, support, triangle
    set) plus from-scratch parity after every batch."""
    rng = np.random.default_rng(seed + 1)
    for n_add, n_rm in batches:
        cur = seq.edges
        m = cur.shape[0]
        rm = cur[rng.choice(m, size=min(n_rm, m), replace=False)] \
            if m else np.zeros((0, 2), np.int64)
        add = np.stack([rng.integers(0, n + 2, n_add),
                        rng.integers(0, n + 2, n_add)], axis=1)
        add = add[add[:, 0] != add[:, 1]]
        s1 = seq.update(add_edges=add, remove_edges=rm)
        s2 = bat.update(add_edges=add, remove_edges=rm)
        if s1.inserted and s1.mode != "noop":
            assert s1.insert_mode == "sequential"
            assert s2.insert_mode == "batched"
        assert np.array_equal(bat.edges, seq.edges)
        assert np.array_equal(bat.trussness, seq.trussness), (n_add, n_rm)
        assert np.array_equal(bat.support, seq.support), (n_add, n_rm)
        assert np.array_equal(_tri_set(bat), _tri_set(seq)), (n_add, n_rm)
        _assert_state_exact(bat, (n_add, n_rm, s2.mode))


@given(script=update_scripts(), axis=st.sampled_from(sorted(BATCH_AXES)))
@settings(max_examples=21, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
def test_property_batched_matches_sequential_and_scratch(script, axis):
    """The §13 parity harness: batched ≡ sequential ≡ from-scratch pkt,
    bitwise, across the executor × table × region-regime matrix (the axis
    is drawn per example; every axis also runs deterministically in
    ``test_batched_axes_fixed_script``)."""
    n, E, batches, seed = script
    if E.shape[0] == 0:
        return
    kw = BATCH_AXES[axis]
    seq = IncrementalTruss(E, insert_mode="sequential", **kw)
    bat = IncrementalTruss(E, insert_mode="batched", **kw)
    _paired_script(seq, bat, n, batches, seed)


@pytest.mark.parametrize("axis", sorted(BATCH_AXES))
def test_batched_axes_fixed_script(axis):
    """Deterministic coverage of every matrix axis with a fixed script —
    guaranteed to run (and force region merges: multi-insert batches into
    a clique ring) whichever property backend is active."""
    kw = BATCH_AXES[axis]
    E = ring_of_cliques_edges(4, 5)
    seq = IncrementalTruss(E, insert_mode="sequential", **kw)
    bat = IncrementalTruss(E, insert_mode="batched", **kw)
    _paired_script(seq, bat, 20, [(4, 2), (3, 3), (5, 0)], seed=17)


@given(update_scripts())
@settings(max_examples=6, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
def test_property_update_many_batched_parity(script):
    """Interleaved insert/delete batches composed through ``update_many``
    under ``insert_mode="batched"`` end bitwise-equal to applying them one
    at a time sequentially, and to from-scratch pkt."""
    n, E, batches, seed = script
    if E.shape[0] == 0:
        return
    rng = np.random.default_rng(seed + 1)
    seq = IncrementalTruss(E, insert_mode="sequential", local_frac=1.0)
    bat = IncrementalTruss(E, insert_mode="batched", local_frac=1.0)
    blist = []
    for n_add, n_rm in batches:
        cur = seq.edges          # draw against the sequentially-applied state
        m = cur.shape[0]
        rm = cur[rng.choice(m, size=min(n_rm, m), replace=False)] \
            if m else np.zeros((0, 2), np.int64)
        add = np.stack([rng.integers(0, n + 2, n_add),
                        rng.integers(0, n + 2, n_add)], axis=1)
        add = add[add[:, 0] != add[:, 1]]
        seq.update(add_edges=add, remove_edges=rm)
        blist.append((add, rm))
    st_ = bat.update_many(blist)
    assert st_.coalesced == len(blist)
    assert np.array_equal(bat.edges, seq.edges)
    assert np.array_equal(bat.trussness, seq.trussness)
    _assert_state_exact(bat)


def test_insert_mode_validation_and_override():
    E = np.array([[0, 1], [0, 2], [1, 2]], np.int64)
    with pytest.raises(ValueError, match="insert_mode"):
        IncrementalTruss(E, insert_mode="bogus")
    inc = IncrementalTruss(E)
    assert inc.insert_mode == "batched"      # the default path
    assert set(INSERT_MODES) == {"sequential", "batched"}
    with pytest.raises(ValueError, match="insert_mode"):
        inc.update(add_edges=np.array([[0, 3]]), insert_mode="bogus")
    st_ = inc.update(add_edges=np.array([[0, 3], [1, 3], [2, 3]]),
                     insert_mode="sequential")
    assert st_.insert_mode == "sequential"
    st_ = inc.update(remove_edges=np.array([[0, 3]]))
    assert st_.insert_mode is None           # no insertions in the batch
    _assert_state_exact(inc)


def test_batched_single_region_dispatch(monkeypatch):
    """A multi-insert batch with overlapping candidate regions re-peels
    exactly once — the per-edge regions merge into one dispatch (§13) —
    while the sequential oracle re-peels once per inserted edge."""
    calls = {"n": 0}
    orig = IncrementalTruss._region_peel

    def counting(self, *a, **k):
        calls["n"] += 1
        return orig(self, *a, **k)

    monkeypatch.setattr(IncrementalTruss, "_region_peel", counting)
    # three K5s, each missing one edge; the batch completes all three
    rows, missing = [], []
    for c in range(3):
        vs = range(5 * c, 5 * c + 5)
        allp = [(i, j) for i in vs for j in vs if i < j]
        missing.append(allp.pop(c))
        rows += allp
    E = np.array(rows, np.int64)
    add = np.array(missing, np.int64)

    bat = IncrementalTruss(E, insert_mode="batched", local_frac=1.0)
    calls["n"] = 0
    st_ = bat.update(add_edges=add)
    assert st_.inserted == 3 and st_.insert_mode == "batched"
    assert st_.mode == "local" and calls["n"] == 1
    seq = IncrementalTruss(E, insert_mode="sequential", local_frac=1.0)
    calls["n"] = 0
    st_ = seq.update(add_edges=add)
    assert st_.mode == "local" and calls["n"] == 3
    assert np.array_equal(bat.trussness, seq.trussness)
    assert (bat.trussness == 5).all()        # every K5 completed
    _assert_state_exact(bat)


def test_batched_overlapping_cascades():
    """Two inserted edges completing two overlapping near-cliques: the
    shared middle edges sit in both candidate regions and the merged
    re-peel must settle the joint cascade exactly."""
    allp = [(i, j) for i in range(5) for j in range(i + 1, 5)]
    E = np.array([e for e in allp if e not in [(0, 1), (3, 4)]], np.int64)
    for imode in INSERT_MODES:
        inc = IncrementalTruss(E, insert_mode=imode, local_frac=1.0)
        inc.update(add_edges=np.array([[0, 1], [3, 4]], np.int64))
        assert (inc.trussness == 5).all(), imode
        _assert_state_exact(inc, imode)


def test_batched_insert_and_delete_one_batch():
    """Inserts and deletes in one batch under batched mode: the deletion
    descent runs first, then one merged-region insertion repair, ending
    bitwise-equal to scratch."""
    E = ring_of_cliques_edges(4, 5)
    seq = IncrementalTruss(E, insert_mode="sequential", local_frac=1.0)
    bat = IncrementalTruss(E, insert_mode="batched", local_frac=1.0)
    add = np.array([[0, 7], [1, 11], [2, 16]], np.int64)
    rem = E[:3]
    s1 = seq.update(add_edges=add, remove_edges=rem)
    s2 = bat.update(add_edges=add, remove_edges=rem)
    assert s1.mode == s2.mode == "local"
    assert s2.insert_mode == "batched" and s2.deleted == 3
    assert np.array_equal(bat.trussness, seq.trussness)
    assert np.array_equal(bat.support, seq.support)
    _assert_state_exact(bat)


def test_batched_spans_compaction_boundary():
    """A batch whose merged region runs the compacted device subset peel
    with compaction forced on every sub-level (compact_min=1) — the region
    re-peel crosses compaction boundaries mid-batch."""
    E = _er_edges(26, 0.35, 21)
    kw = dict(local_frac=1.0, host_peel_max=0, compact_frac=0.99,
              compact_min=1)
    add = np.array([[0, 25], [1, 24], [2, 23], [3, 22]], np.int64)
    seq = IncrementalTruss(E, insert_mode="sequential", **kw)
    bat = IncrementalTruss(E, insert_mode="batched", **kw)
    seq.update(add_edges=add)
    s2 = bat.update(add_edges=add)
    # the merged region must repair locally through the compacting subset
    # peel (the oracle may legitimately fall back on cumulative work —
    # its result is exact either way)
    assert s2.mode == "local" and s2.insert_mode == "batched"
    assert np.array_equal(bat.trussness, seq.trussness)
    _assert_state_exact(bat)


def test_batched_touches_kmax_edges():
    """A batch inserted inside the maximum-k clique — touching k_max edges
    and raising k_max itself — repairs exactly in one merged region."""
    allp = [(i, j) for i in range(6) for j in range(i + 1, 6)]
    E = np.array(allp + [(0, 6), (0, 7), (6, 7)], np.int64)
    bat = IncrementalTruss(E, insert_mode="batched", local_frac=1.0)
    assert int(bat.trussness.max()) == 6
    st_ = bat.update(add_edges=np.array([[6, k] for k in range(1, 6)],
                                        np.int64))
    assert st_.mode == "local" and st_.insert_mode == "batched"
    assert int(bat.trussness.max()) == 7     # vertex 6 completed K7
    _assert_state_exact(bat)


class _Boom(RuntimeError):
    pass


@pytest.mark.parametrize("imode,fail_at", [("batched", 1), ("sequential", 2)])
def test_fault_injection_no_half_applied_batch(monkeypatch, imode, fail_at):
    """A region peel raising mid-batch leaves the handle bitwise untouched —
    including the deletion phase of the same update (no half-applied
    batch) — and the handle stays serviceable afterwards (§13)."""
    E = ring_of_cliques_edges(4, 5)
    inc = IncrementalTruss(E, insert_mode=imode, local_frac=1.0)
    snap = (inc.edges, inc.trussness, inc.support, _tri_set(inc),
            dict(inc.stats))
    orig = IncrementalTruss._region_peel
    calls = {"n": 0}

    def flaky(self, *a, **k):
        calls["n"] += 1
        if calls["n"] == fail_at:
            raise _Boom("injected mid-batch")
        return orig(self, *a, **k)

    monkeypatch.setattr(IncrementalTruss, "_region_peel", flaky)
    add = np.array([[0, 7], [1, 11], [2, 16]], np.int64)
    rem = E[:2]
    with pytest.raises(_Boom):
        inc.update(add_edges=add, remove_edges=rem)
    assert calls["n"] == fail_at             # it really failed mid-batch
    assert np.array_equal(inc.edges, snap[0])
    assert np.array_equal(inc.trussness, snap[1])
    assert np.array_equal(inc.support, snap[2])
    assert np.array_equal(_tri_set(inc), snap[3])
    assert inc.stats["updates"] == snap[4]["updates"]
    monkeypatch.setattr(IncrementalTruss, "_region_peel", orig)
    st_ = inc.update(add_edges=add, remove_edges=rem)
    assert st_.mode == "local"               # same batch now lands cleanly
    _assert_state_exact(inc)


# ------------------------------------------------------- batch composition --


def test_compose_update_batches_set_algebra():
    """Composition follows A <- (A \\ r) | a, R <- R | r: add-wins, sorted."""
    from repro.core.truss_inc import compose_update_batches

    b1 = (np.array([[0, 1], [2, 3]], np.int64), None)
    b2 = (np.array([[4, 5]], np.int64), np.array([[0, 1]], np.int64))
    b3 = (np.array([[0, 1]], np.int64), np.array([[8, 9]], np.int64))
    add, rem = compose_update_batches([b1, b2, b3])
    # [0,1] was added, removed, re-added -> survives in add; [8,9] was
    # never added so it only accumulates in remove
    assert add.tolist() == [[0, 1], [2, 3], [4, 5]]
    assert rem.tolist() == [[0, 1], [8, 9]]
    assert add.dtype == np.int64 and rem.dtype == np.int64


def test_compose_update_batches_matches_sequential():
    """One composed update == the same batches applied one at a time."""
    from repro.core.truss_inc import compose_update_batches

    e = _er_edges(16, 0.35, 40)
    batches = [
        (np.array([[0, 9], [1, 10]], np.int64), None),
        (None, np.array([[0, 9]], np.int64)),
        (np.array([[2, 11]], np.int64), np.array([[1, 10]], np.int64)),
    ]
    seq = IncrementalTruss(e)
    for add, rem in batches:
        seq.update(add_edges=add, remove_edges=rem)
    one = IncrementalTruss(e)
    st = one.update_many(batches)
    assert st.coalesced == 3
    assert np.array_equal(one.edges, seq.edges)
    assert np.array_equal(one.trussness, seq.trussness)
    assert np.array_equal(one.trussness, truss_pkt(one.edges))
    # degenerate cases
    add, rem = compose_update_batches([])
    assert add.shape == (0, 2) and rem.shape == (0, 2)
    with pytest.raises(ValueError):
        compose_update_batches([(np.array([[1, 1]], np.int64), None)])
