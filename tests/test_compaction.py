"""Live-edge compaction (DESIGN.md §10): peeling with compaction enabled —
at any threshold — must be bitwise identical to the uncompacted run, across
both peel executors, both table modes, the batched engine, and the
incremental layer's compacted region re-peel.

Runs under real ``hypothesis`` and under the deterministic fallback shim.
"""

import numpy as np
import pytest

from hypothesis import HealthCheck, given, settings
from hypothesis import strategies as st

from repro.core.pkt import PEEL_MODES, peel_live_subset, pkt, truss_pkt
from repro.core.ref import truss_numpy
from repro.core.support import compute_support
from repro.graphs.csr import build_csr, edges_from_arrays
from repro.graphs.gen import (barabasi_albert_edges, ring_of_cliques_edges,
                              rmat_edges)

#: "aggressive" compaction: compact at every level boundary, no size floor —
#: maximally different execution schedule from the single-segment run
AGGRESSIVE = dict(compact_frac=0.99, compact_min=0)
OFF = dict(compact_frac=None)


def _er_edges(n, p, seed):
    rng = np.random.default_rng(seed)
    mask = rng.random((n, n)) < p
    src, dst = np.nonzero(np.triu(mask, 1))
    return edges_from_arrays(src, dst, n)


@st.composite
def graphs(draw):
    kind = draw(st.sampled_from(["er", "powerlaw", "cliques"]))
    seed = draw(st.integers(min_value=0, max_value=9999))
    if kind == "er":
        return _er_edges(draw(st.integers(min_value=6, max_value=24)),
                         draw(st.floats(0.15, 0.5)), seed)
    if kind == "powerlaw":
        return barabasi_albert_edges(
            draw(st.integers(min_value=8, max_value=20)),
            m_attach=draw(st.integers(min_value=2, max_value=4)), seed=seed)
    return ring_of_cliques_edges(draw(st.integers(min_value=2, max_value=4)),
                                 draw(st.integers(min_value=3, max_value=6)))


@settings(max_examples=4, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
@given(graphs())
def test_compaction_parity_matrix(E):
    """Both peel executors × threshold ∈ {off, aggressive}: bitwise equal
    (multi-clique graphs peel over several levels, so aggressive compaction
    really does segment the run)."""
    if E.shape[0] == 0:
        return
    g = build_csr(E)
    base = pkt(g, **OFF)
    if g.m <= 90:
        assert np.array_equal(base.trussness, truss_numpy(g.El))
    for pm in PEEL_MODES:
        for thresh in (OFF, AGGRESSIVE):
            res = pkt(g, mode=pm, **thresh)
            assert np.array_equal(res.trussness, base.trussness), pm
            assert np.array_equal(res.support, base.support), pm
            assert (res.levels, res.sublevels) == \
                (base.levels, base.sublevels), (pm, thresh)


@pytest.mark.parametrize("table_mode", ["numpy", "device"])
def test_compaction_parity_table_modes(table_mode):
    """Compaction rebuilds tables in whichever table_mode is active; both
    rebuild paths must continue the fixed point exactly."""
    for E in (ring_of_cliques_edges(4, 6), rmat_edges(6, edge_factor=5,
                                                      seed=1)):
        g = build_csr(E)
        base = pkt(g, table_mode=table_mode, **OFF)
        res = pkt(g, table_mode=table_mode, **AGGRESSIVE)
        assert res.compactions > 0          # the axis actually engaged
        assert np.array_equal(res.trussness, base.trussness)
        assert (res.levels, res.sublevels) == (base.levels, base.sublevels)


def test_compact_min_floor_disables_small_graphs():
    g = build_csr(ring_of_cliques_edges(3, 5))
    res = pkt(g, compact_frac=0.99, compact_min=1 << 20)
    assert res.compactions == 0
    assert np.array_equal(res.trussness, pkt(g, **OFF).trussness)


def test_truss_pkt_compaction_threaded():
    E = rmat_edges(6, edge_factor=4, seed=9)
    a = truss_pkt(E, compact_frac=None)
    b = truss_pkt(E, compact_frac=0.99, compact_min=0)
    assert np.array_equal(a, b)


def test_engine_table_mode_parity():
    """Batched engine: device-built (in-jit) tables agree with the host
    operand path graph-for-graph, including tiny and triangle-free ones."""
    from repro.serve.truss_engine import truss_batched

    fleet = [_er_edges(14, 0.35, 2), ring_of_cliques_edges(3, 4),
             np.array([[0, 1]], np.int64),
             np.array([[0, 1], [1, 2], [2, 3]], np.int64),
             rmat_edges(5, edge_factor=4, seed=4)]
    base = truss_batched(fleet, table_mode="numpy")
    got = truss_batched(fleet, table_mode="device")
    for b, g_ in zip(base, got):
        assert np.array_equal(b, g_)


def test_peel_live_subset_whole_graph_is_full_peel():
    """With every edge live and nothing pinned, the compacted subset peel
    IS the full decomposition."""
    g = build_csr(_er_edges(18, 0.35, 11))
    S0 = compute_support(g)
    out = peel_live_subset(g.El, np.arange(g.m), S0,
                           compact_frac=0.9, compact_min=0)
    assert np.array_equal(out + 2, pkt(g).trussness)


@pytest.mark.parametrize("table_mode", ["device", "numpy"])
@pytest.mark.parametrize("compact", ["off", "on"])
@pytest.mark.parametrize("mode", PEEL_MODES)
def test_peel_live_subset_pinned(mode, compact, table_mode):
    """A subset peel over the resolved rows of its own compacted graph: the
    edges of the 4-truss, a third of them pinned at their death level,
    peel to their trussness bitwise, in every executor and table mode."""
    from repro.core.truss_inc import triangle_list

    g = build_csr(rmat_edges(6, edge_factor=6, seed=5))
    T = pkt(g).trussness
    ids = np.nonzero(T >= 4)[0]
    inside = np.zeros(g.m, bool)
    inside[ids] = True
    tri = triangle_list(g)
    tri = tri[inside[tri].all(axis=1)]
    S0 = np.bincount(tri.ravel(), minlength=g.m)[ids]
    pinned = np.random.default_rng(3).random(ids.shape[0]) < 1 / 3
    S0 = np.where(pinned, T[ids] - 2, S0)
    kw = OFF if compact == "off" else AGGRESSIVE
    out = peel_live_subset(g.El, ids, S0, pinned, chunk=16, mode=mode,
                           table_mode=table_mode, **kw)
    assert ids.shape[0] > 32 and pinned.any()
    assert np.array_equal(out + 2, T[ids])


@settings(max_examples=4, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
@given(graphs(), st.integers(min_value=0, max_value=999))
def test_truss_inc_script_with_compaction(E, seed):
    """An insert/delete script through the incremental layer — region
    re-peels forced onto the compacted jax path with aggressive compaction —
    ends bitwise-equal to from-scratch pkt."""
    if E.shape[0] < 4:
        return
    from repro.core.truss_inc import IncrementalTruss

    n = int(E.max()) + 1
    inc = IncrementalTruss(E, local_frac=1.0, host_peel_max=0,
                           compact_frac=0.99, compact_min=0)
    rng = np.random.default_rng(seed)
    for _ in range(2):
        cur = inc.edges
        rm = cur[rng.choice(cur.shape[0], size=min(2, cur.shape[0]),
                            replace=False)]
        add = np.stack([rng.integers(0, n + 2, 3),
                        rng.integers(0, n + 2, 3)], axis=1)
        add = add[add[:, 0] != add[:, 1]]
        inc.update(add_edges=add, remove_edges=rm)
        assert np.array_equal(inc.trussness, truss_pkt(inc.edges))
