"""Property-based cross-mode parity: the peel executors must agree.

Both peel modes {dense, chunked} must produce bitwise-identical trussness,
initial support, and level/sub-level counts — and match the brute-force
oracle (``core.ref.truss_numpy``, the definitional O(m·Δ²)-per-round peel)
on graphs small enough to afford it, and on the Graph500 Kronecker graphs
of the benchmark's family at any size the tests run.

Graph population: random Erdős–Rényi and power-law (Barabási–Albert) draws
via ``graphs/gen.py``, plus the adversarial shapes that historically break
table/chunk bookkeeping — stars (empty oriented support table), cliques
(maximal trussness), disconnected unions, the empty graph, and raw inputs
with self-loops / duplicate / endpoint-swapped rows (canonicalized through
``edges_from_arrays`` exactly as production entry points do).

Runs under real ``hypothesis`` when installed and under the deterministic
fallback shim (``repro/testing/hypothesis_fallback.py``) otherwise; CI
exercises both configurations.
"""

import numpy as np
import pytest

from hypothesis import HealthCheck, given, settings
from hypothesis import strategies as st

from repro.core.pkt import PEEL_MODES, pkt
from repro.core.ref import truss_numpy
from repro.graphs.csr import (build_csr, degeneracy_order, edges_from_arrays,
                              relabel)
from repro.graphs.gen import (barabasi_albert_edges, erdos_renyi_edges,
                              ring_of_cliques_edges, rmat_edges)

#: brute-force oracle bound — small enough that every example stays cheap
ORACLE_MAX_M = 90


def _star(k):
    return np.stack([np.zeros(k, np.int64), np.arange(1, k + 1)], axis=1)


def _clique(k, base=0):
    src, dst = np.nonzero(np.triu(np.ones((k, k)), 1))
    return np.stack([src + base, dst + base], axis=1).astype(np.int64)


def _disconnected(seed):
    """Clique ⊔ star ⊔ path — three components with different trussness."""
    rng = np.random.default_rng(seed)
    k = int(rng.integers(3, 6))
    parts = [_clique(k), _star(4) + 20,
             np.array([[30, 31], [31, 32], [32, 33]], np.int64)]
    return np.concatenate(parts, axis=0)


@st.composite
def raw_graph(draw):
    """A raw (k, 2) edge array — possibly loopy, duplicated, or swapped."""
    kind = draw(st.sampled_from(
        ["er", "powerlaw", "star", "clique", "disconnected", "noisy"]))
    seed = draw(st.integers(min_value=0, max_value=9999))
    if kind == "er":
        n = draw(st.integers(min_value=4, max_value=26))
        deg = draw(st.integers(min_value=2, max_value=8))
        return erdos_renyi_edges(n, avg_degree=float(deg), seed=seed)
    if kind == "powerlaw":
        n = draw(st.integers(min_value=6, max_value=22))
        return barabasi_albert_edges(
            n, m_attach=draw(st.integers(min_value=2, max_value=4)),
            seed=seed)
    if kind == "star":
        return _star(draw(st.integers(min_value=2, max_value=14)))
    if kind == "clique":
        return _clique(draw(st.integers(min_value=3, max_value=7)))
    if kind == "disconnected":
        return _disconnected(seed)
    # noisy: self-loops, duplicate and endpoint-swapped rows included
    n = draw(st.integers(min_value=3, max_value=14))
    k = draw(st.integers(min_value=1, max_value=40))
    rng = np.random.default_rng(seed)
    return np.stack([rng.integers(0, n, k), rng.integers(0, n, k)],
                    axis=1).astype(np.int64)


def _assert_matrix_agrees(raw_edges, *, chunk=1 << 14):
    """Canonicalize, run both peel executors, compare bitwise (+ oracle)."""
    E = edges_from_arrays(raw_edges[:, 0], raw_edges[:, 1])
    g = build_csr(E)
    base = pkt(g, mode="chunked", chunk=chunk)
    for pm in PEEL_MODES:
        res = pkt(g, mode=pm, chunk=chunk)
        assert np.array_equal(res.trussness, base.trussness), pm
        assert np.array_equal(res.support, base.support), pm
        assert (res.levels, res.sublevels) == (base.levels, base.sublevels), \
            pm
    if g.m <= ORACLE_MAX_M:
        assert np.array_equal(base.trussness, truss_numpy(g.El))
    return base


# ------------------------------------------------------------- property ----

@settings(max_examples=6, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
@given(raw_graph())
def test_parity_matrix_random(edges):
    _assert_matrix_agrees(edges)


# -------------------------------------------------------- named fixtures ----

NAMED = {
    "empty": np.zeros((0, 2), np.int64),
    "single_edge": np.array([[0, 1]], np.int64),
    "triangle_free_path": np.array([[0, 1], [1, 2], [2, 3]], np.int64),
    "star": _star(9),
    "clique": _clique(7),
    "disconnected": _disconnected(0),
    "ring_of_cliques": ring_of_cliques_edges(4, 5),
    "rmat": rmat_edges(5, edge_factor=4, seed=11),
    "multi_edge_with_loops": np.array(
        [[0, 1], [1, 0], [0, 1], [2, 2], [1, 2], [0, 2], [3, 3], [2, 3]],
        np.int64),
}


@pytest.mark.parametrize("name", sorted(NAMED))
def test_parity_matrix_named(name):
    raw = NAMED[name]
    if name == "empty":
        g = build_csr(raw)
        for pm in PEEL_MODES:
            res = pkt(g, mode=pm)
            assert res.trussness.shape == (0,), pm
        return
    _assert_matrix_agrees(raw)


def test_parity_matrix_small_chunks():
    """Chunk boundaries must not affect either executor."""
    raw = ring_of_cliques_edges(3, 5)
    for chunk in (4, 32):
        _assert_matrix_agrees(raw, chunk=chunk)


@pytest.mark.parametrize("seed", range(4))
@pytest.mark.parametrize("scale", [6, 7, 8])
def test_graph500_rmat_parity(scale, seed):
    """Graph500 Kronecker graphs (A/B/C .57/.19/.19, edge factor 16),
    relabelled by coreness as the benchmark's one-shot cell does: both
    executors and the oracle agree on every count."""
    E = rmat_edges(scale, edge_factor=16, seed=seed)
    n = int(E.max()) + 1
    g = build_csr(relabel(E, degeneracy_order(E, n)), n)
    ref = truss_numpy(g.El)
    dense, chunked = (pkt(g, mode=pm) for pm in ("dense", "chunked"))
    assert np.array_equal(chunked.trussness, ref)
    assert np.array_equal(dense.trussness, ref)
    assert np.array_equal(dense.support, chunked.support)
    assert (dense.levels, dense.sublevels, dense.compactions) == \
        (chunked.levels, chunked.sublevels, chunked.compactions)
    assert chunked.chunk_visits <= dense.chunk_visits
