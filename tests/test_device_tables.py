"""Device-side wedge-table construction (DESIGN.md §10): the jitted XLA
builders must reproduce the host numpy builders row-for-row, and every
pipeline that consumes them (support, pkt, engine, dist) must be bitwise
identical across ``table_mode`` ∈ {numpy, device}.

Runs under real ``hypothesis`` and under the deterministic fallback shim
(``repro/testing/hypothesis_fallback.py``) — same contract as
``tests/test_parity_matrix.py``.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hypothesis import HealthCheck, given, settings
from hypothesis import strategies as st

from repro.core import support as support_mod
from repro.core.pkt import pkt
from repro.graphs.csr import build_csr, edges_from_arrays
from repro.graphs.gen import (barabasi_albert_edges, erdos_renyi_edges,
                              ring_of_cliques_edges, rmat_edges)
from repro.kernels.wedge_common import next_pow2


def _star(k):
    return np.stack([np.zeros(k, np.int64), np.arange(1, k + 1)], axis=1)


#: adversarial shapes: empty graph, triangle-free (star has an *empty*
#: oriented support table, the path an empty-range-heavy one), raw
#: multi-edge/self-loop/swapped input (canonicalized like production entry
#: points), plus dense and skewed standards
ADVERSARIAL = {
    "empty": np.zeros((0, 2), np.int64),
    "single_edge": np.array([[0, 1]], np.int64),
    "star": _star(9),
    "path": np.array([[0, 1], [1, 2], [2, 3], [3, 4]], np.int64),
    "multi_edge_input": np.array(
        [[0, 1], [1, 0], [0, 1], [2, 2], [1, 2], [0, 2], [3, 3], [2, 3]],
        np.int64),
    "clique": edges_from_arrays(*np.nonzero(np.triu(np.ones((7, 7)), 1)), 7),
    "ring_of_cliques": ring_of_cliques_edges(4, 5),
    "rmat": rmat_edges(6, edge_factor=5, seed=3),
}


def _graph(raw):
    E = edges_from_arrays(raw[:, 0], raw[:, 1]) if raw.size else raw
    return build_csr(E)


def _assert_tables_equal(g):
    """Device builders reproduce the numpy builders bit-for-bit, with inert
    sentinel padding beyond the real entries."""
    stab = support_mod.build_support_table(g)
    ptab = support_mod.build_peel_table(g)
    assert support_mod.support_table_size(g) == stab.size
    assert support_mod.peel_table_size(g) == ptab.size
    if g.m == 0:
        return
    dev = g.device_arrays()

    sp = next_pow2(max(1, stab.size))
    e1, cand, lo, hi, off = support_mod._build_support_table_dev(
        dev["El"][:, 0], dev["El"][:, 1], dev["Es"], dev["Eo"],
        jnp.int32(g.m), m=g.m, size=sp)
    k = stab.size
    assert np.array_equal(np.asarray(e1)[:k], stab.e1)
    assert np.array_equal(np.asarray(cand)[:k], stab.cand_slot)
    assert np.array_equal(np.asarray(lo)[:k], stab.lo)
    assert np.array_equal(np.asarray(hi)[:k], stab.hi)
    assert np.array_equal(np.asarray(off), stab.off)
    assert (np.asarray(e1)[k:] == g.m).all()          # anchor sentinel
    assert (np.asarray(lo)[k:] == np.asarray(hi)[k:]).all()  # empty range

    pp = next_pow2(max(1, ptab.size))
    chunk = max(1, min(64, pp))
    e1, cand, lo, hi, off, c_start, c_end, has = \
        support_mod._build_peel_table_dev(
            dev["El"][:, 0], dev["El"][:, 1], dev["Es"], jnp.int32(g.m),
            m=g.m, size=pp, chunk=chunk)
    k = ptab.size
    assert np.array_equal(np.asarray(e1)[:k], ptab.e1)
    assert np.array_equal(np.asarray(cand)[:k], ptab.cand_slot)
    assert np.array_equal(np.asarray(lo)[:k], ptab.lo)
    assert np.array_equal(np.asarray(hi)[:k], ptab.hi)
    assert np.array_equal(np.asarray(off), ptab.off)
    assert (np.asarray(e1)[k:] == g.m).all()
    # chunk-range metadata matches the host bookkeeping
    from repro.core.pkt import chunk_ranges

    h_has, h_cs, h_ce = chunk_ranges(ptab.off, chunk)
    assert np.array_equal(np.asarray(has), h_has)
    assert np.array_equal(np.asarray(c_start)[h_has], h_cs[h_has])
    assert np.array_equal(np.asarray(c_end)[h_has], h_ce[h_has])


@pytest.mark.parametrize("name", sorted(ADVERSARIAL))
def test_builders_equal_adversarial(name):
    _assert_tables_equal(_graph(ADVERSARIAL[name]))


@st.composite
def raw_graph(draw):
    kind = draw(st.sampled_from(["er", "powerlaw", "noisy"]))
    seed = draw(st.integers(min_value=0, max_value=9999))
    if kind == "er":
        n = draw(st.integers(min_value=4, max_value=26))
        return erdos_renyi_edges(
            n, avg_degree=float(draw(st.integers(min_value=2, max_value=8))),
            seed=seed)
    if kind == "powerlaw":
        return barabasi_albert_edges(
            draw(st.integers(min_value=6, max_value=22)),
            m_attach=draw(st.integers(min_value=2, max_value=4)), seed=seed)
    n = draw(st.integers(min_value=3, max_value=14))
    k = draw(st.integers(min_value=1, max_value=40))
    rng = np.random.default_rng(seed)
    return np.stack([rng.integers(0, n, k), rng.integers(0, n, k)],
                    axis=1).astype(np.int64)


@settings(max_examples=8, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
@given(raw_graph())
def test_builders_equal_random(raw):
    g = _graph(raw)
    if g.m == 0:
        return
    _assert_tables_equal(g)


@settings(max_examples=6, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
@given(raw_graph())
def test_support_table_mode_parity(raw):
    g = _graph(raw)
    if g.m == 0:
        return
    base = support_mod.compute_support(g, table_mode="numpy")
    for mode in support_mod.SUPPORT_MODES:
        S = support_mod.compute_support(g, mode=mode, table_mode="device")
        assert np.array_equal(S, base), mode
        assert S.dtype == base.dtype


def test_pkt_table_mode_parity_full_result():
    for raw in (ring_of_cliques_edges(3, 5), rmat_edges(6, edge_factor=4,
                                                        seed=7)):
        g = _graph(raw)
        a = pkt(g, table_mode="numpy")
        b = pkt(g, table_mode="device")
        assert np.array_equal(a.trussness, b.trussness)
        assert np.array_equal(a.support, b.support)
        assert (a.levels, a.sublevels) == (b.levels, b.sublevels)


def test_device_arrays_cached_per_graph():
    g = _graph(ring_of_cliques_edges(3, 4))
    d1 = g.device_arrays()
    d2 = g.device_arrays()
    assert d1 is d2
    assert d1["N"] is d2["N"]
    assert set(d1) == {"N", "Eid", "Es", "Eo", "El"}
    assert np.array_equal(np.asarray(d1["N"]), g.N)


def test_invalid_table_mode_rejected():
    g = _graph(np.array([[0, 1]], np.int64))
    with pytest.raises(ValueError, match="table_mode"):
        pkt(g, table_mode="gpu")
    with pytest.raises(ValueError, match="table_mode"):
        support_mod.compute_support(g, table_mode="gpu")
    from repro.serve.truss_engine import TrussEngine

    with pytest.raises(ValueError, match="table_mode"):
        TrussEngine(table_mode="gpu")


def test_prebuilt_table_forces_numpy_path():
    """Passing a prebuilt host table keeps the legacy path (the table is
    honored, not silently rebuilt on device)."""
    g = _graph(ring_of_cliques_edges(3, 4))
    stab = support_mod.build_support_table(g)
    ptab = support_mod.build_peel_table(g)
    res = pkt(g, support_table=stab, peel_table=ptab)
    assert np.array_equal(res.trussness, pkt(g).trussness)


# --- segment expansion (support._expand_segments) ---------------------------

def _expand_oracle(off, size, m, start):
    """The row → segment map by ``jnp.searchsorted`` over the offsets."""
    idx = start + jnp.arange(size, dtype=jnp.int32)
    e1 = jnp.searchsorted(off[1:], idx, side="right").astype(jnp.int32)
    e1c = jnp.minimum(e1, m - 1)
    valid = idx < off[m]
    return jnp.where(valid, e1, m), e1c, idx - off[e1c], valid


def _offsets(cnt):
    return np.concatenate([[0], np.cumsum(cnt)]).astype(np.int32)


def _random_counts(m, seed, hi=9):
    """Segment lengths with about 30 % empty segments."""
    rng = np.random.default_rng(seed)
    cnt = rng.integers(1, hi, m)
    cnt[rng.random(m) < 0.3] = 0
    return cnt


#: name -> (segment lengths, size, start, how): ``how`` is ``eager``,
#: ``jit`` (``start`` traced) or ``vmap`` (a batch of offset arrays of one
#: padded m, as the batched engine builds them)
_EXPAND_CASES = {
    "leading_empty": ([0, 0, 3, 1, 2], 8, 0, "eager"),
    "trailing_empty": ([2, 3, 1, 0, 0], 8, 0, "eager"),
    "inner_empty_runs": ([1, 0, 0, 0, 2, 0, 3], 8, 0, "eager"),
    "all_empty": ([0, 0, 0, 0], 4, 0, "eager"),
    "m1": ([5], 8, 0, "eager"),
    "m1_empty": ([0], 2, 0, "eager"),
    "size_below_total": (_random_counts(300, 1), 256, 0, "eager"),
    "size_above_total": (_random_counts(300, 2), 4096, 0, "eager"),
    "m22728_size2pow21": (_random_counts(22728, 3, hi=90), 1 << 21, 0, "jit"),
    "jit_start_0": (_random_counts(300, 4), 512, 0, "jit"),
    "jit_start_mid": (_random_counts(300, 5), 256, 333, "jit"),
    "jit_start_past_total": (_random_counts(300, 6), 256, 5000, "jit"),
    "jit_start_mid_m1": ([7], 4, 5, "jit"),
    "size_odd": (_random_counts(50, 7), 333, 0, "eager"),
    "jit_size_384_start_mid": (_random_counts(90, 8), 384, 101, "jit"),
    "vmap_batch": ([_random_counts(40, s) for s in range(5)] + [[0] * 40],
                   256, 0, "vmap"),
}


@pytest.mark.parametrize("name", sorted(_EXPAND_CASES))
def test_expand_segments_equals_searchsorted(name):
    cnt, size, start, how = _EXPAND_CASES[name]
    if how == "vmap":
        off = jnp.asarray(np.stack([_offsets(c) for c in cnt]))
        m = off.shape[1] - 1

        def both(o):
            return (support_mod._expand_segments(o, size, m),
                    _expand_oracle(o, size, m, 0))

        got, want = jax.vmap(both)(off)
    else:
        off = jnp.asarray(_offsets(cnt))
        m = off.shape[0] - 1
        if how == "jit":
            got = jax.jit(support_mod._expand_segments,
                          static_argnums=(1, 2))(off, size, m,
                                                 jnp.int32(start))
        else:
            got = support_mod._expand_segments(off, size, m, start)
        want = _expand_oracle(off, size, m, start)
    for part, a, b in zip(("e1", "e1c", "intra", "valid"), got, want):
        assert a.dtype == b.dtype, part
        assert np.array_equal(np.asarray(a), np.asarray(b)), part


@pytest.mark.parametrize("table", ["peel", "support"])
def test_table_builders_lower_without_loops(table):
    """The builders run no per-row loop: segment expansion is a scatter and
    a prefix sum, so a ``while`` in their StableHLO means a search (or any
    other per-row loop) came back."""
    g = _graph(rmat_edges(6, edge_factor=5, seed=3))
    dev = g.device_arrays()
    u, v = dev["El"][:, 0], dev["El"][:, 1]
    if table == "peel":
        low = support_mod._build_peel_table_dev.lower(
            u, v, dev["Es"], jnp.int32(g.m), m=g.m, size=1 << 12, chunk=64)
    else:
        low = support_mod._build_support_table_dev.lower(
            u, v, dev["Es"], dev["Eo"], jnp.int32(g.m), m=g.m, size=1 << 12)
    assert "stablehlo.while" not in low.as_text()
