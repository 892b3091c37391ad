"""Device-side table construction (DESIGN.md §10): the jitted XLA builders
must reproduce the host builders row-for-row — the support wedge table as
built, the peel table as resolved triangle rows ``(e1, e2, e3)`` (the host
resolve is ``build_peel_table`` + ``probe_np``, as in
``truss_inc.triangles_through``) — and every pipeline that consumes them
(support, pkt, engine, dist) must be bitwise identical across
``table_mode`` ∈ {numpy, device}.

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
from repro.core.pkt import chunk_ranges, pkt
from repro.core.truss_inc import triangles_through
from repro.graphs.csr import build_csr, edges_from_arrays
from repro.graphs.gen import (barabasi_albert_edges, erdos_renyi_edges,
                              ring_of_cliques_edges, rmat_edges)
from repro.kernels.wedge_common import PAD_N, next_pow2, pad1, probe_np


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

    _assert_peel_rows_equal(g, ptab)


def _host_rows(g, ptab):
    """The host resolve written out: ``probe_np`` over the wedge table,
    hits kept as (e1, Eid[cand], Eid[safe])."""
    if ptab.size == 0:
        z = np.zeros(0, np.int64)
        return z, z, z
    hit, safe = probe_np(g.N, ptab.cand_slot, ptab.lo, ptab.hi,
                         iters=support_mod._search_iters(g))
    return (ptab.e1[hit], g.Eid[ptab.cand_slot[hit]], g.Eid[safe[hit]])


def _row_set(e1, e2, e3):
    """Rows as a sorted (k, 3) array: equal arrays mean equal multisets of
    rows per ``e1`` group."""
    rows = np.stack([np.asarray(e1), np.asarray(e2), np.asarray(e3)],
                    axis=1).astype(np.int64)
    return rows[np.lexsort(rows.T[::-1])] if rows.size else rows


def _build_dev(g, *, m_out=None, chunk=64):
    """``_build_peel_table_dev`` of ``g`` at its pow2 size, edge space
    ``m_out`` (padded like a compacted sub-problem when above ``g.m``)."""
    m_out = g.m if m_out is None else m_out
    pp = next_pow2(max(1, support_mod.peel_table_size(g)))
    chunk = max(1, min(chunk, pp))
    n_es = next_pow2(g.n + 1)
    out = support_mod._build_peel_table_dev(
        jnp.asarray(pad1(g.El[:, 0], m_out, 0)),
        jnp.asarray(pad1(g.El[:, 1], m_out, 0)),
        jnp.asarray(pad1(g.Es, n_es, 2 * g.m)),
        jnp.asarray(pad1(g.N, 2 * m_out, PAD_N)),
        jnp.asarray(pad1(g.Eid, 2 * m_out, m_out)), jnp.int32(g.m),
        m=m_out, size=pp, chunk=chunk, iters=support_mod._search_iters(g))
    return [np.asarray(x) for x in out], chunk


def _assert_peel_rows_equal(g, ptab, *, m_out=None):
    """The device builder's resolved rows equal the host resolve, in the
    same order, with the sentinel in all three columns after them; each
    edge's row count is its support; the chunk metadata is the host's."""
    m_out = g.m if m_out is None else m_out
    (e1, e2, e3, c_start, c_end, has, hits), chunk = _build_dev(g,
                                                                 m_out=m_out)
    rows = support_mod.resolve_peel_table(g, ptab)
    want = _host_rows(g, ptab)
    k = rows.size
    assert int(hits) == k == want[0].shape[0]
    for got, host, ref in zip((e1, e2, e3), (rows.e1, rows.e2, rows.e3),
                              want):
        assert np.array_equal(got[:k], host)
        assert np.array_equal(host, ref)
        assert (got[k:] == m_out).all()              # sentinel padding
    assert np.array_equal(_row_set(e1[:k], e2[:k], e3[:k]),
                          _row_set(*triangles_through(g, np.arange(g.m))))
    S = support_mod.compute_support(g, table_mode="numpy")
    assert np.array_equal(np.diff(rows.off), S)
    assert np.array_equal(np.bincount(e1[:k], minlength=g.m)[:g.m], S)
    h_has, h_cs, h_ce = chunk_ranges(rows.off, chunk, m_out=m_out)
    assert np.array_equal(has, h_has)
    assert np.array_equal(c_start[h_has], h_cs[h_has])
    assert np.array_equal(c_end[h_has], h_ce[h_has])


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


#: pow2 edge spaces of compacted sub-problems (``pkt._make_subproblem``):
#: graph -> the m_out its edge space pads to
_PADDED = {"clique": 32, "ring_of_cliques": 64, "rmat": 256, "star": 16,
           "path": 8}


@pytest.mark.parametrize("name", sorted(_PADDED))
def test_peel_rows_in_padded_edge_space(name):
    g = _graph(ADVERSARIAL[name])
    assert _PADDED[name] > g.m
    _assert_peel_rows_equal(g, support_mod.build_peel_table(g),
                            m_out=_PADDED[name])


@pytest.mark.parametrize("hub", [7, 8])
def test_padded_probe_bound_is_a_shape_of_the_bucket(monkeypatch, hub):
    """A compacted sub-problem's builder is bounded by its pow2 vertex
    dimension, not by its largest degree: two graphs of one bucket whose
    largest degrees (7, 8) straddle a power of two compile one builder,
    and the rows still equal the host resolve."""
    from repro.core.pkt import prepare_peel_device

    E = np.concatenate([np.stack([np.zeros(hub, np.int64),
                                  np.arange(1, hub + 1)], axis=1),
                        np.array([[1, 2], [3, 4], [5, 6]], np.int64)])
    g = build_csr(edges_from_arrays(E[:, 0], E[:, 1], 10), 10)
    assert int(g.degrees.max()) == hub
    seen = []
    real = support_mod._build_peel_table_dev

    def spy(*args, **kw):
        seen.append(kw["iters"])
        return real(*args, **kw)

    monkeypatch.setattr(support_mod, "_build_peel_table_dev", spy)
    tabs, _, _, hits = prepare_peel_device(g, 8, m_out=16, m_real=g.m)
    assert seen == [next_pow2(g.n + 1).bit_length()] == [5]
    assert seen[0] >= support_mod._search_iters(g)
    rows = support_mod.resolve_peel_table(g)
    assert int(hits) == rows.size == 9
    assert np.array_equal(np.asarray(tabs.e1)[:rows.size], rows.e1)
    assert np.array_equal(np.asarray(tabs.e2)[:rows.size], rows.e2)
    assert np.array_equal(np.asarray(tabs.e3)[:rows.size], rows.e3)


def test_peel_rows_vmapped_over_a_size_class():
    """One vmapped builder over graphs of one size class, as the batched
    engine runs it: each graph's rows are its own host resolve."""
    gs = [_graph(ADVERSARIAL[k]) for k in ("clique", "ring_of_cliques",
                                           "path", "star")]
    gs.append(_graph(erdos_renyi_edges(14, avg_degree=6.0, seed=2)))
    m, n_es, iters = 128, 64, 9
    size = next_pow2(max(support_mod.peel_table_size(g) for g in gs))
    cols = {
        "u": np.stack([pad1(g.El[:, 0], m, 0) for g in gs]),
        "v": np.stack([pad1(g.El[:, 1], m, 0) for g in gs]),
        "Es": np.stack([pad1(g.Es, n_es, 2 * g.m) for g in gs]),
        "N": np.stack([pad1(g.N, 2 * m, PAD_N) for g in gs]),
        "Eid": np.stack([pad1(g.Eid, 2 * m, m) for g in gs]),
        "m_real": np.array([g.m for g in gs], np.int32)}
    assert max(g.m for g in gs) <= m and max(g.n for g in gs) < n_es
    assert all(support_mod._search_iters(g) <= iters for g in gs)

    def one(u, v, Es, N, Eid, m_real):
        return support_mod._build_peel_table_dev(
            u, v, Es, N, Eid, m_real, m=m, size=size, chunk=16, iters=iters)

    out = jax.vmap(one)(*(jnp.asarray(cols[k]) for k in
                          ("u", "v", "Es", "N", "Eid", "m_real")))
    for i, g in enumerate(gs):
        rows = support_mod.resolve_peel_table(g)
        k = rows.size
        e1, e2, e3, _, _, has, hits = (np.asarray(x)[i] for x in out)
        assert int(hits) == k
        assert np.array_equal(e1[:k], rows.e1)
        assert np.array_equal(e2[:k], rows.e2)
        assert np.array_equal(e3[:k], rows.e3)
        assert (np.stack([e1, e2, e3])[:, k:] == m).all()
        assert np.array_equal(has[:g.m], np.diff(rows.off) > 0)
        assert not has[g.m:].any()


@pytest.mark.parametrize("blocks", ["whole", "small"])
@pytest.mark.parametrize("shards", [1, 2, 3, 5])
def test_peel_rows_slices_cover_the_table(monkeypatch, shards, blocks):
    """Slices ``[start, start + size)`` of the wedge rows (one a device of
    ``pkt_dist``) each keep their own hits, in order, with offsets into
    the slice, whether a slice is resolved in one block or in blocks far
    smaller than it (``next_pow2(m)`` rows): concatenated, they are the
    whole table's rows."""
    g = _graph(ADVERSARIAL["rmat"])
    if blocks == "small":
        monkeypatch.setattr(support_mod, "_RESOLVE_BLOCK", 1)
    rows = support_mod.resolve_peel_table(g)
    per = -(-support_mod.peel_table_size(g) // shards)
    assert next_pow2(g.m) < per            # "small": several blocks a slice
    dev = g.device_arrays()
    fn = jax.jit(support_mod.peel_rows,
                 static_argnames=("m", "size", "chunk", "iters"))
    got = [[], [], []]
    support = np.zeros(g.m, np.int64)
    for k in range(shards):
        e1, e2, e3, off, c_start, c_end, has = (np.asarray(x) for x in fn(
            dev["El"][:, 0], dev["El"][:, 1], dev["Es"], dev["N"],
            dev["Eid"], jnp.int32(g.m), m=g.m, size=per, chunk=8,
            iters=support_mod._search_iters(g), start=jnp.int32(k * per)))
        kept = int(off[g.m])
        for col, x in zip(got, (e1, e2, e3)):
            col.append(x[:kept])
            assert (x[kept:] == g.m).all()
        support += np.diff(off)
        h_has, h_cs, h_ce = chunk_ranges(off.astype(np.int64), 8)
        assert np.array_equal(has, h_has)
        assert np.array_equal(c_start[has], h_cs[has])
        assert np.array_equal(c_end[has], h_ce[has])
    for col, want in zip(got, (rows.e1, rows.e2, rows.e3)):
        assert np.array_equal(np.concatenate(col), want)
    assert np.array_equal(support, np.diff(rows.off))


@settings(max_examples=6, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
@given(raw_graph())
def test_support_table_mode_parity(raw):
    g = _graph(raw)
    if g.m == 0:
        return
    base = support_mod.compute_support(g, table_mode="numpy")
    S = support_mod.compute_support(g, table_mode="device")
    assert np.array_equal(S, base)
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
    """Segment expansion runs no per-row loop: it is a scatter and a prefix
    sum, and so is the peel builder's compaction of its hits.  The two
    ``while`` loops the peel builder may hold are its loop over blocks of
    wedge rows and, inside it, its probe, the binary search each wedge row
    runs once; any other (a search in the expansion, or a loop in the
    compaction) is a per-row loop come back."""
    g = _graph(rmat_edges(6, edge_factor=5, seed=3))
    dev = g.device_arrays()
    u, v = dev["El"][:, 0], dev["El"][:, 1]
    if table == "peel":
        low = support_mod._build_peel_table_dev.lower(
            u, v, dev["Es"], dev["N"], dev["Eid"], jnp.int32(g.m), m=g.m,
            size=1 << 12, chunk=64, iters=support_mod._search_iters(g))
        probes = 2
    else:
        low = support_mod._build_support_table_dev.lower(
            u, v, dev["Es"], dev["Eo"], jnp.int32(g.m), m=g.m, size=1 << 12)
        probes = 0
    assert low.as_text().count("stablehlo.while") == probes
