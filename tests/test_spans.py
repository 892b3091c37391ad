"""Spans and counters inside the one-shot path (``repro.spans``).

``truss_pkt`` / ``pkt`` open a span at each layer boundary, all sharing
one decomposition id; peel segments carry levels, sub-levels and chunk
visits.  ``PKTResult.phases`` is read from the same spans.  The counts
are checked against a plain numpy recount of the level / sub-level peel,
and against themselves with a profiler recording.
"""

import glob
import importlib
import json
import os
import pathlib
import pkgutil

import numpy as np
import pytest

import jax

import repro
from repro import spans
from repro.core.pkt import PEEL_MODES, pkt, truss_pkt
from repro.core import support as support_mod
from repro.core.support import resolve_peel_table
from repro.graphs.csr import build_csr, degeneracy_order, relabel
from repro.graphs.gen import ring_of_cliques_edges, rmat_edges

ROOT = pathlib.Path(__file__).resolve().parents[1]
METRICS = sorted((ROOT / "chipbench" / "metrics").glob("*.json"))


def _graph(name):
    E = {"rmat": lambda: rmat_edges(6, edge_factor=5, seed=1),
         "cliques": lambda: ring_of_cliques_edges(5, 6)}[name]()
    n = int(E.max()) + 1
    return E, build_csr(relabel(E, degeneracy_order(E, n)), n)


@pytest.fixture()
def ring():
    """An empty ring before the test, emptied again after it."""
    spans.drain()
    yield
    spans.drain()


def _profiled(tmp_path, fn):
    """``fn()`` under a CPU profiler trace; returns (result, {name: stats})
    of the ``repro.`` host events."""
    with jax.profiler.trace(str(tmp_path)):
        out = fn()
    path, = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    events = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("repro."):
                    events.setdefault(ev.name, []).append(dict(ev.stats))
    return out, events


def test_spans_nest_under_one_decomposition(ring):
    E, _ = _graph("rmat")
    truss_pkt(E, compact_frac=0.99, compact_min=0)
    recs = spans.drain()
    by_id = {r.id: r for r in recs}
    root, = [r for r in recs if r.name == "truss_pkt"]
    assert root.parent is None and root.decomp == root.id
    assert {r.decomp for r in recs} == {root.id}
    want_parent = {"truss_pkt.prep": "truss_pkt", "pkt": "truss_pkt",
                   "truss_pkt.align": "truss_pkt", "pkt.support": "pkt",
                   "pkt.tables": "pkt", "pkt.peel_segment": "pkt",
                   "pkt.compact": "pkt"}
    assert {r.name for r in recs} == set(want_parent) | {"truss_pkt"}
    for r in recs:
        if r.name in want_parent:
            parent = by_id[r.parent]
            assert parent.name == want_parent[r.name]
            assert parent.start_ns <= r.start_ns <= r.end_ns <= parent.end_ns
    segs = [r for r in recs if r.name == "pkt.peel_segment"]
    assert [s.attrs["segment"] for s in segs] == list(range(len(segs)))
    assert len([r for r in recs if r.name == "pkt.compact"]) == len(segs) - 1
    # a second decomposition starts a new id
    truss_pkt(E)
    assert {r.decomp for r in spans.drain()}.isdisjoint({root.id})


def test_metadata_set_at_exit_reaches_ring_and_trace(ring, tmp_path):
    def body():
        with spans.span("outer", n=3) as sp:
            with spans.span("outer.inner"):
                pass
            sp.set(levels=7)
    _, events = _profiled(tmp_path, body)
    inner, outer = spans.drain()
    assert outer.attrs == {"n": 3, "levels": 7} and outer.traced
    assert inner.parent == outer.id and inner.traced
    assert events["repro.outer"] == [{"n": 3, "levels": 7}]
    assert "repro.outer.inner" in events
    with spans.span("untraced") as sp:
        pass
    assert not sp.traced


def test_ring_stays_bounded(ring):
    for i in range(spans.RING_SIZE + 10):
        with spans.span("tick", i=i):
            pass
    recs = spans.records()
    assert len(recs) == spans.RING_SIZE
    assert recs[0].attrs["i"] == 10          # the oldest went first
    assert len(spans.drain()) == spans.RING_SIZE
    assert spans.records() == []


@pytest.mark.parametrize("kw", [
    dict(), dict(table_mode="numpy"), dict(compact_frac=0.99, compact_min=0),
    dict(empty=True)], ids=["device", "numpy", "compacting", "empty"])
def test_phases_keep_their_four_keys(ring, kw):
    _, g = _graph("rmat")
    if kw.pop("empty", False):
        g = build_csr(np.zeros((0, 2), np.int64), 1)
    res = pkt(g, phase_timings=True, **kw)
    assert set(res.phases) == {"tables", "support", "peel", "compact"}
    assert all(v >= 0.0 for v in res.phases.values())
    if g.m:
        assert res.phases["peel"] > 0.0 and res.phases["support"] > 0.0
        assert (res.phases["compact"] > 0.0) == (res.compactions > 0)
    assert pkt(g, **kw).phases is None


def _triangles(El):
    """Every triangle as a triple of edge ids (rows of ``El``)."""
    eid = {(int(u), int(v)): i for i, (u, v) in enumerate(El)}
    nbrs = {}
    for u, v in eid:
        nbrs.setdefault(u, set()).add(v)
        nbrs.setdefault(v, set()).add(u)
    return [(i, eid[(u, w)], eid[(v, w)]) for (u, v), i in eid.items()
            for w in nbrs[u] & nbrs[v] if w > v]


def _recount(g, off, chunk, n_chunks):
    """Plain level / sub-level peel: (sub-levels, chunks overlapping each
    sub-level's frontier, summed)."""
    tri = _triangles(g.El)
    S = np.zeros(g.m, np.int64)
    for t in tri:
        S[list(t)] += 1
    processed = np.zeros(g.m, bool)
    has = off[1:] > off[:-1]
    subs = visits = 0
    while not processed.all():
        l = S[~processed].min()
        curr = ~processed & (S == l)
        while curr.any():
            subs += 1
            active = np.zeros(n_chunks, bool)
            for e in np.nonzero(curr & has)[0]:
                active[off[e] // chunk:(off[e + 1] - 1) // chunk + 1] = True
            visits += int(active.sum())
            dec = np.zeros(g.m, np.int64)
            for t in tri:
                t = list(t)
                if processed[t].any() or not curr[t].any():
                    continue
                for y in t:
                    if not curr[y] and S[y] > l:
                        dec[y] += 1
            hit = ~processed & ~curr & (dec > 0)
            S[hit] = np.maximum(S[hit] - dec[hit], l)
            processed |= curr
            curr = ~processed & (S == l)
    return subs, visits


@pytest.mark.parametrize("mode", PEEL_MODES)
@pytest.mark.parametrize("graph", ["rmat", "cliques"])
@pytest.mark.parametrize("table_mode", ["numpy", "device"])
def test_chunk_visits_match_a_numpy_recount(ring, graph, mode, table_mode):
    _, g = _graph(graph)
    res = pkt(g, mode=mode, chunk=16, table_mode=table_mode,
              compact_frac=None)
    seg, = [r for r in spans.drain() if r.name == "pkt.peel_segment"]
    chunk, n_chunks = seg.attrs["chunk"], seg.attrs["n_chunks"]
    assert seg.attrs["chunk_visits"] == res.chunk_visits
    assert seg.attrs["sublevels"] == res.sublevels
    if mode == "dense":
        assert res.chunk_visits == n_chunks * res.sublevels
    else:
        subs, visits = _recount(g, resolve_peel_table(g).off, chunk,
                                n_chunks)
        assert (res.sublevels, res.chunk_visits) == (subs, visits)


@pytest.mark.parametrize("table_mode", ["numpy", "device"])
@pytest.mark.parametrize("mode", PEEL_MODES)
def test_chunk_visits_sum_over_segments(ring, mode, table_mode):
    """A compacting decomposition of a Graph500 Kronecker graph: the
    ``pkt.peel_segment`` spans' chunk visits and sub-levels sum to the
    result's, and a dense segment visits every one of its chunks at each of
    its sub-levels — the counters ``peel_scan_pct`` reads, segment by
    segment."""
    g = build_csr(rmat_edges(8, edge_factor=16))
    res = pkt(g, mode=mode, table_mode=table_mode, compact_frac=0.9,
              compact_min=1)
    segs = [r.attrs for r in spans.drain() if r.name == "pkt.peel_segment"]
    assert len(segs) == res.compactions + 1 >= 2
    assert [s["segment"] for s in segs] == list(range(len(segs)))
    assert sum(s["chunk_visits"] for s in segs) == res.chunk_visits
    assert sum(s["sublevels"] for s in segs) == res.sublevels
    assert sum(s["levels"] for s in segs) == res.levels
    for s in segs:
        if mode == "dense":
            assert s["chunk_visits"] == s["n_chunks"] * s["sublevels"]
        else:
            assert s["chunk_visits"] <= s["n_chunks"] * s["sublevels"]


@pytest.mark.parametrize("table_mode", ["numpy", "device"])
def test_peel_segments_carry_table_rows_and_hits(ring, table_mode):
    """Each segment's table: its wedge rows (``table_rows``) and the rows
    the build kept (``table_hits``), three per triangle of the segment's
    graph — 3 x 35 for the first segment of a 7-clique ring."""
    E = ring_of_cliques_edges(4, 7)
    g = build_csr(E)
    T = len(_triangles(g.El))
    assert T == 4 * 35
    res = pkt(g, chunk=16, table_mode=table_mode, compact_frac=0.99,
              compact_min=0)
    segs = [r for r in spans.drain() if r.name == "pkt.peel_segment"]
    assert len(segs) == res.compactions + 1 >= 2
    first = segs[0].attrs
    assert first["table_rows"] == support_mod.peel_table_size(g)
    assert first["table_hits"] == 3 * T
    for seg in segs[1:]:
        assert 0 <= seg.attrs["table_hits"] <= seg.attrs["table_rows"]


@pytest.mark.parametrize("table_mode", ["numpy", "device"])
def test_rebuild_triangle_list_span_counts_its_table_rows(ring, table_mode):
    """A handle's full rebuild takes its triangle list from the peel table:
    the ``inc.triangle_list`` span's ``rows`` (triangles returned) are a
    third of its ``table_hits`` (rows the first segment's table kept), at
    open and at a rebuild forced by ``local_frac=0``."""
    from repro.core.truss_inc import IncrementalTruss

    E, _ = _graph("rmat")
    inc = IncrementalTruss(E, table_mode=table_mode, local_frac=0.0,
                           compact_frac=0.99, compact_min=0)
    inc.update(remove_edges=E[::9])
    recs = spans.drain()
    lists = [r.attrs for r in recs if r.name == "inc.triangle_list"]
    firsts = [r.attrs for r in recs
              if r.name == "pkt.peel_segment" and r.attrs["segment"] == 0]
    assert len(lists) == len(firsts) == 2
    for tl, seg in zip(lists, firsts):
        assert 3 * tl["rows"] == tl["table_hits"] == seg["table_hits"] > 0
    assert lists[-1]["rows"] == inc.triangles.shape[0]


def test_one_shot_path_selects_no_triangles(ring, monkeypatch):
    """``truss_pkt`` and ``pkt(g)`` return no triangle list and never
    dispatch the selection jit, so the one-shot path runs exactly its
    device programs; ``pkt(g, triangles=True)`` dispatches it once."""
    calls = []
    select = support_mod._triangle_rows_dev

    def counted(*args, **kw):
        calls.append(kw)
        return select(*args, **kw)

    monkeypatch.setattr(support_mod, "_triangle_rows_dev", counted)
    E, g = _graph("rmat")
    truss_pkt(E)
    assert pkt(g).triangles is None
    assert calls == []
    rows, hits = pkt(g, triangles=True).triangles.read()
    assert len(calls) == 1 and 3 * rows.shape[0] == hits


def test_resolve_runs_in_the_peel_table_build():
    """The probe runs once, inside ``_build_peel_table_dev`` (where
    ``tables_dev_ms`` reads it), and the peel jits take no probe bound and
    run no probe: the chunked peel lowers to its level, sub-level and
    chunk loops and nothing more."""
    import inspect

    pkt_mod = importlib.import_module("repro.core.pkt")
    _, g = _graph("rmat")
    dev = g.device_arrays()
    build = support_mod._build_peel_table_dev.lower(
        dev["El"][:, 0], dev["El"][:, 1], dev["Es"], dev["N"], dev["Eid"],
        jax.numpy.int32(g.m), m=g.m, size=1 << 12, chunk=64,
        iters=support_mod._search_iters(g))
    assert "stablehlo.while" in build.as_text()       # the one probe
    for fn in (pkt_mod._peel_segment_jit, pkt_mod._pkt_peel_jit,
               pkt_mod._peel_loop):
        params = inspect.signature(fn).parameters
        assert not {"iters", "N", "Eid"} & set(params), fn
    tabs, chunk, n_chunks, _ = pkt_mod.prepare_peel_device(g, 64)
    S = jax.numpy.zeros((g.m + 1,), jax.numpy.int32)
    proc = jax.numpy.zeros((g.m + 1,), bool).at[g.m].set(True)
    for mode, loops in (("chunked", 3), ("dense", 3)):
        text = pkt_mod._peel_segment_jit.lower(
            S, proc, jax.numpy.int32(0), None, tabs, m=g.m, chunk=chunk,
            n_chunks=n_chunks, mode=mode).as_text()
        assert text.count("stablehlo.while") == loops, mode


def test_counts_are_the_same_with_the_profiler_on(ring, tmp_path):
    _, g = _graph("rmat")
    kw = dict(compact_frac=0.99, compact_min=0)
    off = pkt(g, **kw)
    on, events = _profiled(tmp_path, lambda: pkt(g, **kw))
    counts = [(r.levels, r.sublevels, r.chunk_visits, r.compactions)
              for r in (off, on)]
    assert counts[0] == counts[1] and off.compactions > 0
    assert np.array_equal(off.trussness, on.trussness)
    segs = events["repro.pkt.peel_segment"]
    assert sum(s["sublevels"] for s in segs) == on.sublevels
    assert sum(s["chunk_visits"] for s in segs) == on.chunk_visits


def _jitted_names():
    names = set()
    for mod in pkgutil.walk_packages(repro.__path__, "repro."):
        if mod.name.startswith(("repro.core", "repro.serve", "repro.kernels")):
            module = importlib.import_module(mod.name)
            names |= {k for k, v in vars(module).items()
                      if callable(getattr(v, "lower", None))}
    return names


@pytest.fixture(scope="module")
def program_span_names():
    """Every span name the one-shot path and a handle's stream open: a
    compacting ``truss_pkt``, an open, a deletion batch, a re-insertion
    repaired locally, and a (q, k) community query."""
    from repro.serve.truss_engine import TrussEngine

    spans.drain()
    E, _ = _graph("rmat")
    truss_pkt(E, compact_frac=0.99, compact_min=0)
    eng = TrussEngine()
    h = eng.open(E, local_frac=1.0)
    rows = h.edges[::7]
    eng.update(h, remove_edges=rows)
    eng.update(h, add_edges=rows)
    h.community(int(rows[0, 0]), 3)
    names = {r.name for r in spans.drain()}
    yield names


@pytest.mark.parametrize("path", METRICS, ids=lambda p: p.stem)
def test_benchmark_metrics_name_what_the_program_has(path,
                                                     program_span_names):
    """A metric reads jits by name and spans by name: a rename reads
    nothing, so every name a metric file gives must still exist."""
    spec = json.loads(path.read_text())
    if "jits" in spec:
        assert set(spec["jits"]) <= _jitted_names()
    named = ([spec["span"]] if "span" in spec else []) \
        + spec.get("spans", []) + spec.get("roots", [])
    assert set(named) <= program_span_names
