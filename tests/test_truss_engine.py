"""Batched multi-graph engine: order alignment, bucketing, mode parity."""

import numpy as np
import pytest

from repro.graphs.csr import edges_from_arrays
from repro.graphs.gen import ring_of_cliques_edges, rmat_edges
from repro.core.pkt import truss_pkt
from repro.serve.truss_engine import (TrussEngine, TrussHandle, truss_batched,
                                      _next_pow2)


def _er_edges(n, p, seed):
    rng = np.random.default_rng(seed)
    mask = rng.random((n, n)) < p
    src, dst = np.nonzero(np.triu(mask, 1))
    return edges_from_arrays(src, dst, n)


def _expected(edges):
    """Reference: truss_pkt on the unique canonical edges, per input row."""
    e = np.asarray(edges, np.int64)
    lo = np.minimum(e[:, 0], e[:, 1])
    hi = np.maximum(e[:, 0], e[:, 1])
    n = int(e.max()) + 1
    uniq = np.unique(lo * n + hi)
    E = np.stack([uniq // n, uniq % n], axis=1)
    t = truss_pkt(E)
    return t[np.searchsorted(uniq, lo * n + hi)]


def _mixed_fleet():
    return [
        _er_edges(12, 0.4, 0),
        ring_of_cliques_edges(3, 5),
        np.array([[0, 1]], np.int64),                  # tiny: one edge
        _er_edges(36, 0.2, 1),
        rmat_edges(6, edge_factor=4, seed=2),
        np.array([[0, 1], [1, 2]], np.int64),          # tiny: path
        _er_edges(20, 0.35, 3),
    ]


def test_mixed_sizes_order_aligned():
    """The core contract: results align to submission order and row order,
    regardless of how submissions are bucketed and reordered internally."""
    fleet = _mixed_fleet()
    eng = TrussEngine()
    tickets = [eng.submit(e) for e in fleet]
    # resolve deliberately out of submission order
    for i in reversed(range(len(tickets))):
        got = eng.result(tickets[i])
        assert np.array_equal(got, _expected(fleet[i])), i
    assert eng.stats["graphs_done"] == len(fleet)


def test_bucket_reuse_same_class():
    """Graphs of one pow2 size class share a bucket (one compile, one batch)."""
    a = _er_edges(16, 0.3, 10)
    b = _er_edges(16, 0.3, 11)
    eng = TrussEngine()
    ka = eng._size_class(*_prep(eng, a))
    kb = eng._size_class(*_prep(eng, b))
    if ka == kb:  # identical class: one batched dispatch for both
        outs = eng.map([a, b])
        assert eng.stats["batches"] == 1
        assert np.array_equal(outs[0], _expected(a))
        assert np.array_equal(outs[1], _expected(b))


def _prep(eng, edges):
    from repro.graphs.csr import build_csr
    from repro.core import support as support_mod
    e = np.asarray(edges, np.int64)
    g = build_csr(e, int(e.max()) + 1)
    return g, support_mod.build_support_table(g), \
        support_mod.build_peel_table(g)


@pytest.mark.parametrize("mode", ["dense"])
def test_engine_mode_parity(mode):
    fleet = [_er_edges(14, 0.35, 20), ring_of_cliques_edges(3, 4)]
    base = truss_batched(fleet, mode="chunked")
    got = truss_batched(fleet, mode=mode)
    for b, g_ in zip(base, got):
        assert np.array_equal(b, g_)


def test_row_alignment_swapped_and_duplicate_rows():
    """Input rows may be endpoint-swapped or duplicated; results align by row."""
    edges = np.array([[1, 0], [0, 1], [1, 2], [2, 1], [0, 2]], np.int64)
    out = TrussEngine().map([edges])[0]
    assert out.shape == (5,)
    assert (out == 3).all()  # one triangle: every row reports trussness 3


def test_empty_and_selfloop():
    eng = TrussEngine()
    t = eng.submit(np.zeros((0, 2), np.int64))
    assert eng.result(t).shape == (0,)
    with pytest.raises(ValueError, match="self-loop"):
        eng.submit(np.array([[3, 3]], np.int64))


def test_no_reorder_path():
    fleet = [_er_edges(18, 0.3, 30)]
    got = truss_batched(fleet, reorder=False)
    assert np.array_equal(got[0], _expected(fleet[0]))


def test_auto_flush_on_max_pending():
    fleet = [_er_edges(10, 0.4, s) for s in range(4)]
    eng = TrussEngine(max_pending=2)
    for e in fleet:
        eng.submit(e)
    # two auto-flushes happened; all results already materialized
    assert eng.stats["flushes"] == 2
    assert len(eng._pending) == 0


def test_next_pow2():
    assert [_next_pow2(x) for x in (1, 2, 3, 5, 8, 9)] == [1, 2, 4, 8, 8, 16]


# ------------------------------------------------------------ failure paths --

def test_oversized_graph_rejected():
    """Submissions beyond max_edges fail fast with an actionable error, and
    the engine stays serviceable afterwards."""
    eng = TrussEngine(max_edges=8)
    with pytest.raises(ValueError, match="too large.*max_edges=8"):
        eng.submit(_er_edges(20, 0.5, 0))
    assert eng.stats["graphs_done"] == 0 and not eng._pending
    t = eng.submit(np.array([[0, 1], [0, 2], [1, 2]], np.int64))
    assert (eng.result(t) == 3).all()
    # the limit counts *canonical* edges: duplicate/swapped rows collapse
    dup = np.array([[0, 1], [1, 0]] * 6, np.int64)
    t2 = TrussEngine(max_edges=1).submit(dup)
    assert t2 >= 0
    with pytest.raises(ValueError, match="max_edges"):
        TrussEngine(max_edges=0)


def test_out_of_order_result_pickup():
    """A later ticket may be redeemed first; earlier results stay intact and
    are served from the materialized store without a second flush."""
    eng = TrussEngine()
    fleet = [_er_edges(12, 0.4, 40), ring_of_cliques_edges(3, 4),
             _er_edges(30, 0.2, 41)]
    t0, t1, t2 = [eng.submit(e) for e in fleet]
    assert np.array_equal(eng.result(t2), _expected(fleet[2]))
    flushes = eng.stats["flushes"]
    assert np.array_equal(eng.result(t0), _expected(fleet[0]))
    assert np.array_equal(eng.result(t1), _expected(fleet[1]))
    assert eng.stats["flushes"] == flushes  # no extra flush needed


def test_submit_rejects_negative_and_huge_ids():
    """submit used to accept negative ids (corrupting the lo*n+hi key
    packing) and huge ids (overflowing the int32 CSR layout)."""
    eng = TrussEngine()
    with pytest.raises(ValueError, match="negative"):
        eng.submit(np.array([[-1, 2]], np.int64))
    with pytest.raises(ValueError, match="exceeds"):
        eng.submit(np.array([[0, 2**31]], np.int64))
    t = eng.submit(np.array([[0, 1], [0, 2], [1, 2]], np.int64))
    assert (eng.result(t) == 3).all()  # engine still serviceable


# ------------------------------------------------- handle lifecycle (§9) --

def test_handle_open_update_close():
    eng = TrussEngine()
    E = ring_of_cliques_edges(3, 5)
    h = eng.open(E)
    assert isinstance(h, TrussHandle)
    assert np.array_equal(h.trussness, truss_pkt(h.edges))
    st = eng.update(h, add_edges=np.array([[0, 2]]),
                    remove_edges=np.array([[0, 1]]))
    assert st.handle is h and st.mode in ("local", "full")
    assert np.array_equal(h.trussness, truss_pkt(h.edges))
    assert eng.stats["updates"] == 1
    assert eng.stats["updates_local"] + eng.stats["updates_full"] == 1
    eng.close(h)
    assert h.closed
    with pytest.raises(ValueError, match="closed"):
        eng.update(h, add_edges=np.array([[0, 3]]))
    eng.close(h)  # idempotent


def test_handle_sequence_matches_from_scratch():
    """A churned handle stays bitwise-equal to from-scratch pkt."""
    rng = np.random.default_rng(12)
    eng = TrussEngine()
    h = eng.open(_er_edges(22, 0.3, 50), local_frac=1.0)
    for _ in range(3):
        cur = h.edges
        rm = cur[rng.choice(cur.shape[0], size=2, replace=False)]
        add = np.stack([rng.integers(0, 24, 3), rng.integers(0, 24, 3)], 1)
        add = add[add[:, 0] != add[:, 1]]
        eng.update(h, add_edges=add, remove_edges=rm)
        assert np.array_equal(h.trussness, truss_pkt(h.edges))
    assert list(h.query(h.edges[:3])) == list(h.trussness[:3])


def test_ticket_promotion_to_handle():
    """update() accepts a still-pending ticket: it is consumed and promoted
    to a persistent handle carried in the returned stats."""
    eng = TrussEngine()
    E = _er_edges(14, 0.35, 60)
    t = eng.submit(E)
    st = eng.update(t, add_edges=np.array([[0, 13]]))
    h = st.handle
    assert isinstance(h, TrussHandle)
    assert np.array_equal(h.trussness, truss_pkt(h.edges))
    with pytest.raises(KeyError):        # ticket consumed by promotion
        eng.result(t)
    # a flushed/collected ticket cannot be promoted (graph released)
    t2 = eng.submit(E)
    eng.result(t2)
    with pytest.raises(KeyError, match="cannot be promoted"):
        eng.update(t2)


def test_duplicate_ticket_redemption():
    """Results are single-read: a second redemption (or an unknown ticket)
    raises KeyError rather than silently recomputing."""
    eng = TrussEngine()
    t = eng.submit(np.array([[0, 1], [0, 2], [1, 2]], np.int64))
    assert (eng.result(t) == 3).all()
    with pytest.raises(KeyError, match="already-collected"):
        eng.result(t)
    with pytest.raises(KeyError, match="unknown"):
        eng.result(10_000)


# ------------------------------------------------ scheduler hooks + flush --


def test_flush_only_selected_bucket():
    """flush(only=[key]) dispatches that bucket and leaves others pending."""
    small, big = _er_edges(12, 0.4, 30), _er_edges(40, 0.2, 31)
    eng = TrussEngine()
    ts, tb = eng.submit(small), eng.submit(big)
    ks, kb = eng.bucket_of(ts), eng.bucket_of(tb)
    assert ks is not None and kb is not None and ks != kb
    eng.flush(only=[ks])
    assert eng.bucket_of(ts) is None          # materialized
    assert eng.bucket_of(tb) == kb            # untouched
    assert np.array_equal(eng.result(ts), _expected(small))
    assert np.array_equal(eng.result(tb), _expected(big))
    # flush(only=[unknown key]) is a no-op
    eng.submit(small)
    eng.flush(only=[kb])
    assert eng.stats["graphs_done"] == 2


def test_bucket_of_and_discard():
    """discard releases a pending ticket; its result is gone for good."""
    e = _er_edges(12, 0.4, 32)
    eng = TrussEngine()
    t = eng.submit(e)
    assert eng.bucket_of(t) is not None
    eng.discard(t)
    assert eng.bucket_of(t) is None
    with pytest.raises(KeyError):
        eng.result(t)
    eng.discard(123456)                       # unknown: ignored
    # discard also drops an already-materialized result
    t2 = eng.submit(e)
    eng.flush()
    eng.discard(t2)
    with pytest.raises(KeyError):
        eng.result(t2)


def test_flush_failure_keeps_tickets_pending(monkeypatch):
    """The flush-ordering contract: a raising dispatch loses no tickets.

    Submissions whose bucket dispatch fails stay in the pending queue and
    remain redeemable once the fault clears (regression: flush() used to
    clear the queue *before* dispatching).
    """
    import repro.serve.truss_engine as te

    e1, e2 = _er_edges(12, 0.4, 33), _er_edges(12, 0.4, 34)
    eng = TrussEngine()
    t1, t2 = eng.submit(e1), eng.submit(e2)

    def boom(*a, **k):
        raise RuntimeError("injected dispatch failure")

    monkeypatch.setattr(te, "_batched_truss", boom)
    monkeypatch.setattr(te, "_batched_truss_dev", boom)
    with pytest.raises(RuntimeError, match="injected"):
        eng.flush()
    assert eng.bucket_of(t1) is not None      # still pending, not lost
    assert eng.bucket_of(t2) is not None
    monkeypatch.undo()
    assert np.array_equal(eng.result(t1), _expected(e1))
    assert np.array_equal(eng.result(t2), _expected(e2))


def test_promotion_observes_earlier_submits_in_flush():
    """A pending promotion and a same-bucket flush agree bitwise.

    Promoting ticket B must not disturb ticket A's pending result, and the
    promoted handle's trussness equals the batched flush of the same edges.
    """
    a, b = _er_edges(14, 0.4, 35), _er_edges(14, 0.4, 36)
    eng = TrussEngine()
    ta, tb = eng.submit(a), eng.submit(b)
    st = eng.update(tb)                       # promote B while A pending
    h = st.handle
    assert np.array_equal(eng.result(ta), _expected(a))   # flush after
    # the promotion's from-scratch decomposition matches the batched path
    sep = TrussEngine()
    assert np.array_equal(h.trussness, truss_pkt(h.edges))
    assert np.array_equal(sep.map([b])[0], _expected(b))


def test_engine_update_many_matches_sequential():
    """update_many(batches) is bitwise one-at-a-time, at one repair."""
    e = _er_edges(16, 0.35, 37)
    b1 = (np.array([[0, 9], [1, 10]], np.int64), None)
    b2 = (np.array([[2, 11]], np.int64), np.array([[0, 9]], np.int64))
    b3 = (None, np.array([[1, 10]], np.int64))

    eng = TrussEngine()
    h_seq = eng.open(e)
    for add, rem in (b1, b2, b3):
        eng.update(h_seq, add_edges=add, remove_edges=rem)
    h_one = eng.open(e)
    updates_before = eng.stats["updates"]
    st = eng.update_many(h_one, [b1, b2, b3])
    assert st.coalesced == 3
    assert st.handle is h_one
    assert eng.stats["updates"] == updates_before + 1
    assert np.array_equal(h_one.edges, h_seq.edges)
    assert np.array_equal(h_one.trussness, h_seq.trussness)
    assert np.array_equal(h_one.trussness, truss_pkt(h_one.edges))
