"""Async scheduler: parity, coalescing, dispatch policy, failure paths."""

import time

import numpy as np
import pytest

from repro.core.pkt import truss_pkt
from repro.graphs.csr import edges_from_arrays
from repro.serve.scheduler import Overloaded, TrussScheduler
from repro.serve.truss_engine import TrussEngine


def _er_edges(n, p, seed):
    rng = np.random.default_rng(seed)
    mask = rng.random((n, n)) < p
    src, dst = np.nonzero(np.triu(mask, 1))
    return edges_from_arrays(src, dst, n)


def _expected(edges):
    e = np.asarray(edges, np.int64)
    lo = np.minimum(e[:, 0], e[:, 1])
    hi = np.maximum(e[:, 0], e[:, 1])
    n = int(e.max()) + 1
    uniq = np.unique(lo * n + hi)
    E = np.stack([uniq // n, uniq % n], axis=1)
    t = truss_pkt(E)
    return t[np.searchsorted(uniq, lo * n + hi)]


# ------------------------------------------------------------------ parity --


def test_submit_async_parity_mixed_sizes():
    """Async trussness is bitwise-equal to the synchronous reference."""
    fleet = [_er_edges(12, 0.4, 0), _er_edges(30, 0.25, 1),
             _er_edges(12, 0.4, 2), np.array([[0, 1], [1, 2]], np.int64)]
    with TrussScheduler(max_batch=4, max_delay_ms=1.0) as sched:
        futs = [sched.submit_async(e) for e in fleet]
        for e, f in zip(fleet, futs):
            assert np.array_equal(f.result(timeout=120), _expected(e))


def test_open_query_communities_async():
    e = _er_edges(16, 0.4, 3)
    with TrussScheduler(max_batch=4, max_delay_ms=1.0) as sched:
        h = sched.open_async(e).result(timeout=120)
        q = sched.query_async(h, e[:5]).result(timeout=120)
        assert np.array_equal(q, _expected(e)[:5])
        kmax = int(max(2, q.max()))
        comms = sched.communities_async(h, kmax).result(timeout=120)
        direct = h.communities(kmax)
        assert len(comms) == len(direct)
        for got, want in zip(comms, direct):
            assert np.array_equal(got, want)


# -------------------------------------------------------- update coalescing --


def test_update_coalescing_same_handle():
    """Consecutive updates on one handle merge into one composed repair."""
    e = _er_edges(16, 0.35, 4)
    sched = TrussScheduler(start=False, max_batch=4, max_delay_ms=1.0)
    h = sched.engine.open(e)
    a1 = np.array([[0, 9], [1, 10]], np.int64)
    a2 = np.array([[2, 11]], np.int64)
    f1 = sched.update_async(h, add_edges=a1)
    f2 = sched.update_async(h, add_edges=a2)
    fq = sched.query_async(h, e[:4])
    sched.start()
    st1, st2 = f1.result(timeout=120), f2.result(timeout=120)
    q = fq.result(timeout=120)
    sched.close()
    assert st1 is st2
    assert st1.coalesced == 2
    # state equals applying both batches, and the query observed it
    full = np.concatenate([e, a1, a2])
    assert np.array_equal(h.query(e[:4]), _expected(full)[:4])
    assert np.array_equal(q, _expected(full)[:4])
    assert sched.stats()["counters"]["coalesced_updates"] == 1


def test_coalesced_insert_then_delete_not_resurrected():
    """An edge inserted in one queued batch and deleted in a later one must
    not survive the composed repair — and must not be resurrected through
    the batched insertion region seed (§13)."""
    e = _er_edges(16, 0.35, 21)
    sched = TrussScheduler(start=False, max_batch=4, max_delay_ms=1.0)
    h = sched.engine.open(e)
    ghost = np.array([[0, 17]], np.int64)     # vertex 17 > n: surely absent
    k1 = np.array([[1, 18]], np.int64)
    k2 = np.array([[2, 19]], np.int64)
    f1 = sched.update_async(h, add_edges=np.concatenate([ghost, k1]))
    f2 = sched.update_async(h, add_edges=k2, remove_edges=ghost)
    sched.start()
    st1, st2 = f1.result(timeout=120), f2.result(timeout=120)
    sched.close()
    assert st1 is st2 and st1.coalesced == 2
    # the scheduler's composed output lands on the batched insertion path
    assert st1.insert_mode == "batched"
    cur = {(int(u), int(v)) for u, v in h.edges}
    assert (0, 17) not in cur                 # not resurrected
    assert {(1, 18), (2, 19)} <= cur
    assert np.array_equal(h.trussness, truss_pkt(h.edges))


def test_query_is_barrier_between_updates():
    """A query splits the update run: it observes exactly its FIFO prefix."""
    e = _er_edges(16, 0.35, 5)
    sched = TrussScheduler(start=False, max_batch=4, max_delay_ms=1.0)
    h = sched.engine.open(e)
    a1 = np.array([[0, 9]], np.int64)
    a2 = np.array([[1, 10]], np.int64)
    f1 = sched.update_async(h, add_edges=a1)
    fq = sched.query_async(h, e[:4])
    f2 = sched.update_async(h, add_edges=a2)
    sched.start()
    st1, st2 = f1.result(timeout=120), f2.result(timeout=120)
    q = fq.result(timeout=120)
    sched.close()
    assert st1 is not st2
    assert st1.coalesced == 1 and st2.coalesced == 1
    # the barrier query saw a1 but not a2
    assert np.array_equal(q, _expected(np.concatenate([e, a1]))[:4])
    assert np.array_equal(h.query(e[:4]),
                          _expected(np.concatenate([e, a1, a2]))[:4])


# --------------------------------------------------------- dispatch policy --


def test_full_bucket_dispatches_before_deadline():
    """max_batch requests of one size class release without the delay."""
    with TrussScheduler(max_batch=2, max_delay_ms=60_000.0) as sched:
        e1, e2 = _er_edges(14, 0.4, 6), _er_edges(14, 0.4, 7)
        f1, f2 = sched.submit_async(e1), sched.submit_async(e2)
        assert np.array_equal(f1.result(timeout=120), _expected(e1))
        assert np.array_equal(f2.result(timeout=120), _expected(e2))
        assert sched.stats()["counters"]["dispatches"] >= 1


def test_deadline_dispatches_partial_bucket():
    """A non-full bucket still dispatches once its oldest hits max_delay."""
    with TrussScheduler(max_batch=64, max_delay_ms=30.0) as sched:
        fleet = [_er_edges(14, 0.4, s) for s in (8, 9, 10)]
        futs = [sched.submit_async(e) for e in fleet]
        for e, f in zip(fleet, futs):
            assert np.array_equal(f.result(timeout=120), _expected(e))
        st = sched.stats()
        assert st["counters"]["dispatches"] >= 1
        assert st["buckets_waiting"] == {}


# ------------------------------------------------------- admission control --


def test_queue_depth_shedding():
    """Admissions beyond max_queue shed with Overloaded, typed and counted."""
    sched = TrussScheduler(start=False, max_batch=4, max_delay_ms=1.0,
                           max_queue=2)
    e = _er_edges(12, 0.4, 11)
    f1, f2 = sched.submit_async(e), sched.submit_async(e)
    with pytest.raises(Overloaded, match="queue depth"):
        sched.submit_async(e)
    assert sched.stats()["counters"]["shed"] == 1
    sched.start()
    assert np.array_equal(f1.result(timeout=120), _expected(e))
    assert np.array_equal(f2.result(timeout=120), _expected(e))
    # capacity freed: the retry admits
    f3 = sched.submit_async(e)
    assert np.array_equal(f3.result(timeout=120), _expected(e))
    sched.close()


def test_per_tenant_inflight_shedding():
    """One tenant at max_inflight sheds; other tenants still admit."""
    sched = TrussScheduler(start=False, max_batch=4, max_delay_ms=1.0,
                           max_inflight=1)
    e = _er_edges(12, 0.4, 12)
    f1 = sched.submit_async(e, tenant="a")
    with pytest.raises(Overloaded, match="tenant 'a'"):
        sched.submit_async(e, tenant="a")
    f2 = sched.submit_async(e, tenant="b")
    sched.start()
    assert np.array_equal(f1.result(timeout=120), _expected(e))
    assert np.array_equal(f2.result(timeout=120), _expected(e))
    sched.close()
    assert sched.stats()["inflight"] == {}


# ------------------------------------------------------------ error typing --


def test_handle_type_and_closed_errors():
    """Non-handle targets TypeError; closed handles ValueError, synchronously."""
    sched = TrussScheduler(start=False, max_batch=4, max_delay_ms=1.0)
    e = _er_edges(12, 0.4, 13)
    h = sched.engine.open(e)
    with pytest.raises(TypeError, match="TrussHandle"):
        sched.query_async(7, e[:2])     # a ticket int is not a handle
    sched.engine.close(h)
    with pytest.raises(ValueError, match="closed"):
        sched.update_async(h, add_edges=np.array([[0, 9]], np.int64))
    with pytest.raises(ValueError, match="closed"):
        sched.communities_async(h, 3)
    sched.start()
    sched.close()


def test_engine_validation_error_lands_on_future():
    """Bad payloads admit, then the engine's ValueError rides the future."""
    with TrussScheduler(max_batch=4, max_delay_ms=1.0) as sched:
        f = sched.submit_async(np.array([[-1, 2]], np.int64))
        with pytest.raises(ValueError):
            f.result(timeout=120)
        assert sched.stats()["counters"]["errors"] == 1


def test_closed_scheduler_rejects_and_close_is_idempotent():
    sched = TrussScheduler(max_batch=4, max_delay_ms=1.0)
    sched.close()
    sched.close()       # idempotent
    with pytest.raises(RuntimeError, match="closed"):
        sched.submit_async(np.array([[0, 1]], np.int64))


def test_close_without_drain_cancels_queued():
    """close(drain=False) rejects waiting work with typed Cancelled."""
    from repro.serve import Cancelled

    sched = TrussScheduler(max_batch=64, max_delay_ms=60_000.0)
    e = _er_edges(14, 0.4, 14)
    f1, f2 = sched.submit_async(e), sched.submit_async(e)
    # let the loop route them into a bucket that can never fill
    deadline = time.perf_counter() + 30
    while (sched.stats()["buckets_waiting"] == {}
           and time.perf_counter() < deadline):
        time.sleep(0.005)
    sched.close(drain=False)
    for f in (f1, f2):
        assert f.done() and not f.cancelled()   # resolved, typed
        with pytest.raises(Cancelled):
            f.result(timeout=0)
    # the error carries kind and queue position for caller-side retry logic
    exc = f1.exception(timeout=0)
    assert exc.kind == "submit" and isinstance(exc.position, int)
    st = sched.stats()
    assert st["counters"]["cancelled"] == 2
    assert st["depth"] == 0
    assert sched.engine._pending == []      # tickets discarded, not leaked


def test_bad_constructor_args():
    with pytest.raises(ValueError):
        TrussScheduler(max_batch=0)
    with pytest.raises(ValueError):
        TrussScheduler(max_delay_ms=-1.0)
    with pytest.raises(ValueError):
        TrussScheduler(max_queue=0)
    with pytest.raises(ValueError):
        TrussScheduler(max_inflight=0)
    with pytest.raises(ValueError):
        TrussScheduler(TrussEngine(), mode="device")   # engine + kwargs


def test_stats_shape():
    """stats() is JSON-safe and carries every stage and counter."""
    import json

    with TrussScheduler(max_batch=2, max_delay_ms=1.0) as sched:
        e = _er_edges(12, 0.4, 15)
        sched.submit_async(e).result(timeout=120)
        st = sched.stats()
    json.dumps(st)      # must not raise
    for stage in ("queue_wait", "build", "dispatch", "readback",
                  "open", "repair", "query"):
        assert {"count", "seconds", "max_seconds"} <= set(st["stages"][stage])
    assert st["counters"]["submit"] == 1
    assert st["counters"]["done"] == 1
    assert "engine" in st


# ------------------------------------------------------ degradation ladders --

#: each site's rungs, fastest first, by the engine option that shapes them
_FLUSH_RUNGS = {"chunked": ["chunked", "host"],
                "dense": ["dense", "chunked", "host"]}
_REGION_RUNGS = {"chunked": ["default", "host"],
                 "dense": ["default", "chunked", "host"]}
_SUPPORT_RUNGS = {"device": ["default", "numpy"], "numpy": ["default"]}
_HIER_RUNGS = {"device": ["default", "host"], "host": ["default"]}


@pytest.mark.parametrize("hier_mode", ["device", "host"])
@pytest.mark.parametrize("table_mode", ["device", "numpy"])
@pytest.mark.parametrize("mode", ["chunked", "dense"])
def test_ladder_rungs_follow_engine_config(mode, table_mode, hier_mode):
    """Every site's ladder holds the configured executor, then each slower
    executor that gives the same result, and nothing else."""
    sched = TrussScheduler(start=False, mode=mode, table_mode=table_mode,
                           hier_mode=hier_mode)
    ladders = sched.stats()["resilience"]
    sched.close()
    assert {site: lad["rungs"] for site, lad in ladders.items()} == {
        "flush": _FLUSH_RUNGS[mode], "region": _REGION_RUNGS[mode],
        "support": _SUPPORT_RUNGS[table_mode],
        "hierarchy": _HIER_RUNGS[hier_mode]}
    assert all(lad["rung"] == lad["rungs"][0] for lad in ladders.values())
