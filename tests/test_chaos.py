"""Chaos hardening: fault injection, retry/ladder, heal, deadlines, watchdog.

The fault-injection matrix drives every dispatch site (engine flush,
region re-peel, support build, hierarchy flood) through raise-once /
raise-twice / raise-until-exhausted / delay-past-deadline faults and
asserts the typed-error contract, the retry counters, ladder
demotion/re-promotion, and — throughout — bitwise parity of every
completed result with the fault-free reference.
"""

import time

import numpy as np
import pytest

from repro.core.pkt import truss_pkt
from repro.core.truss_inc import IntegrityError
from repro.graphs.csr import edges_from_arrays
from repro.serve import (Cancelled, DeadlineExceeded, Ladder, Overloaded,
                         RetryPolicy, TrussEngine, TrussScheduler, Wedged)
from repro.serve.resilience import run_with_resilience
from repro.testing.chaos import (DISPATCH_SITES, FaultPlan, InjectedFault,
                                 fault_point)


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


_FAST = RetryPolicy(max_retries=2, base_delay_s=0.001, max_delay_s=0.002)


# ------------------------------------------------------- fault-plan harness --


def test_fault_plan_times_rules_fire_exactly_n_times():
    plan = FaultPlan().add("flush", times=2)
    with plan:
        for _ in range(2):
            with pytest.raises(InjectedFault) as ei:
                fault_point("flush", rung="dense")
            assert ei.value.site == "flush" and ei.value.rung == "dense"
        assert fault_point("flush") is None         # rule exhausted
    st = plan.stats()
    assert st["calls"]["flush"] == 3 and st["injected"]["flush"] == 2


def test_fault_plan_rate_rules_are_seed_deterministic():
    def fire_pattern(seed):
        plan = FaultPlan.uniform(0.3, sites=("region",), seed=seed)
        hits = []
        with plan:
            for _ in range(50):
                try:
                    fault_point("region")
                    hits.append(0)
                except InjectedFault:
                    hits.append(1)
        return hits
    assert fire_pattern(7) == fire_pattern(7)
    assert fire_pattern(7) != fire_pattern(8)
    assert 0 < sum(fire_pattern(7)) < 50


def test_fault_plan_rung_filter_and_modes():
    plan = (FaultPlan()
            .add("flush", rung="dense", times=5)
            .add("support", mode="corrupt", times=1)
            .add("region", mode="delay", delay_s=0.05, times=1))
    with plan:
        assert fault_point("flush", rung="chunked") is None  # filtered out
        with pytest.raises(InjectedFault):
            fault_point("flush", rung="dense")
        assert fault_point("support") == "corrupt"
        t0 = time.perf_counter()
        assert fault_point("region") is None        # delay mode: sleeps
        assert time.perf_counter() - t0 >= 0.04


def test_fault_plan_validation_and_exclusive_activation():
    with pytest.raises(ValueError, match="dispatch site"):
        FaultPlan().add("nonsense")
    with pytest.raises(ValueError, match="fault mode"):
        FaultPlan().add("flush", mode="explode")
    with pytest.raises(ValueError, match="rate"):
        FaultPlan().add("flush", rate=1.5)
    with FaultPlan():
        with pytest.raises(RuntimeError, match="already active"):
            FaultPlan().__enter__()
    assert fault_point("flush") is None             # deactivated on exit


def test_fault_point_is_noop_without_a_plan():
    for site in DISPATCH_SITES:
        assert fault_point(site, rung="anything") is None


# --------------------------------------------------- resilience primitives --


def test_retry_policy_backoff_is_deterministic_and_bounded():
    pol = RetryPolicy(max_retries=3, base_delay_s=0.002, max_delay_s=0.01)
    a = [pol.backoff("flush", i) for i in (1, 2, 3)]
    assert a == [pol.backoff("flush", i) for i in (1, 2, 3)]
    assert a[0] >= 0.002 and max(a) <= 0.01
    assert pol.backoff("flush", 1) != pol.backoff("region", 1)  # decorrelated
    with pytest.raises(ValueError):
        RetryPolicy(max_retries=-1)


def test_ladder_demotes_probes_and_repromotes():
    lad = Ladder(("fast", "slow"), demote_after=2, probe_after=2,
                 promote_after=2)
    lad.record_failure()
    assert lad.current() == "fast"                  # one failure: no demote
    lad.record_failure()
    assert lad.current() == "slow" and lad.demotions == 1
    lad.record_success()
    assert not lad.should_probe()
    lad.record_success()
    assert lad.should_probe() and lad.probe_rung() == "fast"
    lad.record_probe_failure()                      # stays demoted
    assert lad.current() == "slow"
    lad.record_success(), lad.record_success()
    lad.record_probe_success()
    lad.record_probe_success()                      # full probe streak
    assert lad.current() == "fast" and lad.promotions == 1
    assert lad.snapshot()["probe_failures"] == 1


def test_run_with_resilience_retries_transient_only():
    lad = Ladder(("a", "b"))
    calls = []

    def flaky(rungs):
        calls.append(rungs["x"])
        if len(calls) < 3:
            raise RuntimeError("transient")
        return "ok"
    out = run_with_resilience(flaky, ladders={"x": lad}, primary="x",
                              policy=_FAST)
    assert out == "ok" and len(calls) == 3
    assert lad.failures == 2 and lad.demotions == 1     # demoted to "b"
    assert calls == ["a", "a", "b"]

    def buggy(rungs):
        raise ValueError("permanent")
    with pytest.raises(ValueError):                 # no retry for caller bugs
        run_with_resilience(buggy, ladders={"x": Ladder(("a",))},
                            primary="x", policy=_FAST)

    def slow(rungs):
        time.sleep(0.02)
        raise RuntimeError("transient")
    with pytest.raises(DeadlineExceeded):
        run_with_resilience(slow, ladders={"x": Ladder(("a",))}, primary="x",
                            policy=_FAST,
                            deadline=time.perf_counter() + 0.03, kind="q")


# ----------------------------------------- invariant checks + self-healing --


def test_check_invariants_detects_corruption_and_rebuild_heals():
    e = _er_edges(16, 0.4, 9)
    h = TrussEngine().open(e)
    inc = h._inc
    assert inc.check_invariants(sample=1 << 20) == inc.m    # full sweep clean
    assert inc.check_invariants(sample=8, seed=3) == 8      # sampled form
    t_good = inc.T.copy()
    inc.T[0] += 1
    with pytest.raises(IntegrityError, match="invariant violation"):
        inc.check_invariants(sample=1 << 20)
    inc.rebuild()
    assert np.array_equal(inc.T, t_good)                    # healed exactly
    inc.S[2] += 3
    with pytest.raises(IntegrityError, match="support disagrees"):
        inc.check_invariants(sample=1 << 20)
    inc.rebuild()
    assert inc.verify()


# ------------------------------------------------- fault-injection matrix --
# site × {raise-once, raise-twice}: retried to a bitwise-correct result,
# with retry counters and ladder demotions visible in stats().


@pytest.mark.parametrize("times", [1, 2])
def test_flush_faults_are_retried_to_parity(times):
    e = _er_edges(14, 0.4, 20)
    want = _expected(e)
    with FaultPlan().add("flush", times=times):
        with TrussScheduler(max_batch=4, max_delay_ms=1.0,
                            retry=_FAST) as sched:
            out = sched.submit_async(e).result(timeout=120)
            st = sched.stats()
    assert np.array_equal(out, want)
    assert st["counters"]["retries"] == times
    assert st["resilience"]["flush"]["failures"] == times
    assert st["resilience"]["flush"]["demotions"] == (1 if times >= 2 else 0)


@pytest.mark.parametrize("times", [1, 2])
def test_region_faults_are_retried_to_parity(times):
    e = _er_edges(16, 0.35, 21)
    add = np.array([[0, 9], [1, 10]], np.int64)
    full = np.concatenate([e, add])
    want = _expected(full)
    with TrussScheduler(max_batch=4, max_delay_ms=1.0, retry=_FAST) as sched:
        h = sched.open_async(e, local_frac=1.0).result(timeout=120)
        with FaultPlan().add("region", times=times):
            stats = sched.update_async(h, add_edges=add).result(timeout=120)
            st = sched.stats()
        q = sched.query_async(h, full).result(timeout=120)
    assert stats is not None
    assert np.array_equal(q, want)
    assert st["counters"]["retries"] == times
    assert st["resilience"]["region"]["failures"] == times


@pytest.mark.parametrize("times", [1, 2])
def test_support_faults_are_retried_to_parity(times):
    e = _er_edges(14, 0.4, 22)
    want = _expected(e)
    with TrussScheduler(max_batch=4, max_delay_ms=1.0, retry=_FAST) as sched:
        with FaultPlan().add("support", times=times):
            h = sched.open_async(e).result(timeout=120)
            st = sched.stats()
        # a demoted open must hand back a handle on the engine's executors
        assert h._inc.table_mode == sched.engine.table_mode
        q = sched.query_async(h, e).result(timeout=120)
    assert np.array_equal(q, want)
    assert st["counters"]["retries"] == times
    assert st["resilience"]["support"]["failures"] == times


@pytest.mark.parametrize("times", [1, 2])
def test_hierarchy_faults_are_retried_to_parity(times):
    e = _er_edges(16, 0.4, 23)
    eng = TrussEngine()
    href = eng.open(e)
    kmax = int(max(2, href.trussness.max()))
    want = href.communities(kmax)
    with TrussScheduler(max_batch=4, max_delay_ms=1.0, retry=_FAST) as sched:
        h = sched.open_async(e).result(timeout=120)
        with FaultPlan().add("hierarchy", times=times):
            got = sched.communities_async(h, kmax).result(timeout=120)
            st = sched.stats()
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert np.array_equal(g, w)
    assert st["counters"]["retries"] == times
    assert st["resilience"]["hierarchy"]["failures"] == times


def test_exhausted_retries_surface_the_typed_injected_fault():
    e = _er_edges(14, 0.4, 24)
    with FaultPlan().add("flush", times=50):
        with TrussScheduler(max_batch=4, max_delay_ms=1.0,
                            retry=_FAST) as sched:
            f = sched.submit_async(e)
            with pytest.raises(InjectedFault) as ei:
                f.result(timeout=120)
            st = sched.stats()
    assert ei.value.site == "flush"
    assert st["counters"]["errors"] == 1
    # every rung was tried on the way down
    assert st["resilience"]["flush"]["demotions"] >= 1


def test_delay_fault_past_deadline_is_a_typed_deadline_error():
    e = _er_edges(14, 0.4, 25)
    with FaultPlan().add("flush", mode="delay", delay_s=0.2, times=1):
        with TrussScheduler(max_batch=4, max_delay_ms=1.0,
                            retry=_FAST) as sched:
            f = sched.submit_async(e, deadline_ms=60.0)
            with pytest.raises(DeadlineExceeded) as ei:
                f.result(timeout=120)
            st = sched.stats()
    assert ei.value.kind == "submit"
    assert st["counters"]["deadline_exceeded"] == 1


# --------------------------------------------- ladder demotion/re-promotion --


def test_dense_failure_degrades_to_chunked_then_repromotes():
    """Acceptance: forced dense-peel failures demote to the chunked rung
    with identical outputs, then recovery probes re-promote to dense."""
    e = _er_edges(14, 0.4, 26)
    want = _expected(e)
    plan = FaultPlan().add("flush", rung="dense", times=2)
    with plan:
        with TrussScheduler(mode="dense", max_batch=1,
                            max_delay_ms=0.0, retry=_FAST,
                            ladder={"demote_after": 2, "probe_after": 1,
                                    "promote_after": 1}) as sched:
            outs = [sched.submit_async(e).result(timeout=120)
                    for _ in range(3)]
            st = sched.stats()
    for out in outs:                # demoted and dense results identical
        assert np.array_equal(out, want)
    flush = st["resilience"]["flush"]
    assert flush["rungs"] == ["dense", "chunked", "host"]
    assert flush["failures"] == 2           # two forced dense failures
    assert flush["demotions"] == 1          # -> chunked
    assert flush["probes"] == 1             # recovery probe on live traffic
    assert flush["promotions"] == 1         # back on dense
    assert flush["rung"] == "dense"
    assert plan.stats()["injected"]["flush"] == 2


# ----------------------------------------------------- handle self-healing --


def test_corrupt_injection_heals_via_quarantine_and_rebuild():
    e = _er_edges(16, 0.35, 27)
    add = np.array([[0, 9], [1, 10]], np.int64)
    full = np.concatenate([e, add])
    want = _expected(full)
    with TrussScheduler(max_batch=4, max_delay_ms=1.0, retry=_FAST) as sched:
        h = sched.open_async(e, local_frac=1.0).result(timeout=120)
        with FaultPlan().add("region", mode="corrupt", times=1):
            stats = sched.update_async(h, add_edges=add).result(timeout=120)
        q = sched.query_async(h, full).result(timeout=120)
        st = sched.stats()
    assert stats is not None                # the update future still resolved
    assert np.array_equal(q, want)
    assert st["counters"]["heals"] == 1
    assert st["counters"]["heal_failures"] == 0
    assert st["quarantined"] == []
    assert h._inc.verify()


def test_repeated_heal_failure_quarantines_then_next_request_recovers():
    e = _er_edges(16, 0.35, 28)
    a1 = np.array([[0, 9]], np.int64)
    a2 = np.array([[1, 10]], np.int64)
    with TrussScheduler(max_batch=4, max_delay_ms=1.0, retry=_FAST) as sched:
        h = sched.open_async(e, local_frac=1.0).result(timeout=120)
        with FaultPlan().add("region", mode="corrupt", times=50):
            f = sched.update_async(h, add_edges=a1)
            with pytest.raises(IntegrityError):
                f.result(timeout=120)       # heal kept failing: typed error
            st = sched.stats()
            assert st["counters"]["heal_failures"] >= 1
            assert st["quarantined"] == [h.hid]
        # faults gone: the next request triggers another rebuild and is
        # served — quarantined handles wait for recovery, not abandonment
        stats = sched.update_async(h, add_edges=a2).result(timeout=120)
        st = sched.stats()
    assert stats is not None
    assert st["quarantined"] == []
    assert st["counters"]["heals"] >= 2
    # a1 never committed (its future failed); state is e + a2 exactly
    full = np.concatenate([e, a2])
    assert np.array_equal(h.query(full), _expected(full))
    assert h._inc.verify()


# ------------------------------------------------------------- watchdog --


def test_watchdog_fails_outstanding_futures_with_wedged():
    e = _er_edges(12, 0.4, 29)
    with FaultPlan().add("flush", mode="delay", delay_s=1.5, times=1):
        sched = TrussScheduler(max_batch=1, max_delay_ms=0.0,
                               watchdog_s=0.2, retry=_FAST)
        f = sched.submit_async(e)
        with pytest.raises(Wedged, match="wedged"):
            f.result(timeout=30)
        with pytest.raises(Wedged):         # admission fails fast after trip
            sched.submit_async(e)
        st = sched.stats()
        sched.close()
    assert st["counters"]["watchdog_trips"] == 1
    assert st["wedged"] is not None and "stack" in st["wedged"]
    assert st["depth"] == 0


# ----------------------------------------------------- typed cancellation --


def test_close_never_started_drains_or_cancels_typed():
    e = _er_edges(12, 0.4, 30)
    sched = TrussScheduler(start=False, max_batch=4, max_delay_ms=1.0)
    f = sched.submit_async(e)
    sched.close(drain=True)                 # started just to drain
    assert np.array_equal(f.result(timeout=0), _expected(e))

    sched2 = TrussScheduler(start=False, max_batch=4, max_delay_ms=1.0)
    f2 = sched2.submit_async(e)
    sched2.close(drain=False)
    assert f2.done() and not f2.cancelled()
    with pytest.raises(Cancelled) as ei:
        f2.result(timeout=0)
    assert ei.value.kind == "submit" and ei.value.position == 0


def test_close_with_inflight_repair_leaves_no_future_unresolved():
    e = _er_edges(16, 0.35, 31)
    add = np.array([[0, 9], [1, 10]], np.int64)
    sched = TrussScheduler(max_batch=4, max_delay_ms=1.0, retry=_FAST)
    h = sched.open_async(e, local_frac=1.0).result(timeout=120)
    with FaultPlan().add("region", mode="delay", delay_s=0.4, times=1):
        fu = sched.update_async(h, add_edges=add)
        time.sleep(0.1)                     # the repair is now inflight
        fq = sched.query_async(h, e[:3])    # queued behind it
        sched.close(drain=False)
    assert fu.result(timeout=120) is not None   # inflight repair completed
    assert fq.done()
    with pytest.raises(Cancelled):
        fq.result(timeout=0)
    assert sched.engine._pending == []
    assert sched.stats()["depth"] == 0


# -------------------------------------------------- admission + deadlines --


def test_overloaded_carries_retry_after_hint():
    sched = TrussScheduler(start=False, max_batch=4, max_delay_ms=2.0,
                           max_queue=1)
    e = _er_edges(12, 0.4, 32)
    f = sched.submit_async(e)
    with pytest.raises(Overloaded) as ei:
        sched.submit_async(e)
    assert ei.value.retry_after_ms is not None
    assert ei.value.retry_after_ms >= 2.0   # floored at max_delay_ms
    assert "retry after" in str(ei.value)
    sched.close(drain=False)
    assert f.done()


def test_deadline_rejects_pre_dispatch_with_typed_error():
    e = _er_edges(12, 0.4, 33)
    sched = TrussScheduler(start=False, max_batch=4, max_delay_ms=1.0,
                           deadline_ms=5.0)
    h = sched.engine.open(e)
    m0 = h.m
    fs = sched.submit_async(e)              # scheduler-default deadline
    fu = sched.update_async(h, add_edges=np.array([[0, 9]], np.int64),
                            deadline_ms=5.0)
    fq = sched.query_async(h, e[:2], deadline_ms=60_000.0)
    time.sleep(0.05)                        # both 5ms budgets expire queued
    sched.start()
    for f, kind in ((fs, "submit"), (fu, "update")):
        with pytest.raises(DeadlineExceeded) as ei:
            f.result(timeout=120)
        assert ei.value.kind == kind
    assert h.m == m0                        # the expired update never ran
    assert np.array_equal(fq.result(timeout=120), _expected(e)[:2])
    st = sched.stats()
    sched.close()
    assert st["counters"]["deadline_exceeded"] == 2


def test_resilience_argument_validation():
    with pytest.raises(ValueError):
        TrussScheduler(deadline_ms=0.0, start=False)
    with pytest.raises(ValueError):
        TrussScheduler(watchdog_s=-1.0, start=False)
    with pytest.raises(ValueError):
        TrussScheduler(invariant_sample=-1, start=False)
    sched = TrussScheduler(start=False)
    with pytest.raises(ValueError):
        sched.submit_async(np.array([[0, 1]], np.int64), deadline_ms=-5.0)
    sched.close(drain=False)


def test_stats_expose_resilience_state_json_safely():
    import json

    with TrussScheduler(max_batch=2, max_delay_ms=1.0) as sched:
        sched.submit_async(_er_edges(12, 0.4, 34)).result(timeout=120)
        st = sched.stats()
    json.dumps(st)
    assert set(st["resilience"]) == set(DISPATCH_SITES)
    for site in DISPATCH_SITES:
        snap = st["resilience"][site]
        assert {"rung", "rungs", "failures", "demotions", "promotions",
                "probes", "probe_failures"} <= set(snap)
        assert snap["rung"] == snap["rungs"][0]     # healthy: top rung
    assert st["quarantined"] == [] and st["wedged"] is None
    for c in ("retries", "deadline_exceeded", "heals", "heal_failures",
              "watchdog_trips"):
        assert st["counters"][c] == 0
    assert "heal" in st["stages"]
