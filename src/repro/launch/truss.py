"""Truss decomposition driver — the paper's pipeline end-to-end.

  PYTHONPATH=src python -m repro.launch.truss --graph rmat-small \
      [--order kco|natural] [--engine pkt|dist|trilist|wc|ros] [--verify]

Streaming replay (incremental maintenance, DESIGN.md §9): open the graph as
a persistent engine handle and replay K churn batches through
``TrussEngine.update``, reporting local-vs-full repair decisions and
timings; with ``--verify`` the final state is checked against a
from-scratch PKT:

  PYTHONPATH=src python -m repro.launch.truss --graph rmat-small \
      --update-stream 16 --churn 0.01 \
      [--insert-mode batched|sequential] [--verify]

Community serving (DESIGN.md §11): open the graph as a handle, build the
triangle-connected k-truss community index, and answer queries at level k —
with ``--verify`` the device label-propagation labels are checked bitwise
against the host union-find oracle on every level.  Composes with
``--update-stream`` (the index is queried on the post-churn graph, having
survived the updates through remap/dirty-level invalidation):

  PYTHONPATH=src python -m repro.launch.truss --graph rmat-small \
      --query-communities 4 [--hier-mode device|host] [--verify]

Async serving (DESIGN.md §12): replay paced mixed 90/9/1 query/update/open
traffic through the continuous-batching ``TrussScheduler``, printing
per-kind latency percentiles and the scheduler's per-stage timing; with
``--verify`` every async result is checked bitwise against a synchronous
engine replay of the same schedule:

  PYTHONPATH=src python -m repro.launch.truss --graph rmat-small \
      --serve 200 --qps 200 [--max-batch 16] [--max-delay-ms 2] [--verify]

Chaos serving (DESIGN.md §15): same replay with deterministic faults
injected at every dispatch site at ``--fault-rate`` and optional
per-request ``--deadline-ms`` budgets; failures surface as typed errors,
the availability and resilience counters (retries, ladder demotions,
heals) are reported, and ``--verify`` masks failed requests before the
synchronous parity replay:

  PYTHONPATH=src python -m repro.launch.truss --graph rmat-small \
      --serve 200 --qps 200 --fault-rate 0.1 [--deadline-ms 250] [--verify]
"""

from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.graphs.datasets import named_graph
from repro.graphs.csr import build_csr, relabel, degeneracy_order
from repro.core import (pkt, truss_wc, truss_ros, truss_trilist, truss_numpy,
                        pkt_dist)

# ------------------------------------------------------- host env tuning ----

#: re-exec guard: set once tuning has been applied so ``--tune-env`` cannot
#: loop the process
_ENV_TUNED_MARK = "_TRUSS_ENV_TUNED"

#: where distro packages put tcmalloc (the SNIPPETS.md serving exemplar);
#: first hit wins, absence just skips the preload
TCMALLOC_PATHS = (
    "/usr/lib/x86_64-linux-gnu/libtcmalloc.so.4",
    "/usr/lib/x86_64-linux-gnu/libtcmalloc_minimal.so.4",
    "/usr/lib/libtcmalloc.so.4",
)


def tuned_env(environ=None) -> dict[str, str]:
    """Host-side env additions for serving (docs/PERFORMANCE.md):

    * ``LD_PRELOAD`` tcmalloc — glibc malloc serializes the multi-GiB host
      buffer churn of table builds; tcmalloc's thread caches don't.
    * ``TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD`` raised so steady-state
      large allocations don't spam stderr.
    * ``TF_CPP_MIN_LOG_LEVEL=4`` — silence the XLA C++ banner on every
      worker.
    * ``JAX_DEFAULT_DTYPE_BITS=32`` — the whole pipeline is int32/float32;
      keep accidental int64 promotion off the device.

    Returns only the *additions* (never overrides anything the user set),
    so it is unit-testable and composes with existing environments.
    """
    env = os.environ if environ is None else environ
    add: dict[str, str] = {}
    if "TF_CPP_MIN_LOG_LEVEL" not in env:
        add["TF_CPP_MIN_LOG_LEVEL"] = "4"
    if "JAX_DEFAULT_DTYPE_BITS" not in env:
        add["JAX_DEFAULT_DTYPE_BITS"] = "32"
    if "TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD" not in env:
        add["TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD"] = "60000000000"
    if "libtcmalloc" not in env.get("LD_PRELOAD", ""):
        for p in TCMALLOC_PATHS:
            if os.path.exists(p):
                pre = env.get("LD_PRELOAD", "")
                add["LD_PRELOAD"] = f"{pre}:{p}".strip(":")
                break
    return add


def apply_env_tuning(*, reexec: bool = True) -> dict[str, str]:
    """Apply ``tuned_env`` to this process (idempotent via the guard var).

    ``LD_PRELOAD`` only binds at process start, so when the preload is part
    of the additions and ``reexec`` is allowed the process re-execs itself
    once with the tuned environment; everything else takes effect in place.
    Returns the additions that were applied.
    """
    if os.environ.get(_ENV_TUNED_MARK):
        return {}
    add = tuned_env()
    os.environ[_ENV_TUNED_MARK] = "1"
    os.environ.update(add)
    if reexec and "LD_PRELOAD" in add:
        os.execv(sys.executable, [sys.executable] + sys.argv)
    return add


def churn_batch(edges: np.ndarray, n: int, frac: float, rng):
    """One synthetic update batch: remove ``frac·m`` existing edges and add
    the same number of random absent edges (vertex space preserved)."""
    m = edges.shape[0]
    k = max(1, int(round(frac * m)))
    rm = edges[rng.choice(m, size=min(k, m), replace=False)]
    present = set(map(tuple, edges.tolist()))
    add = []
    tries = 0
    while len(add) < k and tries < 100 * k + 1000:  # dense graphs: give up
        tries += 1
        u, v = int(rng.integers(0, n)), int(rng.integers(0, n))
        if u == v:
            continue
        e = (min(u, v), max(u, v))
        if e not in present:
            present.add(e)
            add.append(e)
    if not add:
        return np.zeros((0, 2), np.int64), rm
    return np.asarray(add, np.int64), rm


def report_communities(handle, k: int, *, verify: bool = False) -> None:
    """Build the community index on ``handle`` and report level-``k`` stats.

    Prints index-build cost (one vmapped dispatch in device mode), the
    level-k community size spectrum, and a sampled per-query latency; with
    ``verify`` every level's labels are checked bitwise against the host
    union-find oracle.
    """
    t0 = time.perf_counter()
    hier = handle.hierarchy().build_all()
    t_build = time.perf_counter() - t0
    comms = handle.communities(k)
    sizes = sorted((c.shape[0] for c in comms), reverse=True)
    E = handle.edges                    # hoisted: El copies stay untimed
    t0 = time.perf_counter()
    n_q = 0
    for eid in range(0, handle.m, max(1, handle.m // 64)):
        handle.community(tuple(E[eid]), k)
        n_q += 1
    t_query = (time.perf_counter() - t0) / max(1, n_q)
    print(f"community index: k_max={hier.k_max} "
          f"levels={len(list(hier.levels))} build {t_build * 1e3:.1f}ms "
          f"({hier.stats})")
    print(f"k={k}: {len(comms)} communities, edge sizes top5={sizes[:5]}, "
          f"query {t_query * 1e6:.0f}us/edge")
    if verify:
        other = "host" if hier.mode == "device" else "device"
        oracle = handle.hierarchy(mode=other).build_all()
        ok = all(np.array_equal(hier.level_labels(kk), oracle.level_labels(kk))
                 for kk in hier.levels)
        print(f"verify {hier.mode} labels vs {other} builder:",
              "OK" if ok else "MISMATCH")
        if not ok:
            raise SystemExit(1)


def run_update_stream(args) -> None:
    """Replay ``--update-stream`` churn batches through an engine handle."""
    from repro.serve.truss_engine import TrussEngine

    E = named_graph(args.graph)
    n = int(E.max()) + 1
    eng = TrussEngine(mode=args.mode, table_mode=args.table_mode,
                      hier_mode=args.hier_mode, insert_mode=args.insert_mode,
                      chunk=args.chunk)
    t0 = time.perf_counter()
    h = eng.open(E, local_frac=args.local_frac)
    t_open = time.perf_counter() - t0
    print(f"graph={args.graph} n={n} m={h.m} open {t_open:.3f}s "
          f"mode={args.mode} insert={args.insert_mode}")
    if args.query_communities:
        # build the index up front so the stream exercises its survival
        # (local repairs remap untouched levels, dirty the rest)
        h.hierarchy().build_all()

    rng = np.random.default_rng(args.update_seed)
    for i in range(args.update_stream):
        add, rm = churn_batch(h.edges, n, args.churn, rng)
        st = eng.update(h, add_edges=add, remove_edges=rm)
        print(f"batch {i:3d}: +{st.inserted} -{st.deleted} -> m={st.m_after} "
              f"repair={st.mode} affected={st.affected} "
              f"boundary={st.boundary} changed={st.changed} "
              f"{st.seconds * 1e3:.1f}ms")

    s = eng.stats
    mean_ms = 1e3 * s["update_seconds"] / max(1, s["updates"])
    print(f"stream done: {s['updates']} updates "
          f"({s['updates_local']} local / {s['updates_full']} full), "
          f"mean {mean_ms:.1f}ms vs open {t_open * 1e3:.1f}ms")

    if args.query_communities:
        report_communities(h, args.query_communities, verify=args.verify)

    if args.verify:
        from repro.core import truss_pkt
        ok = np.array_equal(h.trussness, truss_pkt(h.edges))
        print("verify vs from-scratch pkt:", "OK" if ok else "MISMATCH")
        if not ok:
            raise SystemExit(1)


def serve_schedule(E: np.ndarray, n: int, requests: int, seed: int) -> list:
    """A deterministic 90/9/1 query/update/open request schedule.

    Queries read 8 base rows; updates toggle edges of a reserved pool of
    32 absent edges, disjoint from the base rows the queries sample, so
    both an async and a sync replay of the schedule stay valid; opens carry
    small fresh Erdős–Rényi graphs.  Generation tracks pool presence so
    removals always hit present edges.
    """
    from repro.graphs.gen import erdos_renyi_edges

    rng = np.random.default_rng(seed)
    present = {(int(u), int(v)) for u, v in E}
    pool = []
    while len(pool) < 32:
        u, v = int(rng.integers(0, n)), int(rng.integers(0, n))
        if u != v and (min(u, v), max(u, v)) not in present:
            pool.append((min(u, v), max(u, v)))
            present.add(pool[-1])
    ops, in_pool, n_open = [], set(), 0
    for _ in range(requests):
        r = rng.random()
        if r < 0.90:
            ops.append(("query", E[rng.integers(0, E.shape[0], size=8)]))
        elif r < 0.99:
            picks = [pool[j] for j in rng.choice(len(pool), size=4,
                                                 replace=False)]
            add = [e for e in picks if e not in in_pool]
            rem = [e for e in picks if e in in_pool]
            in_pool |= set(add)
            in_pool -= set(rem)
            ops.append(("update", np.array(add or np.zeros((0, 2)), np.int64),
                        np.array(rem or np.zeros((0, 2)), np.int64)))
        else:
            ops.append(("open", erdos_renyi_edges(
                64, 8.0, seed=seed + 5000 + n_open)))
            n_open += 1
    return ops


def replay_schedule(sched, handle, ops: list, qps: float):
    """Replay ``ops`` through ``sched`` at ``qps`` against ``handle``.

    Returns ``(outcomes, latencies, seconds)``: per op ``("ok", result)``
    or ``("failed", exception)`` in schedule order, ``(kind, seconds)``
    enqueue-to-completion latencies, and the replay's wall time.
    """
    lat, futs = [], []
    t_start = time.perf_counter()
    for i, op in enumerate(ops):
        target = t_start + i / qps
        if target > time.perf_counter():
            time.sleep(target - time.perf_counter())
        t_enq = time.perf_counter()
        if op[0] == "query":
            f = sched.query_async(handle, op[1])
        elif op[0] == "update":
            f = sched.update_async(handle, add_edges=op[1], remove_edges=op[2])
        else:
            f = sched.open_async(op[1])
        f.add_done_callback(lambda f, k=op[0], t=t_enq:
                            lat.append((k, time.perf_counter() - t)))
        futs.append(f)
    outcomes = []
    for f in futs:
        try:
            outcomes.append(("ok", f.result()))
        except Exception as e:  # noqa: BLE001 — typed, classified by callers
            outcomes.append(("failed", e))
    return outcomes, lat, time.perf_counter() - t_start


def latency_percentiles(lat: list) -> dict:
    """Per request kind: ``{"n", "p50_ms", "p99_ms", "max_ms"}``."""
    out = {}
    for kind in ("query", "update", "open"):
        ms = sorted(1e3 * s for k, s in lat if k == kind)
        if ms:
            out[kind] = {"n": len(ms), "p50_ms": ms[len(ms) // 2],
                         "p99_ms": ms[min(len(ms) - 1, int(0.99 * len(ms)))],
                         "max_ms": ms[-1]}
    return out


def verify_replay(E: np.ndarray, ops: list, outcomes: list, handle, *,
                  local_frac: float = 0.25, **engine_kwargs):
    """Replay ``ops`` synchronously through a fresh ``TrussEngine``.

    Every completed async result is compared bitwise with the synchronous
    one, and the final handle trussness with the sync handle's; failed ops
    are masked (their updates never committed — commit is batch-scoped).
    Returns ``(ok, sync_handle)``.
    """
    from repro.serve.truss_engine import TrussEngine

    eng = TrussEngine(**engine_kwargs)
    hs = eng.open(E, local_frac=local_frac)
    ok = True
    for op, (status, got) in zip(ops, outcomes):
        if status != "ok":
            continue
        if op[0] == "query":
            ok = ok and np.array_equal(got, hs.query(op[1]))
        elif op[0] == "update":
            eng.update(hs, add_edges=op[1], remove_edges=op[2])
        else:
            ok = ok and np.array_equal(got.trussness,
                                       eng.open(op[1]).trussness)
    ok = ok and np.array_equal(handle.trussness, hs.trussness)
    return bool(ok), hs


def run_serve(args) -> None:
    """Replay paced mixed traffic through the async scheduler (``--serve``).

    Opens the named graph as a persistent handle, then replays ``--serve``
    requests at ``--qps`` in the 90/9/1 query/update/open serving mix
    (DESIGN.md §12, ``serve_schedule``).  Prints per-kind latency and the
    scheduler's stage breakdown; ``--verify`` replays the same schedule
    through a synchronous engine and checks every result bitwise.

    With ``--fault-rate`` a seeded ``FaultPlan`` injects dispatch faults
    during the replay (DESIGN.md §15): completed requests stay bitwise
    parity-checked, failed ones are masked from the sync replay.
    """
    import contextlib

    from repro.serve.scheduler import TrussScheduler

    E = named_graph(args.graph)
    n = int(E.max()) + 1
    engine_kwargs = dict(mode=args.mode, table_mode=args.table_mode,
                         hier_mode=args.hier_mode, chunk=args.chunk)
    # a replay measures latency, not shedding: admit the whole schedule
    sched = TrussScheduler(
        max_batch=args.max_batch, max_delay_ms=args.max_delay_ms,
        max_queue=max(256, 4 * args.serve),
        max_inflight=max(64, 4 * args.serve),
        deadline_ms=args.deadline_ms, insert_mode=args.insert_mode,
        **engine_kwargs)
    t0 = time.perf_counter()
    h = sched.open_async(E, local_frac=args.local_frac).result()
    print(f"graph={args.graph} n={n} m={h.m} open "
          f"{time.perf_counter() - t0:.3f}s qps={args.qps} "
          f"mix=90/9/1 query/update/open fault_rate={args.fault_rate}")

    plan = None
    if args.fault_rate > 0.0:
        from repro.testing.chaos import FaultPlan
        plan = FaultPlan.uniform(args.fault_rate, seed=args.update_seed)

    ops = serve_schedule(E, n, args.serve, args.update_seed)
    with plan if plan is not None else contextlib.nullcontext():
        outcomes, lat, duration = replay_schedule(sched, h, ops, args.qps)
    st = sched.stats()
    sched.close()

    for kind, p in latency_percentiles(lat).items():
        print(f"{kind:6s} n={p['n']:4d} p50={p['p50_ms']:.2f}ms "
              f"p99={p['p99_ms']:.2f}ms max={p['max_ms']:.2f}ms")
    print(f"achieved {len(ops) / duration:.0f} qps "
          f"(offered {args.qps:.0f}); dispatches="
          f"{st['counters']['dispatches']} "
          f"coalesced_updates={st['counters']['coalesced_updates']} "
          f"shed={st['counters']['shed']}")
    for stage, s in st["stages"].items():
        if s["count"]:
            print(f"  stage {stage:10s} n={s['count']:4d} "
                  f"total={s['seconds'] * 1e3:.1f}ms "
                  f"max={s['max_seconds'] * 1e3:.1f}ms")

    n_ok = sum(1 for s, _ in outcomes if s == "ok")
    if plan is not None or args.deadline_ms:
        from repro.serve import DeadlineExceeded
        from repro.testing.chaos import InjectedFault
        fails = [e for s, e in outcomes if s == "failed"]
        n_inj = sum(isinstance(e, InjectedFault) for e in fails)
        n_dead = sum(isinstance(e, DeadlineExceeded) for e in fails)
        inj = dict(plan.stats()["injected"]) if plan is not None else {}
        print(f"chaos: availability {n_ok}/{len(ops)} "
              f"({n_ok / max(1, len(ops)):.3f}) injected={inj} "
              f"failed: injected={n_inj} deadline={n_dead} "
              f"other={len(fails) - n_inj - n_dead}")
        print(f"  retries={st['counters']['retries']} "
              f"heals={st['counters']['heals']} "
              f"deadline_exceeded={st['counters']['deadline_exceeded']} "
              f"rungs=" +
              ", ".join(f"{site}:{r['rung']}"
                        for site, r in st["resilience"].items()))

    if args.verify:
        ok, _ = verify_replay(E, ops, outcomes, h, local_frac=args.local_frac,
                              **engine_kwargs)
        print("verify async vs sync engine (failed ops masked):",
              "OK" if ok else "MISMATCH")
        if not ok:
            raise SystemExit(1)


def run_query_communities(args) -> None:
    """Open the graph as a serving handle and answer community queries."""
    from repro.serve.truss_engine import TrussEngine

    E = named_graph(args.graph)
    eng = TrussEngine(mode=args.mode, table_mode=args.table_mode,
                      hier_mode=args.hier_mode, chunk=args.chunk)
    t0 = time.perf_counter()
    h = eng.open(E)
    t_open = time.perf_counter() - t0
    print(f"graph={args.graph} n={h.n} m={h.m} open {t_open:.3f}s "
          f"hier_mode={args.hier_mode}")
    report_communities(h, args.query_communities, verify=args.verify)


def main(argv=None):
    # env tuning must act before any JAX backend initialises (importing
    # this module initialises none); re-exec only on a real CLI invocation
    # (tests pass argv explicitly and must not exec away)
    raw = sys.argv[1:] if argv is None else argv
    if "--tune-env" in raw:
        apply_env_tuning(reexec=argv is None)
    ap = argparse.ArgumentParser()
    ap.add_argument("--tune-env", action="store_true",
                    help="apply host env tuning (tcmalloc preload, XLA/TF "
                         "log + dtype defaults) before running; re-execs "
                         "once when the preload changes")
    ap.add_argument("--graph", default="rmat-small")
    ap.add_argument("--order", default="kco", choices=["kco", "natural"])
    ap.add_argument("--engine", default="pkt",
                    choices=["pkt", "dist", "trilist", "wc", "ros"])
    ap.add_argument("--chunk", type=int, default=None,
                    help="peel chunk size (default: derived from the table "
                         "size, see kernels.wedge_common.auto_chunk)")
    from repro.core.pkt import PEEL_MODES
    from repro.core.support import TABLE_MODES
    ap.add_argument("--mode", default="chunked", choices=list(PEEL_MODES))
    ap.add_argument("--table-mode", default="device",
                    choices=list(TABLE_MODES),
                    help="where wedge tables are built: jitted XLA on "
                         "device (default) or host numpy (parity oracle)")
    ap.add_argument("--compact-frac", type=float, default=0.25,
                    help="live-edge compaction threshold for the peel loop "
                         "(0 disables; see DESIGN.md §10)")
    from repro.core.hierarchy import HIER_MODES
    ap.add_argument("--query-communities", type=int, default=0, metavar="K",
                    help="build the truss community index and report the "
                         "K-truss communities (DESIGN.md §11); composes "
                         "with --update-stream")
    ap.add_argument("--hier-mode", default="device",
                    choices=list(HIER_MODES),
                    help="community-index builder: device label propagation "
                         "(default) or the host union-find parity oracle")
    ap.add_argument("--verify", action="store_true",
                    help="check against the numpy oracle (small graphs!)")
    ap.add_argument("--update-stream", type=int, default=0, metavar="K",
                    help="replay K incremental churn batches through "
                         "TrussEngine.update instead of one decomposition")
    from repro.core.truss_inc import INSERT_MODES
    ap.add_argument("--insert-mode", default="batched",
                    choices=list(INSERT_MODES),
                    help="insertion repair strategy for handle updates: one "
                         "merged-region re-peel per batch (default) or the "
                         "one-at-a-time parity oracle (DESIGN.md §13)")
    ap.add_argument("--churn", type=float, default=0.01,
                    help="fraction of edges swapped per update batch")
    ap.add_argument("--local-frac", type=float, default=0.25,
                    help="affected-region fraction above which an update "
                         "falls back to full recompute")
    ap.add_argument("--update-seed", type=int, default=0)
    ap.add_argument("--serve", type=int, default=0, metavar="N",
                    help="replay N mixed 90/9/1 query/update/open requests "
                         "through the async TrussScheduler (DESIGN.md §12)")
    ap.add_argument("--qps", type=float, default=200.0,
                    help="offered request rate for --serve")
    ap.add_argument("--max-batch", type=int, default=16,
                    help="scheduler bucket size before dispatch (--serve)")
    ap.add_argument("--max-delay-ms", type=float, default=2.0,
                    help="scheduler latency bound: a non-full bucket "
                         "dispatches once its oldest request waits this "
                         "long (--serve)")
    ap.add_argument("--fault-rate", type=float, default=0.0,
                    help="inject seeded dispatch faults at this rate during "
                         "--serve (DESIGN.md §15); completed requests stay "
                         "parity-checked under --verify")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request deadline for --serve; expired "
                         "requests fail with a typed DeadlineExceeded")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.serve:
        return run_serve(args)
    if args.update_stream:
        return run_update_stream(args)
    if args.query_communities:
        return run_query_communities(args)

    E = named_graph(args.graph)
    n = int(E.max()) + 1
    t0 = time.perf_counter()
    if args.order == "kco":
        E = relabel(E, degeneracy_order(E, n))
    g = build_csr(E, n)
    t_build = time.perf_counter() - t0
    print(f"graph={args.graph} n={g.n} m={g.m} wedges={g.wedge_count():.3e} "
          f"build {t_build:.2f}s order={args.order}")

    t0 = time.perf_counter()
    if args.engine == "pkt":
        res = pkt(g, chunk=args.chunk, mode=args.mode,
                  table_mode=args.table_mode,
                  compact_frac=args.compact_frac or None)
        truss = res.trussness
        extra = (f"levels={res.levels} sublevels={res.sublevels} "
                 f"compactions={res.compactions}")
    elif args.engine == "dist":
        truss = pkt_dist(g, chunk=args.chunk, table_mode=args.table_mode)
        extra = ""
    elif args.engine == "trilist":
        truss = truss_trilist(g)
        extra = ""
    elif args.engine == "wc":
        truss = truss_wc(g)
        extra = ""
    else:
        truss = truss_ros(g)
        extra = ""
    dt = time.perf_counter() - t0
    gweps = g.wedge_count() / max(dt, 1e-12) / 1e9

    tmax = int(truss.max(initial=2))
    hist = np.bincount(np.asarray(truss, np.int64))
    top = ", ".join(f"{k}:{hist[k]}" for k in np.nonzero(hist)[0][-5:])
    print(f"engine={args.engine} time {dt:.3f}s  GWeps {gweps:.4f}  "
          f"t_max {tmax}  {extra}")
    print(f"largest k-classes: {top}")

    if args.verify:
        ref = truss_numpy(g.El)
        ok = np.array_equal(np.asarray(truss, np.int64), ref)
        print("verify vs oracle:", "OK" if ok else "MISMATCH")
        if not ok:
            raise SystemExit(1)


if __name__ == "__main__":
    main()
