"""Closed loop, one client: edge batches and (q, k) community queries.

The deployment of Huang et al. (SIGMOD 2014): one resident handle with
its community index, opened through ``TrussScheduler.open_async``.  Each
round the client sends one ``update_async`` batch that deletes ``batch``
live edges drawn uniformly and re-inserts the ones deleted
``window_rounds`` rounds earlier (so the edge count holds once the stream
is that deep), waits for its commit, reads the committed trussness of
every live edge with ``query_async``, then sends ``queries``
``community_async`` requests and waits for every answer.  A query's
vertex is an endpoint of a uniformly drawn live edge (degree-weighted),
drawn again where none of the vertex's edges reaches trussness 3; its
level is uniform in 3 .. the largest trussness among the vertex's edges,
so no answer is empty.

The configuration's ``stream_seed`` fixes the graph's stream: which edges
each round deletes and which queries it asks (given the committed
trussness).  The run's seed relabels the vertices and orders the rows of
every array sent, as in ``oneshot``.  A round starts only while one more,
at the last one's length, still ends inside the window.

Set-up opens the handle and runs ``SETUP_ROUNDS`` stream rounds and a
query at every level, which compiles (or loads) every program the
window's rounds run.  The check compares every round of the window (at
most ``CHECK_ROUNDS`` of them, drawn from the seed, plus the last) with
the plain reference: each live edge's trussness, and each answer's
communities as edge sets.
"""

from __future__ import annotations

import collections
import importlib
import time

import numpy as np

from chipbench import graphs, reference, reference_community, work_community
from chipbench.common import Cell, log

#: stream rounds set-up runs before the window, the first of them
#: deletion-only: the edge count reaches its steady value at round
#: ``window_rounds`` and the rounds after it take the window's path, so
#: the window obtains no program
SETUP_ROUNDS = 6
#: rounds the check covers at the most, besides the last one
CHECK_ROUNDS = 128


class Stream:
    """The edge stream and the queries, in the base graph's labels."""

    def __init__(self, base: np.ndarray, seed: int, *, batch: int,
                 window_rounds: int, queries: int):
        self.base = base
        self.n = int(base.max()) + 1
        self.rng = np.random.default_rng(seed)
        self.batch = batch
        self.window_rounds = window_rounds
        self.queries = queries
        self.live = np.ones(base.shape[0], bool)
        #: edge ids each round deleted, round by round
        self.deleted: list[np.ndarray] = []

    def next_batch(self) -> tuple[np.ndarray, np.ndarray]:
        """(re-inserted, deleted) base edge ids of the next round."""
        dele = np.sort(self.rng.choice(np.flatnonzero(self.live),
                                       self.batch, replace=False))
        r = len(self.deleted)
        ins = (self.deleted[r - self.window_rounds]
               if r >= self.window_rounds else np.zeros(0, np.int64))
        self.live[dele] = False
        self.live[ins] = True
        self.deleted.append(dele)
        return ins, dele

    def live_at(self, r: int) -> np.ndarray:
        """Live mask after round ``r``'s batch."""
        live = np.ones(self.base.shape[0], bool)
        for d in self.deleted[max(0, r - self.window_rounds + 1):r + 1]:
            live[d] = False
        return live

    def pick(self, T: np.ndarray) -> list[tuple[int, int]]:
        """The round's (q, k) queries, given the trussness ``T`` of the
        live edges (in base id order)."""
        E = self.base[self.live]
        top = np.zeros(self.n, np.int64)
        np.maximum.at(top, E[:, 0], T)
        np.maximum.at(top, E[:, 1], T)
        out = []
        while len(out) < self.queries:
            q = int(E[self.rng.integers(0, E.shape[0]),
                      self.rng.integers(0, 2)])
            if top[q] >= 3:
                out.append((q, 3 + int(self.rng.integers(0, top[q] - 2))))
        return out


class Driver(Cell):
    """See the module docstring."""

    def setup(self) -> None:
        from repro.serve import TrussScheduler

        if not hasattr(TrussScheduler, "community_async"):
            raise SystemExit("chipbench: this program has no "
                             "TrussScheduler.community_async; the cell "
                             "cannot run")
        t0 = time.perf_counter()
        tr = self.traffic
        rng = np.random.default_rng(self.seed)
        self.base = graphs.graph_from_config(self.config)
        self.perm = graphs.relabelling(self.base, rng)
        self.rows_rng = rng
        self.stream = Stream(self.base, self.config["stream_seed"],
                             batch=tr["batch"],
                             window_rounds=tr["window_rounds"],
                             queries=tr["queries"])
        self.n_run = int(self.perm.max()) + 1
        self.inv = np.zeros(self.n_run, np.int64)
        active = np.flatnonzero(self.perm >= 0)
        self.inv[self.perm[active]] = active
        log(f"inputs {time.perf_counter() - t0:.3f}s: n={active.shape[0]} "
            f"m={self.base.shape[0]}")

        t0 = time.perf_counter()
        self.failed = 0
        self.sched = TrussScheduler()
        self.handle = self.sched.open_async(
            self._rows(np.arange(self.base.shape[0]))).result()
        log(f"open {time.perf_counter() - t0:.3f}s")
        for r in range(SETUP_ROUNDS):
            t0 = time.perf_counter()
            rec = self._round()
            log(f"set-up round {r} {time.perf_counter() - t0:.3f}s: "
                f"{rec['mode']}, m={int(self.stream.live.sum())}, "
                f"levels {sorted(k for _, k in rec['queries'])}")
        t0 = time.perf_counter()
        T = rec["T"]
        live = self.base[self.stream.live]
        q = int(live[int(np.argmax(T)), 0])
        futs = [self.sched.community_async(self.handle, int(self.perm[q]), k)
                for k in range(int(T.max()), 2, -1)]
        for f in futs:
            f.result()
        log(f"a query at every level, 3..{int(T.max())}: "
            f"{time.perf_counter() - t0:.3f}s")
        self.times: list[float] = []
        self.bounds: list[tuple[int, int]] = []
        self.kept: dict[int, dict] = {}
        self.slots: list[int] = []
        self.check_rng = np.random.default_rng(self.seed)
        self.last: tuple[int, dict] | None = None
        self.traced = 0
        self.traced_recs: list[dict] = []
        self.traced_spans = None
        self.failed = 0         # the window's failed requests

    def _rows(self, ids: np.ndarray) -> np.ndarray:
        """The base edges ``ids`` in the run's labels and row order."""
        rows = self.perm[self.base[ids]]
        return graphs.shuffled_rows(rows, self.rows_rng) if rows.size \
            else rows.reshape(0, 2)

    def _round(self) -> dict:
        """One round: the batch, its commit, the trussness read and the
        queries; returns what the check needs."""
        ins, dele = self.stream.next_batch()
        st = self.sched.update_async(self.handle, add_edges=self._rows(ins),
                                     remove_edges=self._rows(dele)).result()
        ids = np.flatnonzero(self.stream.live)
        T = np.asarray(self.sched.query_async(
            self.handle, self.perm[self.base[ids]]).result())
        queries = self.stream.pick(T)
        futs = [self.sched.community_async(self.handle, int(self.perm[q]), k)
                for q, k in queries]
        answers = []
        for f in futs:
            try:
                answers.append(f.result())
            except Exception as e:              # noqa: BLE001 — counted
                log(f"query failed: {type(e).__name__}: {e}")
                self.failed += 1
                answers.append(None)
        return {"r": len(self.stream.deleted) - 1, "mode": st.mode, "T": T,
                "queries": queries, "answers": answers}

    def window(self) -> None:
        import jax

        t_start = time.perf_counter()
        last = 0.0
        while not self.times or (time.perf_counter() - t_start + last
                                 <= self.seconds):
            t0 = time.perf_counter()
            ns = time.perf_counter_ns()
            with jax.profiler.TraceAnnotation("chipbench.round"):
                rec = self._round()
            last = time.perf_counter() - t0
            self.bounds.append((ns, time.perf_counter_ns()))
            self.times.append(last)
            self._keep(len(self.times) - 1, rec)
            if self.tracer.active:
                self.traced = len(self.times)
                self.traced_recs.append(rec)
                if self.tracer.due():
                    self.tracer.stop()
                    self._snapshot_spans()
        if self.tracer.active:
            self._snapshot_spans()
        self.elapsed = time.perf_counter() - t_start
        modes = collections.Counter(r["mode"] for r in self.kept.values())
        log(f"rounds {len(self.times)} in {self.elapsed:.3f}s "
            f"(kept {dict(modes)}): "
            + " ".join(f"{s:.3f}" for s in self.times))
        self._log_rounds()

    def _log_rounds(self) -> None:
        """The scheduler's retry and heal counts, and where the slowest and
        the median round spent their time by the program's spans, so a run
        with a stall says where it sat."""
        c = self.sched.stats()["counters"]
        log(f"scheduler: retries {c['retries']} heals {c['heals']} "
            f"errors {c['errors']}")
        try:
            spans = importlib.import_module("repro.spans")
        except ImportError:
            return
        recs = spans.records()
        order = np.argsort(self.times)
        for what, i in (("slowest", order[-1]),
                        ("median", order[len(order) // 2])):
            lo, hi = self.bounds[i]
            mine = [r for r in recs if lo <= r.start_ns and r.end_ns <= hi]
            if not mine:
                log(f"{what} round {i}: its spans have left the ring")
                continue
            split = collections.Counter()
            for r in mine:
                split[r.name] += r.end_ns - r.start_ns
            inside = sum(r.end_ns - r.start_ns for r in mine
                         if r.parent is None)
            log(f"{what} round {i} {self.times[i]:.3f}s: "
                + ", ".join(f"{k} {v / 1e9:.3f}"
                            for k, v in split.most_common())
                + f"; outside the program's spans "
                f"{(hi - lo - inside) / 1e9:.3f}")

    def _keep(self, i: int, rec: dict) -> None:
        """Keep round ``i`` for the check: every round up to
        ``CHECK_ROUNDS``, then a uniform sample of that many drawn from the
        seed (reservoir sampling), and always the last round."""
        self.last = (i, rec)
        if i < CHECK_ROUNDS:
            self.slots.append(i)
            self.kept[i] = rec
            return
        j = int(self.check_rng.integers(0, i + 1))
        if j < CHECK_ROUNDS:
            del self.kept[self.slots[j]]
            self.slots[j] = i
            self.kept[i] = rec

    def _snapshot_spans(self) -> None:
        """The program's spans recorded inside the trace, taken when it
        stops (the ring keeps only the newest spans)."""
        try:
            spans = importlib.import_module("repro.spans")
        except ImportError:
            return
        self.traced_spans = [s for s in spans.records() if s.traced]

    def close(self) -> None:
        self.sched.close()
        self.handle = None

    def _answer_ids(self, rnd, answer) -> list[np.ndarray]:
        """An answer's communities as sorted edge ids of ``rnd``'s edges
        (-1 for an edge not in the graph), ordered by smallest id."""
        keys = reference.edge_key(rnd.E, rnd.n)
        out = []
        for comm in answer:
            e = self.inv[np.asarray(comm, np.int64).reshape(-1, 2)]
            k = (np.minimum(e[:, 0], e[:, 1]) * rnd.n
                 + np.maximum(e[:, 0], e[:, 1]))
            at = np.minimum(np.searchsorted(keys, k), keys.shape[0] - 1)
            ids = np.where(keys[at] == k, at, -1)
            out.append(np.sort(ids))
        return sorted(out, key=lambda ids: int(ids[0]) if ids.size else -1)

    def _reference(self, rec: dict):
        live = self.stream.live_at(rec["r"])
        return reference_community.Round.of(self.base[live])

    def check(self) -> dict:
        t0 = time.perf_counter()
        rounds = dict(self.kept)
        i, rec = self.last
        rounds[i] = rec
        wrong_t = wrong_c = 0
        for i in sorted(rounds):
            rec = rounds[i]
            rnd = self._reference(rec)
            wrong_t += int((rec["T"] != rnd.T).sum())
            for (q, k), answer in zip(rec["queries"], rec["answers"]):
                if answer is None:
                    continue
                got = self._answer_ids(rnd, answer)
                want = rnd.answer(q, k)
                if len(got) != len(want) or not all(
                        np.array_equal(a, b) for a, b in zip(got, want)):
                    wrong_c += 1
        log(f"reference {time.perf_counter() - t0:.3f}s over {len(rounds)} "
            f"of {len(self.times)} rounds")
        return {"wrong_trussness": {"value": wrong_t, "limit": 0},
                "wrong_communities": {"value": wrong_c, "limit": 0}}

    def end_to_end(self) -> dict:
        return {"decomp_s": self.elapsed / len(self.times)}

    def observations(self) -> dict:
        # a traced run describes the rounds inside its trace
        rounds = self.traced or len(self.times)
        recs = self.traced_recs or list(self.kept.values())[:rounds]
        flood = sum(work_community.answer_bytes(self._reference(rec),
                                                rec["queries"])
                    for rec in recs)
        return {"rounds": rounds,
                "decompositions": sum(r["mode"] == "full" for r in recs),
                "traced_spans": self.traced_spans,
                "traced_roots": {"inc.update": rounds,
                                 "engine.community":
                                     rounds * self.traffic["queries"]},
                "flood_bytes": flood}

    def attempts(self) -> tuple[int, int]:
        return len(self.times) * (2 + self.traffic["queries"]), self.failed
