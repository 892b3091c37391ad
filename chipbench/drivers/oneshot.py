"""Closed loop, one client: whole decompositions back to back.

The client hands ``truss_pkt`` the cell's edge array (rows in the seed's
order, labels from the seed's relabelling) and waits for trussness of
every row; then it sends the next.  Host preparation, the upload and the
readback are inside every decomposition, as a batch user pays them.  A
decomposition starts only while one more, at the last one's length, still
ends inside the window, so the window never runs long.

Set-up runs the benchmark's own host preparation (degeneracy order,
relabelling, CSR) and one ``pkt`` of the result, which compiles (or loads)
every program the window's decompositions run and reports their levels
and sub-levels outside the window.
"""

from __future__ import annotations

import time

import numpy as np

from chipbench import graphs, reference, work
from chipbench.common import Cell, log


class Driver(Cell):
    """See the module docstring."""

    def setup(self) -> None:
        from repro.core import pkt
        from repro.graphs.csr import (build_csr, canonical_edges_with_rows,
                                      degeneracy_order, relabel)

        t0 = time.perf_counter()
        rng = np.random.default_rng(self.seed)
        base = graphs.graph_from_config(self.config)
        self.edges = graphs.shuffled_rows(
            graphs.relabelling(base, rng)[base], rng)
        E = reference.canonical(self.edges)
        self.work = {"support_probes": work.support_probes(E),
                     "peel_probes": work.peel_probes(E)}
        log(f"inputs and work counts {time.perf_counter() - t0:.3f}s")

        t0 = time.perf_counter()
        Ec, _, _, n = canonical_edges_with_rows(self.edges)
        g = build_csr(relabel(Ec, degeneracy_order(Ec, n)), n)
        self.prep_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        res = pkt(g)
        log(f"graph: n={g.n} m={g.m} support probes "
            f"{self.work['support_probes']} peel probes "
            f"{self.work['peel_probes']}; host prep {self.prep_s:.3f}s; "
            f"warm-up pkt {time.perf_counter() - t0:.3f}s: levels "
            f"{res.levels} sub-levels {res.sublevels} compactions "
            f"{res.compactions} t_max {int(res.trussness.max(initial=2))}")
        self.outputs: list[np.ndarray] = []
        self.spans: list[float] = []
        self.traced = 0

    def window(self) -> None:
        import jax

        from repro.core import truss_pkt

        t_start = time.perf_counter()
        last = 0.0
        while not self.spans or (time.perf_counter() - t_start + last
                                 <= self.seconds):
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("chipbench.truss_pkt"):
                out = truss_pkt(self.edges)
            last = time.perf_counter() - t0
            self.outputs.append(out)
            self.spans.append(last)
            if self.tracer.active:
                self.traced = len(self.spans)
                if self.tracer.due():
                    self.tracer.stop()
        self.elapsed = time.perf_counter() - t_start
        log(f"decompositions {len(self.spans)} in {self.elapsed:.3f}s: "
            + " ".join(f"{s:.3f}" for s in self.spans))

    def close(self) -> None:
        import jax

        jax.clear_caches()

    def check(self) -> dict:
        t0 = time.perf_counter()
        E, t = reference.trussness(self.edges)
        n = int(E.max()) + 1
        lo = np.minimum(self.edges[:, 0], self.edges[:, 1])
        hi = np.maximum(self.edges[:, 0], self.edges[:, 1])
        want = t[np.searchsorted(reference.edge_key(E, n), lo * n + hi)]
        wrong = sum(int((np.asarray(out) != want).sum())
                    for out in self.outputs)
        log(f"reference {time.perf_counter() - t0:.3f}s over "
            f"{len(self.outputs)} decompositions of {len(want)} rows")
        return {"wrong_trussness": {"value": wrong, "limit": 0}}

    def end_to_end(self) -> dict:
        return {"decomp_s": self.elapsed / len(self.spans)}

    def observations(self) -> dict:
        # a traced run describes the decompositions inside its trace
        return {"decompositions": self.traced or len(self.spans), **self.work,
                "host_s": {"prep": self.prep_s}}

    def attempts(self) -> tuple[int, int]:
        return len(self.spans), 0
