"""Batched-engine throughput: many small graphs/sec, serial vs batched.

The serving workload the ROADMAP targets: a stream of modest graphs (ego
nets, rolling windows).  Serial = one ``truss_pkt`` call per graph (each
distinct shape recompiles, then dispatches one-at-a-time).  Batched = the
``TrussEngine`` bucketing the stream into pow2 size classes and vmapping one
compiled pipeline per class.  Both are measured post-warmup (compiles paid),
so the gap isolates dispatch/batching efficiency.
"""

from __future__ import annotations

import numpy as np

from repro.core.pkt import truss_pkt
from repro.graphs.gen import (erdos_renyi_edges, ring_of_cliques_edges,
                              rmat_edges)
from repro.serve.truss_engine import TrussEngine
from benchmarks.common import timeit, row


def _fleet(n_graphs: int, seed: int = 0) -> list[np.ndarray]:
    """A mixed-shape, mixed-size stream of small graphs."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n_graphs):
        kind = i % 3
        if kind == 0:
            out.append(erdos_renyi_edges(
                int(rng.integers(24, 80)), avg_degree=8.0, seed=seed + i))
        elif kind == 1:
            out.append(ring_of_cliques_edges(
                int(rng.integers(3, 6)), int(rng.integers(4, 8))))
        else:
            out.append(rmat_edges(6, edge_factor=4, seed=seed + i))
    return [e for e in out if e.size]


def run(n_graphs: int = 24, mode: str = "chunked",
        seed: int = 0) -> list[str]:
    """CSV rows: serial-vs-batched engine throughput."""
    graphs = _fleet(n_graphs, seed)

    def serial():
        for e in graphs:
            truss_pkt(e, mode=mode)

    t_serial = timeit(serial, warmup=1, reps=2)
    gps_serial = len(graphs) / t_serial
    out = [row(f"engine/serial/{mode}", t_serial,
               f"graphs={len(graphs)};graphs_per_sec={gps_serial:.2f}")]

    # warmup pays per-bucket compiles (cached in jax's global jit cache);
    # the timed pass on a fresh engine measures steady-state dispatch
    TrussEngine(mode=mode).map(graphs)

    def batched():
        TrussEngine(mode=mode).map(graphs)

    t_batched = timeit(batched, warmup=0, reps=2)
    gps_batched = len(graphs) / t_batched
    out.append(row(
        f"engine/batched/{mode}", t_batched,
        f"graphs={len(graphs)};graphs_per_sec={gps_batched:.2f}"
        f";speedup={t_serial / t_batched:.2f}x"))
    return out


if __name__ == "__main__":
    print("\n".join(run()))
