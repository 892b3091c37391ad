"""Paper Fig. 4: breakdown of PKT execution among phases, per execution mode.

Phases mirrored: support computation / SCAN+processing (peel) — plus the
wedge-table construction our shape-static SPMD adaptation adds (DESIGN.md
§7.3), reported honestly as its own phase.

The peel is timed per peel executor (dense / chunked), one row each, so
the support-vs-peel split exposes where each pipeline's time goes.
"""

from __future__ import annotations

import time

import jax.numpy as jnp

from repro.core import support as support_mod
from repro.core.pkt import _pkt_peel_jit, prepare_peel
from repro.graphs.datasets import GRAPH_SUITE
from benchmarks.common import prep_graph, timeit, row

MODES = ("dense", "chunked")


def run(suite=None, modes=MODES) -> list[str]:
    """CSV rows: per-phase seconds for every peel executor on the suite."""
    out = []
    for name in suite or GRAPH_SUITE:
        g, stats = prep_graph(name, order="kco")

        t0 = time.perf_counter()
        stab = support_mod.build_support_table(g)
        rows = support_mod.resolve_peel_table(g)
        t_tables = time.perf_counter() - t0

        t_sup = timeit(lambda: support_mod.compute_support(g, stab))
        S0 = support_mod.compute_support(g, stab)

        tabs, chunk, n_chunks = prepare_peel(rows, g.m, None)   # tuned/auto chunk policy

        for pmode in modes:
            def peel():
                # fresh S0 upload per call: _pkt_peel_jit donates its S0
                S, _, _ = _pkt_peel_jit(jnp.asarray(S0), tabs,
                                        m=g.m, chunk=chunk,
                                        n_chunks=n_chunks, mode=pmode)
                S.block_until_ready()

            t_p = timeit(peel, warmup=1, reps=2)
            tot = t_tables + t_sup + t_p
            out.append(row(
                f"fig4/{name}/peel-{pmode}", tot,
                f"support%={100 * t_sup / tot:.1f}"
                f";peel%={100 * t_p / tot:.1f}"
                f";tables%={100 * t_tables / tot:.1f}"
                f";support_us={t_sup * 1e6:.1f}"
                f";peel_us={t_p * 1e6:.1f}"))
    return out


if __name__ == "__main__":
    print("\n".join(run()))
