"""Paper Fig. 4: breakdown of PKT execution among phases, per execution mode.

Phases mirrored: support computation / SCAN+processing (peel) — plus the
wedge-table construction our shape-static SPMD adaptation adds (DESIGN.md
§7.3), reported honestly as its own phase.

Both phases now carry their own mode axis: support is timed per support
executor (jnp / pallas, ``core/support.py`` vs ``kernels/support.py``) and
peel per peel executor (dense / chunked / pallas), and a row is emitted for
every (support_mode, peel_mode) combination so the support-vs-peel split
exposes where each pipeline's time goes.  On non-TPU backends the Pallas
kernels run in *interpret* mode, which is orders of magnitude slower than
compiled XLA — so pallas rows are only emitted for graphs whose wedge table
fits ``PALLAS_MAX_WEDGES`` (those rows are about lowering coverage and shape
behaviour, not competitive time; on a TPU runner the cap is ignored).
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.core import support as support_mod
from repro.core.pkt import _pkt_peel_jit, prepare_peel
from repro.graphs.datasets import GRAPH_SUITE
from benchmarks.common import prep_graph, timeit, row

#: interpret-mode pallas is only timed below this wedge-table size on CPU
PALLAS_MAX_WEDGES = 1 << 16

MODES = ("dense", "chunked", "pallas")
SUPPORT_MODES = support_mod.SUPPORT_MODES


def run(suite=None, modes=MODES, support_modes=SUPPORT_MODES) -> list[str]:
    """CSV rows: per-phase seconds for every executor pair on the suite."""
    on_tpu = jax.default_backend() == "tpu"
    out = []
    for name in suite or GRAPH_SUITE:
        g, stats = prep_graph(name, order="kco")

        t0 = time.perf_counter()
        stab = support_mod.build_support_table(g)
        rows = support_mod.resolve_peel_table(g)
        t_tables = time.perf_counter() - t0

        t_support = {}
        for smode in support_modes:
            if smode == "pallas" and not on_tpu \
                    and stab.size > PALLAS_MAX_WEDGES:
                continue
            t_support[smode] = timeit(
                lambda: support_mod.compute_support(g, stab, mode=smode))
        S0 = support_mod.compute_support(g, stab)

        tabs, chunk, n_chunks = prepare_peel(rows, g.m, None)   # tuned/auto chunk policy

        t_peel = {}
        for pmode in modes:
            if pmode == "pallas" and not on_tpu \
                    and rows.wedges > PALLAS_MAX_WEDGES:
                continue

            def peel():
                # fresh S0 upload per call: _pkt_peel_jit donates its S0
                S, _, _ = _pkt_peel_jit(jnp.asarray(S0), tabs,
                                        m=g.m, chunk=chunk,
                                        n_chunks=n_chunks,
                                        mode=pmode, interpret=not on_tpu)
                S.block_until_ready()

            t_peel[pmode] = timeit(peel, warmup=1, reps=2)

        for smode, t_sup in t_support.items():
            for pmode, t_p in t_peel.items():
                tot = t_tables + t_sup + t_p
                out.append(row(
                    f"fig4/{name}/sup-{smode}+peel-{pmode}", tot,
                    f"support%={100 * t_sup / tot:.1f}"
                    f";peel%={100 * t_p / tot:.1f}"
                    f";tables%={100 * t_tables / tot:.1f}"
                    f";support_us={t_sup * 1e6:.1f}"
                    f";peel_us={t_p * 1e6:.1f}"))
    return out


if __name__ == "__main__":
    print("\n".join(run()))
