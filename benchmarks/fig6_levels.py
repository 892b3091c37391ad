"""Paper Fig. 6: trussness distribution vs per-level peel time.

The paper's claim: parallel time correlates with the wedge work, not t_max —
50% of uk-2002's time sits below trussness 24 although t_max = 944. We
reproduce the analysis: cumulative edge fraction and cumulative peel-time
fraction by level, using a python-level loop over levels around a jitted
single-level peel (levels stay bulk-synchronous inside)."""

from __future__ import annotations

import functools
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import support as support_mod
from repro.core.pkt import prepare_peel, _SENTINEL_S
from benchmarks.common import prep_graph, row


@functools.partial(jax.jit, static_argnames=("m", "chunk", "n_chunks"))
def _one_level(S_ext, processed, tabs, *, m, chunk, n_chunks):
    """Peel one full level (all sub-levels); returns updated state + level."""
    l = jnp.min(jnp.where(processed, _SENTINEL_S, S_ext))
    inCurr = (~processed) & (S_ext == l)

    def chunk_contrib(c, dec, S_ext, processed, inCurr):
        base = c * chunk
        e1 = jax.lax.dynamic_slice(tabs.e1, (base,), (chunk,))
        e2 = jax.lax.dynamic_slice(tabs.e2, (base,), (chunk,))
        e3 = jax.lax.dynamic_slice(tabs.e3, (base,), (chunk,))
        in1 = inCurr[e1]
        valid = in1 & ~processed[e2] & ~processed[e3]
        dec2 = valid & (S_ext[e2] > l) & ((~inCurr[e3]) | (e1 < e3))
        dec3 = valid & (S_ext[e3] > l) & ((~inCurr[e2]) | (e1 < e2))
        dec = dec.at[jnp.where(dec2, e2, m)].add(dec2.astype(jnp.int32))
        dec = dec.at[jnp.where(dec3, e3, m)].add(dec3.astype(jnp.int32))
        return dec

    def sub_body(st):
        S_ext, processed, inC, subs = st
        curr_edges = inC[:m] & tabs.has_entries
        delta = jnp.zeros((n_chunks + 1,), jnp.int32)
        delta = delta.at[jnp.where(curr_edges, tabs.c_start, n_chunks)].add(
            curr_edges.astype(jnp.int32))
        delta = delta.at[jnp.where(curr_edges, tabs.c_end + 1, n_chunks)].add(
            -curr_edges.astype(jnp.int32))
        active = jnp.cumsum(delta[:n_chunks]) > 0
        n_act = jnp.sum(active.astype(jnp.int32))
        (ids,) = jnp.nonzero(active, size=n_chunks, fill_value=n_chunks - 1)

        def wbody(s):
            i, dec = s
            return i + 1, chunk_contrib(ids[i], dec, S_ext, processed, inC)

        _, dec = jax.lax.while_loop(lambda s: s[0] < n_act, wbody,
                                    (jnp.int32(0),
                                     jnp.zeros((m + 1,), jnp.int32)))
        S_ext = jnp.where((~processed) & (~inC) & (dec > 0),
                          jnp.maximum(S_ext - dec, l), S_ext)
        processed = processed | inC
        inC = (~processed) & (S_ext == l)
        inC = inC.at[m].set(False)
        return S_ext, processed, inC, subs + 1

    S_ext, processed, _, subs = jax.lax.while_loop(
        lambda st: jnp.any(st[2]), sub_body,
        (S_ext, processed, inCurr, jnp.int32(0)))
    return S_ext, processed, l, subs


def run(suite=("rmat-small", "cliques-small", "ba-small")) -> list[str]:
    """CSV rows: per-level frontier widths + sub-level counts (Fig. 6)."""
    out = []
    for name in suite:
        g, stats = prep_graph(name, order="kco")
        stab = support_mod.build_support_table(g)
        rows = support_mod.resolve_peel_table(g)
        S0 = support_mod.compute_support(g, stab)
        tabs, chunk, n_chunks = prepare_peel(rows, g.m, None)   # tuned/auto chunk policy

        S_ext = jnp.concatenate([jnp.asarray(S0),
                                 jnp.full((1,), _SENTINEL_S)])
        processed = jnp.zeros((g.m + 1,), jnp.bool_).at[g.m].set(True)
        times, levels, counts = [], [], []
        while int(jnp.sum(processed)) < g.m + 1:
            t0 = time.perf_counter()
            S_ext, processed, l, subs = _one_level(
                S_ext, processed, tabs, m=g.m, chunk=chunk,
                n_chunks=n_chunks)
            S_ext.block_until_ready()
            times.append(time.perf_counter() - t0)
            levels.append(int(l))
        t = np.asarray(S_ext[:g.m]) + 2
        total = sum(times)
        ct = np.cumsum(times) / max(total, 1e-12)
        # level below which 50% / 90% of time is spent
        lv = np.asarray(levels) + 2
        t50 = int(lv[np.searchsorted(ct, 0.5)])
        t90 = int(lv[np.searchsorted(ct, 0.9)])
        e50 = int(np.quantile(t, 0.5))
        e90 = int(np.quantile(t, 0.9))
        out.append(row(
            f"fig6/{name}", total,
            f"tmax={int(t.max())};edge_t50={e50};edge_t90={e90}"
            f";time_t50={t50};time_t90={t90};levels={len(levels)}"))
    return out


if __name__ == "__main__":
    print("\n".join(run()))
