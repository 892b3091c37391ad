"""jit'd wrappers around the Pallas intersect kernel.

``compute_support_kernel`` is a drop-in replacement for
``repro.core.support.compute_support``: edges are bucketed by oriented-degree
class (power-of-two padding — the SPMD stand-in for OpenMP dynamic
scheduling), each bucket is intersected by the Pallas kernel, and support
increments are scattered through the Eid maps. Edges whose endpoints exceed
the largest bucket fall back to the ranged-binary-search path (skewed-tail
handling: the few huge-degree rows would waste VMEM padding).
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from repro.graphs.csr import CSRGraph
from repro.core import support as support_mod
from repro.kernels.intersect import intersect_blocked

_DEG_CLASSES = (8, 16, 32, 64, 128, 256)


def _block_rows_for(d: int) -> int:
    # keep the (BE, D, D) compare cube ≈ ≤ 16 MiB of VMEM traffic
    return int(max(8, min(1024, (1 << 22) // max(d * d, 1))))


def _gather_rows(N, Eid, start, length, D):
    """(E, D) padded rows of N and Eid: N[start[i] + j] for j < length[i]."""
    idx = start[:, None] + jnp.arange(D, dtype=jnp.int32)[None, :]
    mask = jnp.arange(D, dtype=jnp.int32)[None, :] < length[:, None]
    safe = jnp.minimum(idx, N.shape[0] - 1)
    rows = jnp.where(mask, N[safe], -1)
    eids = jnp.where(mask, Eid[safe], 0)
    return rows, eids, mask


def _bucket_support(N, Eid, u_start, u_len, v_start, v_len, e1, m, D,
                    interpret):
    """Support contributions of one degree-class bucket (jit-traceable)."""
    rows_a, eids_a, _ = _gather_rows(N, Eid, u_start, u_len, D)
    rows_b, eids_b, _ = _gather_rows(N, Eid, v_start, v_len, D)
    rows_b = jnp.where(rows_b < 0, -2, rows_b)  # distinct pad for B side
    cnt, hita, hitb = intersect_blocked(
        rows_a, rows_b, block_rows=_block_rows_for(D), interpret=interpret)
    S = jnp.zeros((m + 1,), jnp.int32)
    S = S.at[e1].add(cnt)
    S = S.at[jnp.where(hita > 0, eids_a, m)].add(hita)
    S = S.at[jnp.where(hitb > 0, eids_b, m)].add(hitb)
    return S


def compute_support_kernel(g: CSRGraph, *, interpret: bool | None = None,
                           classes=_DEG_CLASSES) -> np.ndarray:
    """AM4 support computation with the Pallas intersect kernel."""
    if g.m == 0:
        return np.zeros(0, np.int32)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"  # off the TPU

    u = g.El[:, 0].astype(np.int64)
    v = g.El[:, 1].astype(np.int64)
    Es = g.Es.astype(np.int64)
    Eo = g.Eo.astype(np.int64)
    dpu = (Es[u + 1] - Eo[u])     # |N⁺(u)|
    dpv = (Es[v + 1] - Eo[v])     # |N⁺(v)|
    dmax = np.maximum(dpu, dpv)

    N = jnp.asarray(g.N)
    Eid = jnp.asarray(g.Eid)
    S_total = jnp.zeros((g.m + 1,), jnp.int32)

    prev = 0
    fallback_mask = dmax > classes[-1]
    for D in classes:
        sel = (dmax > prev) & (dmax <= D)
        prev = D
        ids = np.nonzero(sel)[0]
        if ids.size == 0:
            continue
        S_total = S_total + _bucket_support(
            N, Eid,
            jnp.asarray(Eo[u[ids]], jnp.int32),
            jnp.asarray(dpu[ids], jnp.int32),
            jnp.asarray(Eo[v[ids]], jnp.int32),
            jnp.asarray(dpv[ids], jnp.int32),
            jnp.asarray(ids, jnp.int32),
            g.m, D, interpret)

    S = np.asarray(S_total[: g.m])

    fb = np.nonzero(fallback_mask)[0]
    if fb.size:
        S = S + _fallback_support(g, fb)
    return S.astype(np.int32)


def _fallback_support(g: CSRGraph, edge_ids: np.ndarray) -> np.ndarray:
    """Ranged-binary-search support restricted to the given (huge) edges."""
    u = g.El[edge_ids, 0].astype(np.int64)
    v = g.El[edge_ids, 1].astype(np.int64)
    Es = g.Es.astype(np.int64)
    Eo = g.Eo.astype(np.int64)
    cnt = Es[v + 1] - Eo[v]
    off = np.zeros(edge_ids.size + 1, np.int64)
    np.cumsum(cnt, out=off[1:])
    nw = int(off[-1])
    local = np.repeat(np.arange(edge_ids.size), cnt)
    intra = np.arange(nw) - off[local]
    tab_e1 = edge_ids[local].astype(np.int32)
    cand_slot = (Eo[v[local]] + intra).astype(np.int32)
    lo = Eo[u[local]].astype(np.int32)
    hi = Es[u[local] + 1].astype(np.int32)
    S = support_mod._support_jit(
        jnp.asarray(g.N), jnp.asarray(g.Eid),
        jnp.asarray(tab_e1), jnp.asarray(cand_slot),
        jnp.asarray(lo), jnp.asarray(hi),
        support_mod._search_iters(g, oriented=True), g.m)
    return np.asarray(S)
