"""Parallel edge-support computation — the AM4 (Algorithm 3) TPU adaptation.

The paper orients edges by increasing k-core vertex order and counts each
triangle once in canonical order, using a thread-local size-n scratch array X
for O(1) membership tests. On TPU there is no per-thread random-access scratch;
the adaptation (DESIGN.md §2) replaces X with:

  * a *flat oriented wedge table* built once per graph: one entry per
    (oriented edge (u→v), candidate w ∈ N⁺(v)) pair — exactly the wedges the
    AM4 loop nest inspects, Θ(Σ_v d⁻(v)·d⁺(v)) entries;
  * a vectorized *ranged binary search* of w in N⁺(u) (sorted CSR rows) —
    the membership test, O(log d⁺) gathers per probe;
  * scatter-adds into S — the deterministic analogue of the three AtomicAdds.

Each triangle u<v<w is discovered exactly once, anchored at its lowest-vertex
edge (u,v) with w scanned from N⁺(v). Work: Θ(m + Σ_v d⁻(v)·d⁺(v)·log d⁺) —
the ordering-dependence (Table 2) is preserved: relabeling by coreness shrinks
d⁺ exactly as in the paper.

The wedge table is evaluated as one flat jnp gather/search/scatter program
(``_support_jit``; fused with the device table build in
``_support_device_jit``) — XLA fuses it, but every probe round-trips
through HBM.  The support and peel-table builders share their search
machinery via ``kernels/wedge_common.py``.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import numpy as np

import jax
import jax.numpy as jnp

from repro.graphs.csr import CSRGraph
from repro.kernels.wedge_common import next_pow2, probe, probe_np
# re-export: the triangle-list engine binary-searches through this module's
# namespace (kernels.wedge_common is the canonical home)
from repro.kernels.wedge_common import ranged_searchsorted  # noqa: F401

#: where wedge tables are constructed: "numpy" is the original host builder
#: (kept as the parity oracle), "device" the jitted XLA builder below —
#: tables never round-trip through host memory
TABLE_MODES = ("numpy", "device")


@dataclasses.dataclass(frozen=True)
class WedgeTable:
    """Flat (edge, candidate-slot) table + per-query search ranges."""

    e1: np.ndarray       # (Nw,) int32 — edge id of (u, v)
    cand_slot: np.ndarray  # (Nw,) int32 — CSR slot of w (gives w and Eid e2)
    lo: np.ndarray       # (Nw,) int32 — probe range start in N
    hi: np.ndarray       # (Nw,) int32 — probe range end in N
    off: np.ndarray      # (m+1,) int64 — entries of edge e at [off[e], off[e+1])

    @property
    def size(self) -> int:
        """Number of wedge entries (Nw)."""
        return int(self.e1.shape[0])


def build_support_table(g: CSRGraph) -> WedgeTable:
    """Oriented wedge table: for edge (u,v), candidates w ∈ N⁺(v), probe N⁺(u)."""
    u = g.El[:, 0].astype(np.int64)
    v = g.El[:, 1].astype(np.int64)
    Es = g.Es.astype(np.int64)
    Eo = g.Eo.astype(np.int64)
    cnt = Es[v + 1] - Eo[v]                      # |N⁺(v)| per edge
    off = np.zeros(g.m + 1, dtype=np.int64)
    np.cumsum(cnt, out=off[1:])
    Nw = int(off[-1])
    e1 = np.repeat(np.arange(g.m, dtype=np.int64), cnt)
    intra = np.arange(Nw, dtype=np.int64) - off[e1]
    cand_slot = Eo[v[e1]] + intra
    lo = Eo[u[e1]]
    hi = Es[u[e1] + 1]
    return WedgeTable(
        e1=e1.astype(np.int32),
        cand_slot=cand_slot.astype(np.int32),
        lo=lo.astype(np.int32),
        hi=hi.astype(np.int32),
        off=off,
    )


def build_peel_table(g: CSRGraph) -> WedgeTable:
    """Full-adjacency wedge table the peel-phase rows are resolved from.

    For edge e=(u,v): candidates w from the *smaller*-degree endpoint's full
    adjacency, probed against the other endpoint's full adjacency — the
    ProcessSubLevel loop nest of Algorithm 5 with the cheap side chosen
    (the paper marks N(u) and scans N(v); we pick min-degree for the scan).
    ``resolve_peel_table`` keeps the rows whose probe hits.
    """
    u = g.El[:, 0].astype(np.int64)
    v = g.El[:, 1].astype(np.int64)
    Es = g.Es.astype(np.int64)
    deg = (Es[1:] - Es[:-1])
    swap = deg[u] > deg[v]
    cand = np.where(swap, v, u)                  # scan this side
    probe = np.where(swap, u, v)                 # binary-search this side
    cnt = deg[cand]
    off = np.zeros(g.m + 1, dtype=np.int64)
    np.cumsum(cnt, out=off[1:])
    Nw = int(off[-1])
    e1 = np.repeat(np.arange(g.m, dtype=np.int64), cnt)
    intra = np.arange(Nw, dtype=np.int64) - off[e1]
    cand_slot = Es[cand[e1]] + intra
    lo = Es[probe[e1]]
    hi = Es[probe[e1] + 1]
    return WedgeTable(
        e1=e1.astype(np.int32),
        cand_slot=cand_slot.astype(np.int32),
        lo=lo.astype(np.int32),
        hi=hi.astype(np.int32),
        off=off,
    )


@dataclasses.dataclass(frozen=True)
class PeelRows:
    """The peel table: one row ``(e1, e2, e3)`` per triangle through ``e1``.

    ``e2``/``e3`` are the two edges that close the triangle with anchor
    ``e1``.  Rows are grouped by ``e1`` in ascending order, in wedge order
    within a group, so edge e's rows are ``[off[e], off[e+1])`` and that
    count is e's support: every triangle appears three times (3T rows).
    ``wedges`` is the row count of the wedge table the rows were resolved
    from; it sets the chunk and the padded size, so a table of resolved
    rows is laid out exactly as its wedge table would be, and 3T never
    exceeds it.
    """

    e1: np.ndarray       # (3T,) int32 anchor edge
    e2: np.ndarray       # (3T,) int32 edge (scanned endpoint, w)
    e3: np.ndarray       # (3T,) int32 edge (probed endpoint, w)
    off: np.ndarray      # (m+1,) int64 — rows of edge e at [off[e], off[e+1])
    wedges: int          # rows of the wedge table resolved

    @property
    def size(self) -> int:
        """Number of resolved rows (3T)."""
        return int(self.e1.shape[0])


def resolve_peel_table(g: CSRGraph,
                       tab: WedgeTable | None = None) -> PeelRows:
    """Host resolve of a peel wedge table: probe every row once (``probe_np``)
    and keep the hits as ``(e1, e2, e3)`` rows (``PeelRows``).

    ``tab`` defaults to ``build_peel_table(g)``; a table restricted to some
    anchors (``truss_inc.wedge_subtable``) resolves the same way.
    """
    if tab is None:
        tab = build_peel_table(g)
    if tab.size == 0:
        z = np.zeros(0, np.int32)
        return PeelRows(z, z.copy(), z.copy(), np.zeros(g.m + 1, np.int64), 0)
    hit, safe = probe_np(g.N, tab.cand_slot, tab.lo, tab.hi,
                         iters=_search_iters(g))
    off = np.zeros(g.m + 1, np.int64)
    np.cumsum(np.bincount(tab.e1[hit], minlength=g.m), out=off[1:])
    return PeelRows(
        e1=tab.e1[hit],
        e2=g.Eid[tab.cand_slot[hit]].astype(np.int32),
        e3=g.Eid[safe[hit]].astype(np.int32),
        off=off, wedges=tab.size)


# --- device-side table construction (DESIGN.md §10) -------------------------
#
# The builders above materialize Θ(Σ d·d)-entry tables in host numpy and pay
# a host→device transfer several× the graph size on every decomposition.  The
# jitted XLA mirrors below build the same rows *on device* from the CSR
# arrays alone: per-edge candidate counts, segment offsets via cumsum, and
# the row→edge assignment by segment expansion (``_expand_segments``): one
# scatter-add of the m segment ends into a per-row histogram and one prefix
# sum over the rows — O(m) scattered + O(size) scanned elements and no loop,
# where a ``searchsorted`` over the offsets costs ceil(log2(m+1)) dependent
# gathers per row.  Rows are materialized to a *static* pow2-padded ``size``
# (the exact entry count is data-dependent; the cheap O(m) host calculators
# below bound it before the jit runs).  Support-table padding rows carry
# the anchor sentinel ``m`` and an empty probe range ``lo == hi == 0``.
# The peel builder probes its wedge rows in the same jit
# and keeps only the hits, as triangle rows ``(e1, e2, e3)`` (``PeelRows``),
# with the sentinel ``m`` in all three columns on the rows left over.
# ``m_real`` is a dynamic scalar so the batched engine can reuse one compiled
# builder for every graph of a size class — and vmap it across the class.

#: device tables carry int32 offsets; reject anything larger outright
_MAX_TABLE = np.iinfo(np.int32).max


def support_table_size(g: CSRGraph) -> int:
    """Exact entry count of ``build_support_table(g)`` — O(m) host work."""
    if g.m == 0:
        return 0
    v = g.El[:, 1].astype(np.int64)
    return int((g.Es.astype(np.int64)[v + 1] - g.Eo.astype(np.int64)[v]).sum())


def peel_table_size(g: CSRGraph) -> int:
    """Exact entry count of ``build_peel_table(g)`` — O(m) host work."""
    if g.m == 0:
        return 0
    Es = g.Es.astype(np.int64)
    deg = Es[1:] - Es[:-1]
    return int(np.minimum(deg[g.El[:, 0]], deg[g.El[:, 1]]).sum())


def _check_table_size(size: int) -> None:
    """Guard the int32 device-table layout.

    ``size`` must be the number of rows the builder will *materialize* —
    i.e. the padded size (pow2, or shard-rounded), not the raw entry count:
    a raw count just under 2^31 still pads past the int32 range.
    """
    if size > _MAX_TABLE:
        raise ValueError(
            f"wedge table of {size} (padded) entries exceeds the int32 "
            f"device-table layout; use table_mode='numpy' (int64 host "
            f"offsets)")


def _prefix_sum(x):
    """Inclusive prefix sum of a 1-D int32 array, equal to ``jnp.cumsum``.

    Scans ``x`` as up to 128 contiguous rows, then adds each row's exclusive
    prefix of row totals.  For a TPU v5e, XLA compiles this in about a
    second; a flat ``cumsum`` of 2^18-2^21 elements took it 8-37 s.
    """
    c = jnp.cumsum(x.reshape(math.gcd(x.shape[0], 128), -1), axis=1,
                   dtype=jnp.int32)
    before = jnp.cumsum(c[:, -1], dtype=jnp.int32) - c[:, -1]
    return (c + before[:, None]).reshape(-1)


def _expand_segments(off, size: int, m: int, start=0):
    """Row → segment assignment for a cumsum offset array ``off`` (m+1,).

    Covers rows ``[start, start + size)`` (``start`` may be traced: each
    shard of the distributed path builds its own slice).  Returns
    ``(e1, e1c, intra, valid)``: the owning segment of each row (``m`` for
    rows beyond ``off[m]``), a clamped variant safe as a gather index, the
    offset within the segment, and the validity mask.
    """
    idx = start + jnp.arange(size, dtype=jnp.int32)
    # e1[i] = #{j : off[j+1] <= idx[i]} by histogram + prefix sum: each
    # segment end, shifted to the slice, lands in one bin (ends before the
    # slice clip into bin 0, ends at or past its end into bin ``size``,
    # which the scatter drops; empty segments share a bin, which the add
    # counts).
    ends = jnp.clip(off[1:] - start, 0, size)
    hist = jnp.zeros((size,), jnp.int32).at[ends].add(1, mode="drop")
    e1 = _prefix_sum(hist)
    e1c = jnp.minimum(e1, m - 1)
    valid = idx < off[m]
    intra = idx - off[e1c]
    return jnp.where(valid, e1, m), e1c, intra, valid


def support_rows(u, v, Es, Eo, m_real, *, m: int, size: int, start=0):
    """Trace-level ``build_support_table`` rows ``[start, start + size)``.

    ``u``/``v``: (m,) edge endpoints (rows >= ``m_real`` are inert padding);
    ``Es``: (n_pad+1,) CSR offsets; ``Eo``: (n_pad,).  Returns
    ``(e1, cand_slot, lo, hi, off)``; rows past the table carry the anchor
    sentinel ``m`` and an empty probe range.
    """
    ar = jnp.arange(m, dtype=jnp.int32)
    cnt = jnp.where(ar < m_real, Es[v + 1] - Eo[v], 0)
    off = jnp.zeros((m + 1,), jnp.int32).at[1:].set(jnp.cumsum(cnt))
    e1, e1c, intra, valid = _expand_segments(off, size, m, start)
    cand = jnp.where(valid, Eo[v[e1c]] + intra, 0)
    lo = jnp.where(valid, Eo[u[e1c]], 0)
    hi = jnp.where(valid, Es[u[e1c] + 1], 0)
    return e1, cand, lo, hi, off


#: wedge rows the peel builder expands and probes per step, at the least
#: (``peel_rows``); a step takes at least ``m`` rows, so the per-step
#: segment-end histogram over m edges never outweighs the rows
_RESOLVE_BLOCK = 1 << 16


def peel_rows(u, v, Es, N, Eid, m_real, *, m: int, size: int, chunk: int,
              iters: int, start=0):
    """Trace-level ``resolve_peel_table`` over wedge rows
    ``[start, start + size)``, with per-edge chunk metadata.

    Builds the ``build_peel_table`` wedge rows (candidates from the
    min-degree endpoint's full adjacency, probes against the other), runs
    the ``probe`` once over every row (``iters`` bounds the probed
    adjacency: ``_search_iters`` of the graph, or log2 of a padded
    vertex bucket plus one), and keeps the hits as
    ``(e1, e2 = Eid[cand], e3 = Eid[safe])``, compacted stably to the
    front: rows stay grouped by ``e1`` in ascending order.  The other
    ``size - hits`` rows carry the sentinel ``m`` in all three columns.
    ``off`` (m+1,) gives each edge's kept rows ``[off[e], off[e+1])``
    within the slice (their count is e's support when the slice covers the
    table), ``off[m]`` the rows kept, and ``c_start``/``c_end``/``has``
    the ``chunk_ranges`` bookkeeping of those rows for the static
    ``chunk``, so the peel loop's chunk-skipping needs no host pass.
    Returns ``(e1, e2, e3, off, c_start, c_end, has)``.

    The rows are expanded, probed and compacted a block at a time, in a
    loop that stops at the slice's last wedge row: the pow2 padding past
    it (up to half of ``size``) is never expanded or probed.  Each block's
    hits land after the hits of the blocks before it (a prefix sum over
    the block's hit mask plus a running count).
    """
    deg = Es[1:] - Es[:-1]
    swap = deg[u] > deg[v]
    cand_v = jnp.where(swap, v, u)               # scan this side
    prob_v = jnp.where(swap, u, v)               # binary-search this side
    ar = jnp.arange(m, dtype=jnp.int32)
    cnt = jnp.where(ar < m_real, deg[cand_v], 0)
    off_w = jnp.zeros((m + 1,), jnp.int32).at[1:].set(jnp.cumsum(cnt))
    end = jnp.minimum(off_w[m], start + size)    # last wedge row, exclusive
    blk = min(size, max(_RESOLVE_BLOCK, next_pow2(m)))
    # per edge: first candidate slot and the probed range
    c_first, p_lo, p_hi = Es[cand_v], Es[prob_v], Es[prob_v + 1]

    def block(state):
        b0, kept, e1, e2, e3, off = state
        e1w, e1c, intra, valid = _expand_segments(off_w, blk, m, b0)
        valid = valid & (b0 + jnp.arange(blk, dtype=jnp.int32) < end)
        cand = jnp.where(valid, c_first[e1c] + intra, 0)
        lo = jnp.where(valid, p_lo[e1c], 0)
        hi = jnp.where(valid, p_hi[e1c], 0)             # lo == hi: a miss
        hit, safe = probe(N, cand, lo, hi, iters=iters)
        hits = _prefix_sum(hit.astype(jnp.int32))
        dst = jnp.where(hit, kept + hits - 1, size)     # misses are dropped
        e1 = e1.at[dst].set(e1w, mode="drop")
        e2 = e2.at[dst].set(Eid[cand], mode="drop")
        e3 = e3.at[dst].set(Eid[safe], mode="drop")
        # each edge's kept rows start after this block's hits before its
        # first wedge row, summed over the blocks
        before = jnp.concatenate([jnp.zeros((1,), jnp.int32), hits])
        off = off + before[jnp.clip(off_w - b0, 0, blk)]
        return b0 + blk, kept + hits[-1], e1, e2, e3, off

    fill = jnp.full((size,), m, jnp.int32)
    state = (jnp.asarray(start, jnp.int32), jnp.int32(0), fill, fill, fill,
             jnp.zeros((m + 1,), jnp.int32))
    _, _, e1, e2, e3, off = jax.lax.while_loop(
        lambda st: st[0] < end, block, state)
    has = off[1:] > off[:-1]
    c_start = off[:-1] // chunk
    c_end = jnp.maximum(off[1:] - 1, 0) // chunk
    return e1, e2, e3, off, c_start, c_end, has


@functools.partial(jax.jit, static_argnames=("m", "size"))
def _build_support_table_dev(u, v, Es, Eo, m_real, *, m: int, size: int):
    """Device mirror of ``build_support_table`` at static padded ``size``."""
    return support_rows(u, v, Es, Eo, m_real, m=m, size=size)


@functools.partial(jax.jit, static_argnames=("m", "size", "chunk", "iters"))
def _build_peel_table_dev(u, v, Es, N, Eid, m_real, *, m: int, size: int,
                          chunk: int, iters: int):
    """Device ``resolve_peel_table`` (``peel_rows``) of a whole graph at
    static padded ``size``: the wedge rows, their one probe and the
    compaction of the hits run in this one jit.  Returns the
    ``core.pkt.PeelTables`` fields ``(e1, e2, e3, c_start, c_end, has)``
    and the rows kept (3T)."""
    e1, e2, e3, off, c_start, c_end, has = peel_rows(
        u, v, Es, N, Eid, m_real, m=m, size=size, chunk=chunk, iters=iters)
    return e1, e2, e3, c_start, c_end, has, off[m]


@functools.partial(jax.jit, static_argnames=("rows", "out"))
def _triangle_rows_dev(e1, e2, e3, *, rows: int, out: int):
    """Every triangle once, from a whole-graph peel table: the rows whose
    anchor ``e1`` is the lowest of the three edges.

    Reads the table's first ``rows`` rows (its kept rows lie there) and
    compacts the selected ones stably to the front of an ``(out, 3)``
    array (prefix sum plus a scatter, as ``peel_rows`` keeps its hits).
    Padding rows carry the sentinel in all three columns, so the strict
    comparisons drop them.  Returns the rows and how many were selected.
    """
    e1, e2, e3 = e1[:rows], e2[:rows], e3[:rows]
    keep = (e1 < e2) & (e1 < e3)
    pos = _prefix_sum(keep.astype(jnp.int32))
    dst = jnp.where(keep, pos - 1, out)                 # the rest are dropped
    tri = jnp.zeros((out, 3), jnp.int32).at[dst].set(
        jnp.stack([e1, e2, e3], axis=1), mode="drop")
    return tri, pos[-1]


@functools.partial(jax.jit, static_argnames=("m", "size", "iters"))
def _support_device_jit(u, v, Es, Eo, N, Eid, m_real, *, m: int, size: int,
                        iters: int):
    """Fused device program: build the oriented table *and* run the support
    executor in one jit — one compile on the open path, and XLA can fuse
    the row construction into the probe (the table is never materialized
    to HBM)."""
    e1, cand, lo, hi, _ = _build_support_table_dev(
        u, v, Es, Eo, m_real, m=m, size=size)
    return _support_jit(N, Eid, e1, cand, lo, hi, iters, m)


def _support_device(g: CSRGraph):
    """Support phase with the table built on device; returns a (m,) device
    array (no host round-trip — ``pkt`` feeds it straight to the peel) and
    the table's padded row count.

    Table construction and the probe run as one fused jit, so its cost is
    all support's (the peel-table build is the "tables" phase)."""
    size = support_table_size(g)
    if size == 0:
        return jnp.zeros((g.m,), jnp.int32), 0
    size_pad = next_pow2(size)
    _check_table_size(size_pad)
    dev = g.device_arrays()
    S = _support_device_jit(
        dev["El"][:, 0], dev["El"][:, 1], dev["Es"], dev["Eo"],
        dev["N"], dev["Eid"], jnp.int32(g.m), m=g.m, size=size_pad,
        iters=_search_iters(g, oriented=True))
    return S, size_pad


# ``ranged_searchsorted`` lives in kernels/wedge_common.py (shared with the
# table builders) and is re-exported here for its established call sites
# (core/pkt.py, core/pkt_dist.py, core/triangle_list.py, benchmarks).


def _search_iters(g: CSRGraph, *, oriented: bool = False) -> int:
    """Binary-search iteration bound = log2(max probe-range length).

    The support path probes only N⁺(u) ranges, whose length is bounded by
    the degeneracy after KCO relabeling — this is where the paper's
    ordering win lands in our adaptation (17 → ~6 iterations on skewed
    graphs). The peel path probes full adjacencies.

    The oriented bound is sized for the larger of the largest out-degree
    and the h-index of the degree sequence (an upper bound on the
    degeneracy): on a skewed graph the h-index sits above most out-degrees
    and hardly moves under edge updates, while the largest out-degree
    wanders across powers of two as a graph changes and would compile a
    new support program each time it does.  (Ties in coreness are broken
    by id, so an out-degree can pass the h-index; the bound then follows
    it.)"""
    d = g.dplus if oriented else g.degrees
    dmax = int(d.max(initial=1))
    if oriented:
        dmax = max(dmax, _h_index(g.degrees))
    return max(1, int(np.ceil(np.log2(dmax + 1))) + 1)


def _h_index(deg: np.ndarray) -> int:
    """The largest h with at least h entries of ``deg`` >= h (O(n))."""
    n = deg.shape[0]
    if n == 0:
        return 0
    at_least = np.cumsum(np.bincount(np.minimum(deg, n),
                                     minlength=n + 1)[::-1])[::-1]
    return int(np.flatnonzero(at_least >= np.arange(n + 1))[-1])


@functools.partial(jax.jit, static_argnames=("iters", "m"))
def _support_jit(N, Eid, e1, cand_slot, lo, hi, iters: int, m: int):
    hit, safe = probe(N, cand_slot, lo, hi, iters=iters)
    e2 = Eid[cand_slot]
    e3 = Eid[safe]
    inc = hit.astype(jnp.int32)
    S = jnp.zeros((m,), jnp.int32)
    S = S.at[e1].add(inc)
    S = S.at[jnp.where(hit, e2, 0)].add(inc)  # masked: inc==0 adds nothing
    S = S.at[jnp.where(hit, e3, 0)].add(inc)
    return S


def compute_support(g: CSRGraph, table: WedgeTable | None = None, *,
                    table_mode: str | None = None) -> np.ndarray:
    """Edge support (triangles per edge) via the AM4 adaptation. Returns (m,).

    ``table_mode`` selects where the wedge table is constructed
    (``TABLE_MODES``): "device" (the default when no prebuilt ``table`` is
    passed) runs the jitted XLA builder, "numpy" the original host builder.
    """
    if table_mode is None:
        table_mode = "numpy" if table is not None else "device"
    if table_mode not in TABLE_MODES:
        raise ValueError(
            f"table_mode must be one of {TABLE_MODES}, got {table_mode!r}")
    if g.m == 0:
        return np.zeros(0, np.int32)
    if table_mode == "device" and table is None:
        S, _ = _support_device(g)
        return np.asarray(S)
    if table is None:
        table = build_support_table(g)
    if table.size == 0:
        # triangle-free under the orientation (e.g. stars): nothing to probe
        return np.zeros(g.m, np.int32)
    S = _support_jit(
        jnp.asarray(g.N), jnp.asarray(g.Eid),
        jnp.asarray(table.e1), jnp.asarray(table.cand_slot),
        jnp.asarray(table.lo), jnp.asarray(table.hi),
        _search_iters(g, oriented=True), g.m,
    )
    return np.asarray(S)


def triangle_count(g: CSRGraph) -> int:
    """Total triangles = sum(S)/3."""
    S = compute_support(g)
    return int(S.sum()) // 3


# --- Ros (Algorithm 2) support computation: edge-based, unordered -----------
#
# For each edge (u,v) the FULL adjacencies are intersected (no orientation),
# so every triangle is counted once *per edge* (3x total work vs AM4 — the
# paper's Σ d(v)^2 vs Σ d⁺(v)^2 gap). Kept as the baseline for Table 2/3.

@functools.partial(jax.jit, static_argnames=("iters", "m"))
def _support_ros_jit(N, e1, cand_slot, lo, hi, iters: int, m: int):
    hit, _ = probe(N, cand_slot, lo, hi, iters=iters)
    S = jnp.zeros((m,), jnp.int32)
    S = S.at[e1].add(hit.astype(jnp.int32))
    return S


def compute_support_ros(g: CSRGraph, table: WedgeTable | None = None) -> np.ndarray:
    """Ros-style support: per-edge full intersection (work ∝ Σ d(v)^2)."""
    if g.m == 0:
        return np.zeros(0, np.int32)
    if table is None:
        table = build_peel_table(g)
    S = _support_ros_jit(
        jnp.asarray(g.N),
        jnp.asarray(table.e1), jnp.asarray(table.cand_slot),
        jnp.asarray(table.lo), jnp.asarray(table.hi),
        _search_iters(g), g.m,
    )
    return np.asarray(S)
