"""PKT — level-synchronous parallel truss decomposition (paper Algorithms 4+5).

JAX/TPU adaptation of the OpenMP original (see DESIGN.md §2 for the mapping):

  * SCAN            → dense masked compare over the support vector S
  * curr/next       → boolean frontier vectors (inCurr/processed); the "next"
                      buffer is recovered as  alive ∧ (S == l)  after update
  * atomicSub+clamp → masked per-wedge decrement contributions aggregated with
                      scatter-add, then  S ← max(S − dec, l)  (identical fixed
                      point, bitwise deterministic)
  * tie-break       → the paper's "lowest frontier edge id processes the
                      triangle" predicate evaluated vectorially per table row
  * dynamic sched.  → chunk-skipping: the flat peel table is cut into fixed
                      chunks; a sub-level only visits chunks overlapping
                      frontier edges' ranges (work-efficiency: each triangle's
                      rows are scanned O(1) times over the whole run)

The peel table holds resolved triangle rows ``(e1, e2, e3)`` (DESIGN.md
§10): the wedge probe that decides whether a row closes a triangle, and
which two edges close it, depends only on the graph, so the table builder
runs it once and keeps the hits; the peel gathers edge state at three
edge ids per row and never searches.

Two peel modes (``mode`` ∈ ``PEEL_MODES``), bitwise identical:
  mode="chunked" (default): work-efficient chunk-skipping while_loop.
  mode="dense":  every sub-level scans the whole peel table with frontier
                 masking — the naive SPMD port, kept as a benchmark foil.

The support phase has one executor, the flat XLA program of
``core.support`` (``_support_device_jit``); both peel modes over it give
bitwise-identical trussness (tests/test_parity_matrix.py asserts it).

The peel loop is written against *padded* edge state so the batched engine
(serve/truss_engine.py) can vmap it across many graphs of one size class:
slot ``m`` is the sentinel, and any edge slot marked processed in
``processed0`` with sentinel support in ``S_ext0`` is inert padding.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

from repro import spans
from repro.graphs.csr import CSRGraph, edge_keys
from repro.core import support as support_mod
from repro.kernels import wedge_common
from repro.testing.chaos import fault_point

#: support of processed / padding edge slots; a numpy scalar, so importing
#: this module initialises no JAX backend
_SENTINEL_S = np.int32(1 << 30)

PEEL_MODES = ("chunked", "dense")


class PeelTables(NamedTuple):
    """Device-resident static tables for the peel phase (padded to chunks).

    One row per (edge ``e1``, triangle through it): ``e2``/``e3`` are the
    triangle's two other edges (``support.PeelRows``).  Rows are grouped
    by ``e1``; padding rows carry the sentinel ``m`` in all three columns,
    which the peel never selects (slot ``m`` is processed, never in the
    frontier).  The padded length is that of the wedge table the rows were
    resolved from, which 3T never exceeds.
    """

    e1: jnp.ndarray         # (n_chunks*C,) int32 anchor edge, sentinel m
    e2: jnp.ndarray         # (n_chunks*C,) int32 closing edge, sentinel m
    e3: jnp.ndarray         # (n_chunks*C,) int32 closing edge, sentinel m
    c_start: jnp.ndarray    # (m,) int32   first chunk containing edge e
    c_end: jnp.ndarray      # (m,) int32   last chunk containing edge e (inclusive)
    has_entries: jnp.ndarray  # (m,) bool


class TriangleRows(NamedTuple):
    """Every triangle of a ``pkt`` graph once, taken from its whole-graph
    peel table (``pkt(..., triangles=True)``).

    The first ``count`` rows of ``rows`` are ``(e1, e2, e3)`` edge ids,
    ``e1`` the lowest; ``table_hits`` is the rows the table kept (3T).
    Device-built values stay on the device until ``read``.
    """

    rows: object            # (out, 3) int32, device or host
    count: object           # rows selected, device or host scalar
    table_hits: object      # rows the peel table kept, device or host scalar

    def read(self) -> tuple[np.ndarray, int]:
        """The ``(T, 3)`` rows on the host, and ``table_hits``.

        Raises:
            RuntimeError: the rows are not a third of the table's, or did
                not fit ``rows``: the list would miss triangles.
        """
        rows, count, hits = jax.device_get(tuple(self))
        count, hits = int(count), int(hits)
        if 3 * count != hits or count > rows.shape[0]:
            raise RuntimeError(
                f"triangle rows disagree with their peel table: {count} "
                f"selected into {rows.shape[0]} slots, {hits} table rows")
        return np.asarray(rows[:count]), hits


@dataclasses.dataclass(frozen=True)
class PKTResult:
    """Full output of one ``pkt`` decomposition, with phase accounting."""

    trussness: np.ndarray   # (m,) int32, >= 2
    support: np.ndarray     # (m,) int32 initial support
    levels: int             # number of peel levels executed
    sublevels: int          # total sub-level iterations (paper's S)
    compactions: int = 0    # live-edge compactions performed (DESIGN.md §10)
    chunk_visits: int = 0   # peel chunk bodies run, over every sub-level
    #: phase wall-times {tables, support, peel, compact}: the host durations
    #: of the phase spans, populated only when ``pkt(..., phase_timings=True)``
    #: (each phase span then waits for its device work before it closes, so
    #: attribution is honest but adds barriers)
    phases: dict | None = None
    #: ``pkt(..., triangles=True)``: the triangles of ``g``, each once
    triangles: TriangleRows | None = None


#: ``pkt``'s phase spans, by the ``PKTResult.phases`` key each feeds
_PHASE_SPANS = {"pkt.support": "support", "pkt.tables": "tables",
                "pkt.peel_segment": "peel", "pkt.compact": "compact"}


def chunk_ranges(off: np.ndarray, chunk: int,
                 m_out: int | None = None) -> tuple[np.ndarray, np.ndarray,
                                                    np.ndarray]:
    """Per-edge chunk-range bookkeeping from a wedge-table offset array.

    Returns (has_entries, c_start, c_end), each of length ``m_out`` (edges
    beyond ``off``'s m are inert padding: no entries, range 0).  Shared by
    the single-graph tables and the batched engine so the two paths cannot
    drift.
    """
    m = off.shape[0] - 1
    m_out = m if m_out is None else m_out
    has = np.zeros(m_out, bool)
    c_start = np.zeros(m_out, np.int32)
    c_end = np.zeros(m_out, np.int32)
    if m == 0 or off[-1] == 0:
        # explicit early-exit: empty graph, or a table with no entries
        # (triangle-free orientation) — every edge has an empty chunk range
        return has, c_start, c_end
    has[:m] = off[1:] > off[:-1]
    c_start[:m] = off[:-1] // chunk
    c_end[:m] = np.maximum(off[1:] - 1, 0) // chunk
    return has, c_start, c_end


def _pad_tables(rows: support_mod.PeelRows, chunk: int, n_chunks: int,
                m_out: int) -> PeelTables:
    """Host rows padded to ``n_chunks * chunk`` with the sentinel ``m_out``
    (the edge space, at least the rows' m) in all three columns."""
    size = n_chunks * chunk
    e1, e2, e3 = (jnp.asarray(wedge_common.pad1(col, size, m_out))
                  for col in (rows.e1, rows.e2, rows.e3))
    has, c_start, c_end = chunk_ranges(rows.off, chunk, m_out=m_out)
    return PeelTables(e1=e1, e2=e2, e3=e3, c_start=jnp.asarray(c_start),
                      c_end=jnp.asarray(c_end), has_entries=jnp.asarray(has))


def prepare_peel(rows: support_mod.PeelRows, m: int,
                 chunk: int | None) -> tuple[PeelTables, int, int]:
    """Clamp ``chunk`` to the wedge table, pad, and derive ``n_chunks``.

    The single place where the chunk size is sanitized (the layout policy
    itself lives in ``kernels.wedge_common.chunk_layout``): a user-passed
    chunk larger than the (padded) table, zero, or negative is clamped so
    that ``n_chunks >= 1`` always holds — tiny graphs (m <= 2, a handful of
    wedge entries) used to be able to reach ``n_chunks == 0`` through the
    old call-site-local ``min(chunk, size)`` dance.

    The layout follows the wedge count ``rows.wedges``, as the device
    builder's does, so both table modes peel the same chunks.  Rows
    resolved from no wedges at all — the empty graph (m == 0), or edges
    with no common-neighbour candidates — take an explicit early-exit
    rather than relying on the clamping arithmetic: one all-padding chunk
    of size 1, every edge marked entry-less.
    """
    if rows.wedges == 0:
        return _empty_peel_tables(m), 1, 1
    chunk, n_chunks = wedge_common.chunk_layout(rows.wedges, chunk)
    tabs = _pad_tables(rows, chunk, n_chunks, m)
    assert tabs.e1.shape[0] == n_chunks * chunk
    return tabs, chunk, n_chunks


def _empty_peel_tables(m: int) -> PeelTables:
    """One all-padding chunk of size 1; every edge entry-less."""
    return PeelTables(
        e1=jnp.full((1,), m, jnp.int32),
        e2=jnp.full((1,), m, jnp.int32),
        e3=jnp.full((1,), m, jnp.int32),
        c_start=jnp.zeros((m,), jnp.int32),
        c_end=jnp.zeros((m,), jnp.int32),
        has_entries=jnp.zeros((m,), jnp.bool_),
    )


def prepare_peel_device(g: CSRGraph, chunk: int | None, *,
                        m_out: int | None = None,
                        m_real: int | None = None
                        ) -> tuple[PeelTables, int, int, object]:
    """Device-built peel tables for ``g``, pow2-padded (DESIGN.md §10).

    The device counterpart of ``resolve_peel_table`` + ``prepare_peel``:
    the wedge-table entry count is bounded on host (O(m)) and sets the
    table's pow2 padded size and chunk; one jit
    (``support._build_peel_table_dev``) expands the wedge rows, probes each
    once, keeps the hits and computes the chunk-range metadata.
    ``m_out`` (default ``g.m``) sizes the edge state space (compacted
    callers pad it to a pow2 bucket, and this pads ``u``, ``v``, ``Es``,
    ``N`` and ``Eid`` to it); ``m_real`` marks how many leading edge
    slots are real.  A padded bucket bounds the probe by its pow2 vertex
    dimension, which is already a shape of the builder, so a graph whose
    largest degree wanders under edge updates compiles no new builder.
    Returns ``(tabs, chunk, n_chunks, hits)`` with ``hits`` the rows kept
    (3T) as a device scalar, read back with the first peel segment.
    """
    m_out = g.m if m_out is None else m_out
    m_real = g.m if m_real is None else m_real
    size = support_mod.peel_table_size(g)
    if size == 0:
        return _empty_peel_tables(m_out), 1, 1, 0
    size_pad = wedge_common.next_pow2(size)
    support_mod._check_table_size(size_pad)
    chunk_eff = wedge_common.pow2_chunk(size_pad, chunk, size=size)
    n_chunks = size_pad // chunk_eff
    if m_out != g.m:
        # pow2 bucket (batched/compacted callers): pad the edge *and* vertex
        # dimensions so the builder's compiled shapes are bucket-keyed.
        # The padded copies are uploaded directly — no device_arrays() cache
        # for a throwaway compaction subgraph.
        u = jnp.asarray(wedge_common.pad1(g.El[:, 0], m_out, 0))
        v = jnp.asarray(wedge_common.pad1(g.El[:, 1], m_out, 0))
        n_es = wedge_common.next_pow2(g.n + 1)
        Es = jnp.asarray(wedge_common.pad1(g.Es, n_es, 2 * g.m))
        N = jnp.asarray(wedge_common.pad1(g.N, 2 * m_out, wedge_common.PAD_N))
        Eid = jnp.asarray(wedge_common.pad1(g.Eid, 2 * m_out, m_out))
        # a degree is below n_es, so log2(n_es) + 1 >= _search_iters(g)
        iters = n_es.bit_length()
    else:
        dev = g.device_arrays()
        u, v, Es = dev["El"][:, 0], dev["El"][:, 1], dev["Es"]
        N, Eid = dev["N"], dev["Eid"]
        iters = support_mod._search_iters(g)
    *cols, hits = support_mod._build_peel_table_dev(
        u, v, Es, N, Eid, jnp.int32(m_real), m=m_out, size=size_pad,
        chunk=chunk_eff, iters=iters)
    return PeelTables(*cols), chunk_eff, n_chunks, hits


def _active_chunk_mask(inCurr, tabs: PeelTables, m: int, n_chunks: int):
    """Chunks overlapping any frontier edge's wedge-entry range (bool mask)."""
    curr_edges = inCurr[:m] & tabs.has_entries
    delta = jnp.zeros((n_chunks + 1,), jnp.int32)
    delta = delta.at[jnp.where(curr_edges, tabs.c_start, n_chunks)].add(
        curr_edges.astype(jnp.int32))
    delta = delta.at[jnp.where(curr_edges, tabs.c_end + 1, n_chunks)].add(
        -curr_edges.astype(jnp.int32))
    return jnp.cumsum(delta[:n_chunks]) > 0


def _peel_loop(S_ext0, processed0, tabs: PeelTables, *, m: int, chunk: int,
               n_chunks: int, mode: str, pinned=None, stop_live=None,
               reduce=None):
    """Full level/sub-level peel over extended (m+1,) edge state.

    ``S_ext0``/``processed0`` define which slots are live: slot m must be the
    processed sentinel, and callers may pre-mark extra padding slots as
    processed (batched engine).  Returns (S_ext, processed, levels,
    sublevels, chunk_visits) — the full extended state, so segmented
    callers can resume, and the chunk bodies the peel ran: every chunk per
    sub-level in dense mode, the frontier's active chunks otherwise.

    ``pinned`` (optional (m+1,) bool) marks *schedule* edges: they enter the
    frontier and process their triangles at exactly their initial support
    level, but never receive decrements themselves — the incremental layer
    (core/truss_inc.py) uses this to replay the known death level of
    boundary edges whose trussness is already final.  Slot m must be False.

    ``stop_live`` (optional dynamic scalar) is the live-edge compaction
    early-exit (DESIGN.md §10): the level loop returns once the number of
    unprocessed edges drops to or below it — always at a level boundary, so
    the caller can gather survivors into a compacted edge space and re-enter
    with bitwise-identical continuation.

    ``reduce`` (optional) combines each sub-level's decrement vector before
    it is applied — the distributed path (core/pkt_dist.py) passes a
    ``psum`` over the mesh, each device having folded only its own table
    shard.
    """
    def chunk_contrib(c, dec, S_ext, processed, inCurr, l):
        """Decrement contributions from one chunk of the peel table."""
        base = c * chunk
        e1 = jax.lax.dynamic_slice(tabs.e1, (base,), (chunk,))
        e2 = jax.lax.dynamic_slice(tabs.e2, (base,), (chunk,))
        e3 = jax.lax.dynamic_slice(tabs.e3, (base,), (chunk,))
        in1 = inCurr[e1]   # sentinel rows: inCurr[m] is False
        valid = in1 & ~processed[e2] & ~processed[e3]
        s2 = S_ext[e2]
        s3 = S_ext[e3]
        in2 = inCurr[e2]
        in3 = inCurr[e3]
        dec2 = valid & (s2 > l) & ((~in3) | (e1 < e3))
        dec3 = valid & (s3 > l) & ((~in2) | (e1 < e2))
        if pinned is not None:
            dec2 = dec2 & ~pinned[e2]
            dec3 = dec3 & ~pinned[e3]
        dec = dec.at[jnp.where(dec2, e2, m)].add(dec2.astype(jnp.int32))
        dec = dec.at[jnp.where(dec3, e3, m)].add(dec3.astype(jnp.int32))
        return dec

    def sublevel(S_ext, processed, inCurr, l):
        """One ProcessSubLevel: aggregate decrements, apply, mark processed.

        Also returns the number of chunk bodies it ran."""
        dec0 = jnp.zeros((m + 1,), jnp.int32)
        if mode == "dense":
            def body(c, dec):
                return chunk_contrib(c, dec, S_ext, processed, inCurr, l)
            dec = jax.lax.fori_loop(0, n_chunks, body, dec0)
            visits = jnp.int32(n_chunks)
        else:  # chunked: visit only chunks overlapping the frontier
            active = _active_chunk_mask(inCurr, tabs, m, n_chunks)
            n_active = jnp.sum(active.astype(jnp.int32))
            (ids,) = jnp.nonzero(active, size=n_chunks, fill_value=n_chunks - 1)

            def body(i, dec):
                return chunk_contrib(ids[i], dec, S_ext, processed, inCurr, l)

            def cond(state):
                i, _ = state
                return i < n_active

            def wbody(state):
                i, dec = state
                return i + 1, body(i, dec)

            _, dec = jax.lax.while_loop(cond, wbody, (jnp.int32(0), dec0))
            visits = n_active
        if reduce is not None:
            dec = reduce(dec)

        S_ext = jnp.where(
            (~processed) & (~inCurr) & (dec > 0),
            jnp.maximum(S_ext - dec, l), S_ext)
        processed = processed | inCurr
        inCurr = (~processed) & (S_ext == l)
        inCurr = inCurr.at[m].set(False)
        return S_ext, processed, inCurr, visits

    def level_body(state):
        S_ext, processed, l_done, todo, levels, subs, visits = state
        alive_S = jnp.where(processed, _SENTINEL_S, S_ext)
        l = jnp.min(alive_S)  # skip-ahead to next populated level
        inCurr = (~processed) & (S_ext == l)
        inCurr = inCurr.at[m].set(False)

        def sub_cond(st):
            return jnp.any(st[2])

        def sub_body(st):
            S_ext, processed, inC, subs_, visits_ = st
            S_ext, processed, inC, v = sublevel(S_ext, processed, inC, l)
            return S_ext, processed, inC, subs_ + 1, visits_ + v

        S_ext, processed, _, subs, visits = jax.lax.while_loop(
            sub_cond, sub_body, (S_ext, processed, inCurr, subs, visits))
        todo = (m + 1) - jnp.sum(processed.astype(jnp.int32))
        return S_ext, processed, l, todo, levels + 1, subs, visits

    stop = jnp.int32(0) if stop_live is None else stop_live

    def level_cond(state):
        return state[3] > stop

    todo0 = (m + 1) - jnp.sum(processed0.astype(jnp.int32))
    state = (S_ext0, processed0, jnp.int32(0), todo0, jnp.int32(0),
             jnp.int32(0), jnp.int32(0))
    S_ext, processed, _, _, levels, subs, visits = jax.lax.while_loop(
        level_cond, level_body, state)
    return S_ext, processed, levels, subs, visits


@functools.partial(
    jax.jit,
    static_argnames=("m", "chunk", "n_chunks", "mode"),
    donate_argnums=(0,),  # S0: consumed into the peel state, never reread
)
def _pkt_peel_jit(S0, tabs: PeelTables, *, m: int, chunk: int,
                  n_chunks: int, mode: str = "chunked"):
    """Runs the full level/sub-level peel; returns (S_final, levels, sublevels)."""
    # extended edge state: slot m is a sentinel (processed, never in frontier)
    S_ext0 = jnp.concatenate([S0.astype(jnp.int32), jnp.full((1,), _SENTINEL_S)])
    processed0 = jnp.zeros((m + 1,), jnp.bool_).at[m].set(True)
    S_ext, _, levels, subs, _ = _peel_loop(
        S_ext0, processed0, tabs, m=m, chunk=chunk, n_chunks=n_chunks,
        mode=mode)
    return S_ext[:m], levels, subs


@functools.partial(
    jax.jit,
    static_argnames=("m", "chunk", "n_chunks", "mode"),
    donate_argnums=(0, 1),  # peel-state buffers: never reread by the caller
)
def _peel_segment_jit(S_ext0, processed0, stop_live, pinned,
                      tabs: PeelTables, *, m: int, chunk: int, n_chunks: int,
                      mode: str):
    """One compaction segment: peel until done or ≤ ``stop_live`` edges live.

    Returns (S_ext, processed, counts): ``counts`` stacks levels,
    sub-levels and chunk visits into one int32 vector, so
    ``_segmented_peel`` reads them back in one transfer.  The peel-state
    buffers are donated — each segment consumes its inputs, so peak device
    memory holds one state generation, not two.
    """
    S_ext, processed, levels, subs, visits = _peel_loop(
        S_ext0, processed0, tabs, m=m, chunk=chunk, n_chunks=n_chunks,
        mode=mode, pinned=pinned, stop_live=stop_live)
    return S_ext, processed, jnp.stack([levels, subs, visits])


# --- live-edge compaction (DESIGN.md §10) -----------------------------------
#
# Wang & Cheng's improved in-memory algorithm wins by *shrinking the graph*
# as edges are peeled; the level-synchronous port above instead scans a
# fixed-size table whose entries go dead as their edges process.  The driver
# below restores the shrink: segments of the peel run under a live-edge
# early-exit, and between segments the surviving edges are gathered into a
# compacted edge space — vertices rank-relabeled, CSR rebuilt, the peel
# table rebuilt (on device) over only live edges at the next pow2 size
# class, and the (S, processed, pinned) state remapped.  The relabeling is
# order-preserving, so the paper's lowest-edge-id tie-break picks the same
# winners and the continuation is bitwise identical — levels, sub-levels
# and the fixed point all match the uncompacted run; only dead wedge
# entries are dropped.  pow2 bucketing of (m, n, table, chunk) bounds
# recompiles exactly like the batched engine's size classes.

#: default compaction policy: compact when the live fraction drops below
#: ``_COMPACT_FRAC``, but never bother below ``_COMPACT_MIN`` live edges
#: (table rebuild + dispatch overhead beats the dead-scan savings there)
_COMPACT_FRAC = 0.25
_COMPACT_MIN = 1 << 11
_MIN_M_PAD = 8


def _make_subproblem(El_rows: np.ndarray, ids: np.ndarray,
                     S_rows: np.ndarray, pinned_rows: np.ndarray | None, *,
                     chunk_req: int | None, table_mode: str) -> dict:
    """Compact ``El_rows`` (live edges, ascending original order) into a
    fresh pow2-bucketed peel problem.

    ``ids`` maps each row to the caller's output slot; ``S_rows`` carries
    the live supports (the continuation state), ``pinned_rows`` the pinned
    schedule marks (or None).  Vertex ids are rank-relabeled —
    order-preserving, so ``build_csr``'s lexicographic edge ids keep the
    input row order and the peel tie-break is unchanged.
    """
    from repro.graphs.csr import build_csr

    m_sub = El_rows.shape[0]
    verts = np.unique(El_rows)
    E_sub = np.searchsorted(verts, El_rows).astype(np.int64)
    g_sub = build_csr(E_sub, verts.shape[0])
    m_pad = max(_MIN_M_PAD, wedge_common.next_pow2(m_sub))
    wedges = support_mod.peel_table_size(g_sub)

    if table_mode == "device":
        tabs, chunk_eff, n_chunks, hits = prepare_peel_device(
            g_sub, chunk_req, m_out=m_pad, m_real=m_sub)
    else:
        rows = support_mod.resolve_peel_table(g_sub)
        hits = rows.size
        if wedges == 0:
            tabs, chunk_eff, n_chunks = _empty_peel_tables(m_pad), 1, 1
        else:
            size_pad = wedge_common.next_pow2(wedges)
            chunk_eff = wedge_common.pow2_chunk(size_pad, chunk_req,
                                                size=wedges)
            n_chunks = size_pad // chunk_eff
            tabs = _pad_tables(rows, chunk_eff, n_chunks, m_pad)

    S_ext0 = np.full(m_pad + 1, int(_SENTINEL_S), np.int32)
    S_ext0[:m_sub] = S_rows
    processed0 = np.ones(m_pad + 1, bool)
    processed0[:m_sub] = False
    ids_pad = np.full(m_pad, -1, np.int64)
    ids_pad[:m_sub] = ids
    pinned = None
    pinned_np = None
    if pinned_rows is not None and pinned_rows.any():
        pinned_np = np.zeros(m_pad + 1, bool)
        pinned_np[:m_sub] = pinned_rows
        pinned = jnp.asarray(pinned_np)
    return dict(
        tabs=tabs, chunk=chunk_eff, n_chunks=n_chunks, table_rows=wedges,
        table_hits=hits, m=m_pad, live=m_sub,
        S_ext0=jnp.asarray(S_ext0), processed0=jnp.asarray(processed0),
        pinned=pinned, pinned_np=pinned_np, El=g_sub.El, ids=ids_pad)


def _segmented_peel(problem: dict, out: np.ndarray, *, mode: str,
                    table_mode: str,
                    compact_frac: float | None, compact_min: int,
                    chunk_req: int | None) -> tuple[int, int, int, int]:
    """Run ``problem`` to the fixed point, compacting between segments.

    Each segment peels until ≤ ``compact_frac · m`` edges remain live (or to
    completion when compaction is off / the problem is below
    ``compact_min``); finished edges scatter their final S into ``out`` (at
    ``problem['ids']`` slots) and survivors are re-bucketed via
    ``_make_subproblem``.  Each segment, its readback included, is a
    ``repro.pkt.peel_segment`` span carrying its levels, sub-levels and
    chunk visits, and its table's wedge rows (``table_rows``) and the rows
    the build kept (``table_hits``, 3T of the segment's graph, read back
    with the segment's state); each compaction is a ``repro.pkt.compact``
    span.
    Returns (levels, sublevels, chunk_visits, compactions).
    """
    counts = np.zeros(3, np.int64)
    compactions = 0
    while True:
        m = problem["m"]
        n_live = problem["live"]
        live_target = 0
        if compact_frac and n_live > compact_min:
            # clamp below the live count so every segment must retire at
            # least one level before the driver considers compacting again
            live_target = min(int(compact_frac * m), n_live - 1)
        with spans.span("pkt.peel_segment", segment=compactions, m_pad=m,
                        live=n_live, chunk=problem["chunk"],
                        n_chunks=problem["n_chunks"],
                        table_rows=problem["table_rows"]) as sp:
            S_ext, processed, seg = _peel_segment_jit(
                problem["S_ext0"], problem["processed0"],
                jnp.int32(live_target), problem["pinned"], problem["tabs"],
                m=m, chunk=problem["chunk"], n_chunks=problem["n_chunks"],
                mode=mode)
            S_np, proc_np, seg, hits = jax.device_get(
                (S_ext, processed, seg, problem["table_hits"]))
            sp.set(levels=int(seg[0]), sublevels=int(seg[1]),
                   chunk_visits=int(seg[2]), table_hits=int(hits))
        counts += seg
        S_np, proc_np = S_np[:m], proc_np[:m]
        ids = problem["ids"]
        live = ~proc_np
        dead = proc_np & (ids >= 0)
        out[ids[dead]] = S_np[dead]
        if not live.any():
            return (*(int(c) for c in counts), compactions)
        # ≤ live_target survivors: gather them into a compacted edge space
        compactions += 1
        live_idx = np.nonzero(live)[0]
        pin_np = problem["pinned_np"]
        with spans.span("pkt.compact", live=live_idx.shape[0]) as sp:
            problem = _make_subproblem(
                problem["El"][live_idx], ids[live_idx], S_np[live_idx],
                None if pin_np is None else pin_np[:m][live_idx],
                chunk_req=chunk_req, table_mode=table_mode)
            sp.set(m_pad=problem["m"])
        assert problem["live"] < n_live  # compaction must strictly shrink


def peel_live_subset(El: np.ndarray, live_ids: np.ndarray,
                     S0_live: np.ndarray,
                     pinned_live: np.ndarray | None = None, *,
                     chunk: int | None = None, mode: str = "chunked",
                     table_mode: str = "device",
                     compact_frac: float | None = _COMPACT_FRAC,
                     compact_min: int = _COMPACT_MIN) -> np.ndarray:
    """Peel a subset of a graph's edges in a compacted edge space.

    The compaction machinery as a standalone entry: ``live_ids`` (sorted
    edge ids into ``El``) are gathered into a compact pow2-bucketed
    subproblem — only their induced subgraph is materialized, so work is
    bounded by the subset, not the host graph — and peeled to the fixed
    point (with further compaction as the subset shrinks).  ``S0_live``
    seeds the per-edge state; ``pinned_live`` marks schedule edges exactly
    as in ``_peel_loop``.  Returns the final S per ``live_ids`` row.  Used
    by ``core/truss_inc.py``'s masked re-peel regions.
    """
    live_ids = np.asarray(live_ids, dtype=np.int64)
    k = live_ids.shape[0]
    if k == 0:
        return np.zeros(0, np.int32)
    if k > 1 and not (np.diff(live_ids) > 0).all():
        # ascending ids are what make the compacted relabeling
        # order-preserving — the tie-break replay is silently wrong otherwise
        raise ValueError("live_ids must be strictly increasing edge ids")
    out = np.zeros(k, np.int32)
    problem = _make_subproblem(
        np.asarray(El)[live_ids], np.arange(k, dtype=np.int64),
        np.asarray(S0_live, dtype=np.int32),
        None if pinned_live is None else np.asarray(pinned_live, bool),
        chunk_req=chunk, table_mode=table_mode)
    _segmented_peel(problem, out, mode=mode, table_mode=table_mode,
                    compact_frac=compact_frac, compact_min=compact_min,
                    chunk_req=chunk)
    return out


def pkt(g: CSRGraph, *, chunk: int | None = None, mode: str = "chunked",
        table_mode: str | None = None,
        support_table: support_mod.WedgeTable | None = None,
        peel_table: support_mod.WedgeTable | None = None,
        compact_frac: float | None = _COMPACT_FRAC,
        compact_min: int = _COMPACT_MIN,
        phase_timings: bool = False, triangles: bool = False) -> PKTResult:
    """Full PKT truss decomposition of one CSR graph.

    Both peel modes and both table modes produce bitwise-identical
    trussness (``tests/test_parity_matrix.py``).

    Args:
        g: the graph as a :class:`~repro.graphs.csr.CSRGraph`.
        chunk: wedge-table chunk size (pow2; ``None`` derives it from the
            table size, see ``kernels.wedge_common.auto_chunk``).
        mode: peel executor — one of ``PEEL_MODES`` ("chunked", "dense").
        table_mode: where the wedge tables are built
            (``support.TABLE_MODES``): "device" — the default, unless
            prebuilt host tables are passed — constructs them as jitted XLA
            programs over the (cached) device CSR arrays, so no table bytes
            cross the host boundary; "numpy" is the original host builder,
            kept as the parity oracle.
        support_table: optional prebuilt host support table (implies
            ``table_mode="numpy"`` unless overridden).
        peel_table: optional prebuilt host peel wedge table (same
            implication); it is resolved to triangle rows on the host.
        compact_frac: live-edge compaction threshold (DESIGN.md §10): once
            a peel segment leaves fewer than ``compact_frac · m`` edges
            live (and more than ``compact_min``), survivors are gathered
            into a compacted pow2-bucketed subproblem and peeling re-enters
            there.  ``None`` disables compaction; results are bitwise
            identical either way.
        compact_min: minimum live-edge count for compaction to trigger.
        phase_timings: populate ``PKTResult.phases`` with a
            {tables, support, peel, compact} wall-time split, read from
            the phase spans (``repro.spans``); adds a sync barrier at the
            end of each phase.
        triangles: also return every triangle of ``g`` once
            (``PKTResult.triangles``), selected from the whole-graph peel
            table this call builds anyway; the device selection is one
            small jit dispatched before the peel, read back by the caller.

    Returns:
        :class:`PKTResult` — per-edge trussness (support + 2, aligned to
        ``g.El`` rows), initial support, and the level, sub-level,
        chunk-visit and compaction counters; with ``triangles``, the
        triangle rows.

    Raises:
        ValueError: unknown ``mode`` / ``table_mode``.
    """
    if mode not in PEEL_MODES:
        raise ValueError(f"mode must be one of {PEEL_MODES}, got {mode!r}")
    if table_mode is None:
        table_mode = ("numpy" if (support_table is not None
                                  or peel_table is not None) else "device")
    if table_mode not in support_mod.TABLE_MODES:
        raise ValueError(f"table_mode must be one of "
                         f"{support_mod.TABLE_MODES}, got {table_mode!r}")
    if g.m == 0:
        return PKTResult(np.zeros(0, np.int32), np.zeros(0, np.int32), 0, 0,
                         phases=(dict.fromkeys(_PHASE_SPANS.values(), 0.0)
                                 if phase_timings else None),
                         triangles=(TriangleRows(np.zeros((0, 3), np.int32),
                                                 0, 0)
                                    if triangles else None))

    with spans.span("pkt", m=g.m) as root:
        # ---- support phase -------------------------------------------------
        fault_point("support", rung=table_mode)
        if table_mode == "device" and support_table is None:
            with spans.span("pkt.support") as sp:
                S0_dev, rows = support_mod._support_device(g)
                sp.set(padded_rows=rows)
                S0 = np.asarray(S0_dev)   # the readback waits for the device
        else:
            with spans.span("pkt.tables", table="support") as sp:
                stab = (support_table if support_table is not None
                        else support_mod.build_support_table(g))
                sp.set(rows=stab.size)
            with spans.span("pkt.support", rows=stab.size):
                S0 = support_mod.compute_support(g, stab)
            S0_dev = jnp.asarray(S0)

        # ---- peel tables ---------------------------------------------------
        tri = None
        with spans.span("pkt.tables", table="peel") as sp:
            if table_mode == "device" and peel_table is None:
                tabs, chunk_eff, n_chunks, hits = prepare_peel_device(g, chunk)
                wedges = support_mod.peel_table_size(g)
                if triangles:
                    tri = _select_triangles(tabs, hits, int(S0.sum()) // 3)
            else:
                rows = support_mod.resolve_peel_table(g, peel_table)
                tabs, chunk_eff, n_chunks = prepare_peel(rows, g.m, chunk)
                hits, wedges = rows.size, rows.wedges
                sp.set(rows=rows.size)
                if triangles:
                    keep = (rows.e1 < rows.e2) & (rows.e1 < rows.e3)
                    tri = TriangleRows(
                        np.stack([rows.e1[keep], rows.e2[keep],
                                  rows.e3[keep]], axis=1),
                        int(keep.sum()), hits)
            sp.set(padded_rows=chunk_eff * n_chunks, chunk=chunk_eff,
                   n_chunks=n_chunks)
            if phase_timings:
                tabs.e1.block_until_ready()

        # ---- segmented peel with live-edge compaction ----------------------
        m = g.m
        S_ext0 = jnp.concatenate(
            [S0_dev.astype(jnp.int32), jnp.full((1,), _SENTINEL_S)])
        processed0 = jnp.zeros((m + 1,), jnp.bool_).at[m].set(True)
        problem = dict(
            tabs=tabs, chunk=chunk_eff, n_chunks=n_chunks, table_rows=wedges,
            table_hits=hits, m=m, live=m, S_ext0=S_ext0,
            processed0=processed0, pinned=None, pinned_np=None, El=g.El,
            ids=np.arange(m, dtype=np.int64))
        S_out = np.zeros(m, np.int32)
        levels, subs, visits, compactions = _segmented_peel(
            problem, S_out, mode=mode, table_mode=table_mode,
            compact_frac=compact_frac, compact_min=compact_min,
            chunk_req=chunk)
        return PKTResult(
            trussness=S_out.astype(np.int32) + 2,
            support=S0,
            levels=levels,
            sublevels=subs,
            compactions=compactions,
            chunk_visits=visits,
            phases=(spans.seconds_by_name(root, _PHASE_SPANS)
                    if phase_timings else None),
            triangles=tri,
        )


def _select_triangles(tabs: PeelTables, hits, n_tri: int) -> TriangleRows:
    """Dispatch the selection of every triangle once from a whole-graph
    device peel table (``support._triangle_rows_dev``).

    ``n_tri`` (a third of the support's sum) sizes it: the selection
    reads the table's first ``next_pow2(3 n_tri)`` rows, where the build
    compacted its 3T kept rows, into ``next_pow2(n_tri)`` slots.  Both
    are pow2 classes of the graph, so a graph that drifts under updates
    seldom compiles a new one; ``TriangleRows.read`` checks the count.
    """
    rows = min(wedge_common.next_pow2(max(1, 3 * n_tri)), tabs.e1.shape[0])
    sel, count = support_mod._triangle_rows_dev(
        tabs.e1, tabs.e2, tabs.e3, rows=rows,
        out=wedge_common.next_pow2(max(1, n_tri)))
    return TriangleRows(sel, count, hits)


def align_to_input(trussness: np.ndarray, g: CSRGraph,
                   edges: np.ndarray | None, n: int, *,
                   keys: np.ndarray | None = None) -> np.ndarray:
    """Map per-``g.El``-row trussness back to the caller's edge order.

    ``edges`` must be the canonical (u<v) edge array ``g`` was built from
    (possibly in a different row order); ``g.El`` rows are lexicographically
    sorted, so each input edge is located by key search.  Callers that
    already hold per-row keys (``u*n + v`` in g's id space) may pass ``keys``
    instead of ``edges``.

    Every requested edge must actually be present in ``g.El``: a missing key
    raises a descriptive ValueError (``np.searchsorted`` alone would silently
    return the *insertion point* — a neighboring edge's trussness — or an
    out-of-range index when the key sorts past the end of the table).
    """
    key_g = edge_keys(g.El[:, 0], g.El[:, 1], n)
    if keys is None:
        keys = edge_keys(edges[:, 0], edges[:, 1], n)
    keys = np.asarray(keys, dtype=np.int64)
    if key_g.shape[0] == 0:
        if keys.shape[0] == 0:
            return np.zeros(0, np.int64)
        raise ValueError(
            f"cannot align {keys.shape[0]} edge(s) to an empty graph")
    pos = np.searchsorted(key_g, keys)
    safe = np.minimum(pos, key_g.shape[0] - 1)
    bad = (pos >= key_g.shape[0]) | (key_g[safe] != keys)
    if bad.any():
        k = int(keys[bad][0])
        raise ValueError(
            f"{int(bad.sum())} edge(s) not present in the graph's edge list; "
            f"first missing: ({k // n}, {k % n})")
    return trussness[pos].astype(np.int64)


def truss_pkt(edges: np.ndarray, *, reorder: bool = True,
              chunk: int | None = None, mode: str = "chunked",
              table_mode: str | None = None,
              compact_frac: float | None = _COMPACT_FRAC,
              compact_min: int = _COMPACT_MIN) -> np.ndarray:
    """Convenience entry: undirected edges → trussness aligned to input order.

    ``edges`` is any (k, 2) integer array: endpoint order is free and
    duplicate rows are allowed — rows are canonicalized and deduped exactly
    like ``TrussEngine.submit`` before decomposition, and the result is
    mapped back so ``out[i]`` is the trussness of ``edges[i]`` whatever its
    form.  Self-loops, negative vertex ids, and ids beyond the int32 CSR /
    int64 key-packing bounds are rejected with a clear error (they used to
    corrupt the decomposition silently).

    With ``reorder`` (the paper's preprocessing) vertices are relabeled by
    increasing coreness before decomposition; results are mapped back.
    """
    from repro.graphs.csr import (build_csr, canonical_edges_with_rows,
                                  degeneracy_order, edge_keys, relabel)

    with spans.span("truss_pkt"):
        with spans.span("truss_pkt.prep") as sp:
            E, lo, hi, n = canonical_edges_with_rows(edges)
            if E.size == 0:
                return np.zeros(0, np.int64)
            if reorder:
                perm = degeneracy_order(E, n)
                r_edges = relabel(E, perm)
                rl, rh = perm[lo], perm[hi]
                row_keys = edge_keys(np.minimum(rl, rh), np.maximum(rl, rh), n)
            else:
                r_edges = E
                row_keys = edge_keys(lo, hi, n)
            g = build_csr(r_edges, n)
            sp.set(n=n, m=g.m)
        res = pkt(g, chunk=chunk, mode=mode, table_mode=table_mode,
                  compact_frac=compact_frac, compact_min=compact_min)
        with spans.span("truss_pkt.align"):
            return align_to_input(res.trussness, g, None, n, keys=row_keys)
