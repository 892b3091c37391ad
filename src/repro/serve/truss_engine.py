"""Batched multi-graph truss engine — many small graphs through one compile.

The serving story for truss decomposition is the opposite of the paper's
single-giant-graph benchmark: heavy traffic means a *stream* of modest graphs
(per-user ego nets, transaction neighborhoods, rolling windows) where XLA
compile time and per-dispatch overhead dominate if each graph is decomposed
alone. This engine amortizes both:

  * **Bucketing** — every submission is preprocessed on host (canonicalize,
    optional k-core reorder, CSR build) and assigned to a *size class*: all
    dimensions padded up to powers of two —
    ``(m_pad, sup_pad, peel_pad, chunk, n_pad)``.  Graphs in one class share
    one compiled executable; the pow2 policy bounds the number of distinct
    compiles to O(log m · log wedges) over any workload.  With the default
    ``table_mode="device"`` the wedge tables never exist on host: their
    entry counts are bounded by an O(m) host pass, the *CSR arrays alone*
    are shipped (``CSROperand``), and both tables are built by the vmapped
    device builders inside the batched jit (DESIGN.md §10);
    ``table_mode="numpy"`` keeps the original host-built table operands.
  * **Batching** — a bucket is decomposed by a single ``jax.vmap`` of the
    support + peel pipeline from ``core/pkt.py`` over the stacked, padded
    operands.  Padding edges are pre-marked processed with sentinel support,
    so they are inert in the level loop; padded support-table rows carry
    empty probe ranges (lo == hi) and the anchor sentinel, so they never
    hit, and padded peel-table rows carry the edge sentinel in all three
    columns.
  * **Order-aligned results** — ``submit`` returns a ticket; results are
    delivered aligned to each submission's own edge-row order regardless of
    bucket membership or flush timing.

Usage:

    eng = TrussEngine(mode="chunked")
    t1 = eng.submit(edges_a)          # queued
    t2 = eng.submit(edges_b)          # queued (maybe same bucket)
    trussness_b = eng.result(t2)      # flushes pending work once
    trussness_a = eng.result(t1)      # already computed

``mode`` selects the peel executor exactly as in ``core.pkt.pkt``.
Submissions larger than ``max_edges`` canonical
edges are rejected at ``submit`` time with a clear error (the padded
operands of an oversized graph would otherwise compile a bucket no steady
workload ever reuses, and can exhaust device memory).
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

from repro import spans
from repro.graphs.csr import (CSRGraph, build_csr, canonical_edges_with_rows,
                              degeneracy_order, edge_keys, relabel)
from repro.core import support as support_mod
from repro.core.hierarchy import HIER_MODES
from repro.core.pkt import (PEEL_MODES, PeelTables, _SENTINEL_S, _peel_loop,
                            align_to_input, chunk_ranges)
from repro.core.ref import truss_numpy
from repro.core.truss_inc import INSERT_MODES, IncrementalTruss, UpdateStats
from repro.kernels import wedge_common
from repro.testing.chaos import fault_point
from repro.kernels.wedge_common import next_pow2 as _next_pow2
from repro.kernels.wedge_common import pad1 as _pad1

_PAD_N = wedge_common.PAD_N  # adjacency padding: larger than any vertex id
_MIN_M_PAD = 8


class SizeClass(NamedTuple):
    """Bucket key: every compiled shape the batched pipeline depends on."""

    m_pad: int        # padded edge count (pow2)
    sup_pad: int      # padded support-table length (pow2)
    peel_pad: int     # padded peel-table length (pow2, multiple of chunk)
    chunk: int        # peel chunk size (pow2, <= peel_pad)
    n_chunks: int     # peel_pad // chunk
    iters: int        # binary-search iteration bound for 2*m_pad-length rows
    n_pad: int        # padded vertex count (pow2; 0 in table_mode="numpy",
    #                   whose operands carry no vertex-indexed arrays)


class _TableDims(NamedTuple):
    """Stand-in for a wedge table when only its entry count is known —
    ``table_mode="device"`` sizes buckets without materializing tables."""

    size: int


class BatchOperand(NamedTuple):
    """Per-graph padded device operands; stacked along axis 0 per bucket."""

    N: jnp.ndarray          # (2*m_pad,) adjacency values
    Eid: jnp.ndarray        # (2*m_pad,) slot → edge id
    s_e1: jnp.ndarray       # (sup_pad,) support-table anchor edges
    s_cand: jnp.ndarray     # (sup_pad,)
    s_lo: jnp.ndarray       # (sup_pad,)
    s_hi: jnp.ndarray       # (sup_pad,)
    p_e1: jnp.ndarray       # (peel_pad,) peel-table anchor edges
    p_e2: jnp.ndarray       # (peel_pad,) closing edges (``PeelTables``)
    p_e3: jnp.ndarray       # (peel_pad,)
    c_start: jnp.ndarray    # (m_pad,) first chunk of edge's entry range
    c_end: jnp.ndarray      # (m_pad,) last chunk (inclusive)
    has_entries: jnp.ndarray  # (m_pad,) bool
    m_real: jnp.ndarray     # () int32 — live edge count of this graph


class CSROperand(NamedTuple):
    """Per-graph padded *CSR* operands (``table_mode="device"``).

    Only graph-sized arrays cross the host boundary; both wedge tables are
    constructed inside the batched jit (vmapped device builders), so a
    submission uploads O(m + n) bytes instead of O(table) — the tables are
    several× the graph size on triangle-rich graphs.
    """

    N: jnp.ndarray          # (2*m_pad,) adjacency values
    Eid: jnp.ndarray        # (2*m_pad,) slot → edge id
    Es: jnp.ndarray         # (n_pad+1,) CSR row offsets
    Eo: jnp.ndarray         # (n_pad,) first >u slot per row
    u: jnp.ndarray          # (m_pad,) edge endpoints (u < v; padding 0)
    v: jnp.ndarray          # (m_pad,)
    m_real: jnp.ndarray     # () int32 — live edge count of this graph


@functools.partial(
    jax.jit,
    static_argnames=("m", "chunk", "n_chunks", "iters", "mode"),
)
def _batched_truss(ops: BatchOperand, *, m: int, chunk: int, n_chunks: int,
                   iters: int, mode: str):
    """vmap of (support → peel) across one bucket of padded graphs."""
    def one(op: BatchOperand):
        S0 = support_mod._support_jit(
            op.N, op.Eid, op.s_e1, op.s_cand, op.s_lo, op.s_hi, iters, m)
        edge_ok = jnp.arange(m + 1, dtype=jnp.int32) < op.m_real
        S_ext0 = jnp.where(
            edge_ok,
            jnp.concatenate([S0, jnp.zeros((1,), jnp.int32)]),
            _SENTINEL_S)
        processed0 = ~edge_ok
        tabs = PeelTables(op.p_e1, op.p_e2, op.p_e3,
                          op.c_start, op.c_end, op.has_entries)
        S_ext, _, levels, subs, _ = _peel_loop(
            S_ext0, processed0, tabs, m=m, chunk=chunk, n_chunks=n_chunks,
            mode=mode)
        return S_ext[:m], S0, levels, subs

    return jax.vmap(one)(ops)


@functools.partial(
    jax.jit,
    static_argnames=("m", "chunk", "n_chunks", "iters", "mode", "sup_pad",
                     "peel_pad"),
)
def _batched_truss_dev(ops: CSROperand, *, m: int, chunk: int, n_chunks: int,
                       iters: int, mode: str, sup_pad: int, peel_pad: int):
    """vmap of (build tables → support → peel) across one bucket of graphs.

    The ``table_mode="device"`` pipeline: both wedge tables are built by the
    vmapped device builders (``core.support._build_*_table_dev``) inside
    this one compiled program, so ``flush`` dispatches exactly one
    executable per bucket and no table ever exists on the host.
    """
    def one(op: CSROperand):
        s_e1, s_cand, s_lo, s_hi, _ = support_mod._build_support_table_dev(
            op.u, op.v, op.Es, op.Eo, op.m_real, m=m, size=sup_pad)
        S0 = support_mod._support_jit(
            op.N, op.Eid, s_e1, s_cand, s_lo, s_hi, iters, m)
        *cols, _hits = support_mod._build_peel_table_dev(
            op.u, op.v, op.Es, op.N, op.Eid, op.m_real, m=m, size=peel_pad,
            chunk=chunk, iters=iters)
        edge_ok = jnp.arange(m + 1, dtype=jnp.int32) < op.m_real
        S_ext0 = jnp.where(
            edge_ok,
            jnp.concatenate([S0, jnp.zeros((1,), jnp.int32)]),
            _SENTINEL_S)
        processed0 = ~edge_ok
        S_ext, _, levels, subs, _ = _peel_loop(
            S_ext0, processed0, PeelTables(*cols), m=m, chunk=chunk,
            n_chunks=n_chunks, mode=mode)
        return S_ext[:m], S0, levels, subs

    return jax.vmap(one)(ops)


@dataclasses.dataclass
class _Pending:
    ticket: int
    g: CSRGraph
    n: int
    in_keys: np.ndarray       # per input row: canonical key in relabeled space
    key: SizeClass
    E: np.ndarray             # canonical pre-relabel edges (handle promotion)
    operand: BatchOperand | CSROperand | None = None


class TrussHandle:
    """Persistent decomposition state — the mutable sibling of a ticket.

    Returned by ``TrussEngine.open`` (or by promoting a still-pending
    ticket through ``TrussEngine.update``).  Unlike the single-read ticket
    API, a handle retains its graph, trussness, and support across
    ``update`` calls until ``TrussEngine.close`` releases it.
    """

    __slots__ = ("hid", "_inc", "closed")

    def __init__(self, hid: int, inc: IncrementalTruss):
        self.hid = hid
        self._inc = inc
        self.closed = False

    @property
    def edges(self) -> np.ndarray:
        """Current canonical (m, 2) edge list (key-sorted)."""
        return self._inc.edges

    @property
    def trussness(self) -> np.ndarray:
        """Per-edge trussness aligned to ``edges`` rows."""
        return self._inc.trussness

    @property
    def m(self) -> int:
        """Current number of (unique, canonical) edges."""
        return self._inc.m

    @property
    def n(self) -> int:
        """Vertex-space size (max id + 1 at open; stable across updates)."""
        return self._inc.n

    @property
    def insert_mode(self) -> str:
        """Insertion repair strategy this handle's updates take (§13)."""
        return self._inc.insert_mode

    def query(self, edges) -> np.ndarray:
        """Trussness for specific edges, aligned to the given rows.

        The call is a ``repro.engine.query`` span with attribute ``rows``.
        """
        with spans.span("engine.query", rows=int(np.shape(edges)[0])):
            return self._inc.query(edges)

    # --------------------------------------------- community queries (§11) --
    def hierarchy(self, *, mode: str | None = None):
        """The handle's :class:`~repro.core.hierarchy.TrussHierarchy`.

        Lazily built from the handle's maintained trussness + triangle list
        and cached; local ``TrussEngine.update`` batches carry it forward
        (untouched levels are id-remapped, repaired levels rebuild lazily),
        full rebuilds drop it.  ``mode`` ∈ ``HIER_MODES`` overrides the
        engine's default ("device" label propagation vs the "host"
        union-find oracle — bitwise-identical labels either way); a
        non-default mode returns a standalone index without touching the
        cache, so oracle reads never evict the serving state.
        """
        return self._inc.hierarchy(mode=mode)

    def communities(self, k: int, *,
                    hier_mode: str | None = None) -> list[np.ndarray]:
        """Every k-truss community as a (c, 2) array of edge endpoints.

        Communities are the *triangle-connected* components of the edges
        with trussness >= k (Wang & Cheng), ordered by their representative
        (minimum) edge id; an edge in no surviving triangle forms a
        singleton.  k above the graph's max trussness yields ``[]``.
        ``hier_mode`` overrides the index builder for this call (the
        resilience layer's hierarchy-ladder hook, DESIGN.md §15): a
        non-default mode builds a standalone index, bypassing — and never
        evicting — the cached one, with bitwise-identical labels.
        """
        E = self._inc.edges
        ids_per = self._inc.hierarchy(mode=hier_mode).communities(k)
        return [E[ids] for ids in ids_per]

    def community(self, edge_or_vertex, k: int, *,
                  hier_mode: str | None = None):
        """The k-truss community around one edge — or all around one vertex.

        An ``(u, v)`` pair returns that edge's community as a (c, 2)
        endpoint array (empty when the edge's trussness is below ``k``; an
        edge not in the graph raises the descriptive alignment ValueError).
        A scalar vertex id returns a *list* of communities, one per distinct
        level-``k`` community among the vertex's incident edges — a vertex,
        unlike an edge, can sit on the border of several k-trusses (Huang et
        al.'s (q, k) query).  ``hier_mode`` overrides the index builder as
        in :meth:`communities`.  The call is a ``repro.engine.community``
        span with attribute ``k`` and counters ``communities`` and
        ``edges`` returned; levels the index builds for it nest inside.
        """
        with spans.span("engine.community", k=int(k)) as sp:
            h = self._inc.hierarchy(mode=hier_mode)
            E = self._inc.edges
            q = np.asarray(edge_or_vertex)
            if q.ndim == 0:                       # vertex query
                v = int(q)
                inc_ids = np.nonzero((E[:, 0] == v) | (E[:, 1] == v))[0]
                labels = h.level_labels(k)[inc_ids]
                reps = np.unique(labels[labels >= 0])
                out = [E[h.community_of(int(r), k)] for r in reps]
                sp.set(communities=len(out),
                       edges=sum(c.shape[0] for c in out))
                return out
            eid = int(self._inc.edge_ids(q.reshape(1, 2))[0])
            out = E[h.community_of(eid, k)]
            sp.set(communities=int(out.shape[0] > 0), edges=out.shape[0])
            return out

    def __repr__(self):
        state = "closed" if self.closed else f"m={self._inc.m}"
        return f"TrussHandle({self.hid}, {state})"


class TrussEngine:
    """Queue API over the batched decomposition pipeline.

    Two traffic shapes share one engine: *single-read tickets*
    (``submit``/``flush``/``result``/``map``) batch same-size-class graphs
    into one vmapped dispatch per bucket, and *persistent handles*
    (``open``/``update``/``update_many``/``close``) absorb edge churn by
    incremental repair (DESIGN.md §9).  ``repro.serve.TrussScheduler``
    wraps an engine with an async continuous-batching facade (§12).

    Args:
        mode: peel executor for every decomposition (see ``core.pkt.pkt``).
        table_mode: wedge-table builder — "device" ships CSR-only operands
            and builds both tables inside the batched jit (§10); "numpy" is
            the host parity oracle.
        hier_mode: community-index builder for handles (§11).
        insert_mode: handle insertion repair strategy ("batched" /
            "sequential", §13) — one merged-region re-peel per update batch
            vs one re-peel per inserted edge; bitwise-identical results.
        chunk: peel chunk size (rounded up to pow2). ``None`` (default)
            derives it per size class from the tuned-chunk policy
            (``kernels.wedge_common.auto_chunk``, §16).
        reorder: degeneracy-reorder each submission before decomposition.
        max_pending: auto-flush threshold — ``submit`` triggers a full
            ``flush`` once this many submissions are queued.
        max_edges: reject submissions beyond this many canonical edges.

    Raises:
        ValueError: unknown mode axis, or non-positive ``chunk`` /
            ``max_edges``.
    """

    def __init__(self, *, mode: str = "chunked", table_mode: str = "device",
                 hier_mode: str = "device", insert_mode: str = "batched",
                 chunk: int | None = None, reorder: bool = True,
                 max_pending: int = 32, max_edges: int = 1 << 22):
        if mode not in PEEL_MODES:
            raise ValueError(f"mode must be one of {PEEL_MODES}, got {mode!r}")
        if table_mode not in support_mod.TABLE_MODES:
            raise ValueError(f"table_mode must be one of "
                             f"{support_mod.TABLE_MODES}, got {table_mode!r}")
        if hier_mode not in HIER_MODES:
            raise ValueError(f"hier_mode must be one of {HIER_MODES}, "
                             f"got {hier_mode!r}")
        if insert_mode not in INSERT_MODES:
            raise ValueError(f"insert_mode must be one of {INSERT_MODES}, "
                             f"got {insert_mode!r}")
        if chunk is not None and chunk < 1:
            raise ValueError("chunk must be positive")
        if max_edges < 1:
            raise ValueError("max_edges must be positive")
        self.mode = mode
        self.table_mode = table_mode
        self.hier_mode = hier_mode
        self.insert_mode = insert_mode
        self.max_edges = max_edges
        self.chunk = None if chunk is None else _next_pow2(chunk)
        self.reorder = reorder
        self.max_pending = max_pending
        self._pending: list[_Pending] = []
        self._results: dict[int, np.ndarray] = {}
        self._next_ticket = 0
        self._handles: dict[int, TrussHandle] = {}
        self._next_handle = 0
        self.stats = {
            "submitted": 0, "flushes": 0, "batches": 0,
            "buckets": set(), "graph_seconds": 0.0, "graphs_done": 0,
            # warm_* counts only dispatches whose bucket was seen before
            # (compile already cached) — the steady-state throughput basis
            "warm_seconds": 0.0, "warm_graphs": 0,
            # handle lifecycle (incremental maintenance)
            "handles_opened": 0, "updates": 0, "updates_local": 0,
            "updates_full": 0, "update_seconds": 0.0,
        }

    # ------------------------------------------------------------- submit --
    def submit(self, edges: np.ndarray) -> int:
        """Queue one graph; returns a ticket for ``result``.

        ``edges`` is any (k, 2) integer array of undirected edges (either
        endpoint order; duplicate rows allowed; self-loops rejected, as are
        negative vertex ids and ids beyond the int32 CSR / int64 key-packing
        bounds — all used to corrupt results silently).  The result is
        aligned to the input rows: ``result(t)[i]`` is the trussness of
        ``edges[i]``.
        """
        E, lo, hi, n = canonical_edges_with_rows(edges)
        ticket = self._next_ticket
        self._next_ticket += 1
        self.stats["submitted"] += 1

        if E.size == 0:
            self._results[ticket] = np.zeros(0, np.int64)
            return ticket
        if E.shape[0] > self.max_edges:
            raise ValueError(
                f"graph too large for this engine: m={E.shape[0]} canonical "
                f"edges exceeds max_edges={self.max_edges}; decompose it "
                f"directly with core.pkt.truss_pkt, or raise max_edges")

        if self.reorder:
            perm = degeneracy_order(E, n)
            r_edges = relabel(E, perm)
        else:
            perm = np.arange(n, dtype=np.int64)
            r_edges = E
        # key of each *input row* in the relabeled space (handles duplicate
        # and endpoint-swapped rows: they map onto the same canonical edge)
        rl, rh = perm[lo], perm[hi]
        in_keys = edge_keys(np.minimum(rl, rh), np.maximum(rl, rh), n)

        g = build_csr(r_edges, n)
        if self.table_mode == "device":
            # tables never materialize on host: bucket by their exact entry
            # counts (O(m) host math) and ship only the CSR arrays
            stab = _TableDims(support_mod.support_table_size(g))
            ptab = _TableDims(support_mod.peel_table_size(g))
            key = self._size_class(g, stab, ptab)
            support_mod._check_table_size(max(key.sup_pad, key.peel_pad))
            operand = self._make_csr_operand(g, key)
        else:
            stab = support_mod.build_support_table(g)
            rows = support_mod.resolve_peel_table(g)
            key = self._size_class(g, stab, _TableDims(rows.wedges))
            operand = self._make_operand(g, key, stab, rows)
        self._pending.append(_Pending(
            ticket=ticket, g=g, n=n, in_keys=in_keys,
            key=key, E=E, operand=operand))
        if len(self._pending) >= self.max_pending:
            self.flush()
        return ticket

    def submit_many(self, graphs) -> list[int]:
        """Submit each graph; returns order-aligned tickets."""
        return [self.submit(e) for e in graphs]

    # ------------------------------------------------------------ results --
    def result(self, ticket: int) -> np.ndarray:
        """Trussness for one ticket, flushing pending work if needed.

        Single-read: each ticket's result is released when collected (keeps
        engine memory bounded under streaming traffic); a second read, or an
        unknown ticket, raises KeyError.
        """
        if ticket not in self._results:
            if any(p.ticket == ticket for p in self._pending):
                self.flush()
            else:
                raise KeyError(
                    f"unknown or already-collected ticket {ticket!r}")
        return self._results.pop(ticket)

    def map(self, graphs) -> list[np.ndarray]:
        """Submit a list of graphs, flush once, return order-aligned results."""
        tickets = self.submit_many(graphs)
        self.flush()
        return [self.result(t) for t in tickets]

    # ----------------------------------------------- incremental handles --
    def open(self, edges, *, local_frac: float = 0.25,
             insert_mode: str | None = None) -> TrussHandle:
        """Decompose ``edges`` into a *persistent* handle for ``update``.

        Unlike ``submit``'s single-read tickets, a handle retains the CSR
        graph, wedge-table-derived state, support, and trussness across
        arbitrarily many ``update`` batches until ``close`` releases it.
        ``insert_mode`` overrides the engine's insertion repair strategy
        for this handle (``None``: engine default, §13).
        """
        inc = IncrementalTruss(
            edges, mode=self.mode, table_mode=self.table_mode,
            hier_mode=self.hier_mode,
            insert_mode=(self.insert_mode if insert_mode is None
                         else insert_mode),
            chunk=self.chunk, local_frac=local_frac)
        h = TrussHandle(self._next_handle, inc)
        self._next_handle += 1
        self._handles[h.hid] = h
        self.stats["handles_opened"] += 1
        return h

    def update(self, ticket_or_handle, *, add_edges=None,
               remove_edges=None,
               insert_mode: str | None = None) -> UpdateStats:
        """Apply one insert/delete batch to a handle (or promote a ticket).

        Accepts a :class:`TrussHandle`, or an *int ticket* whose submission
        is still pending — the ticket is then consumed (it can no longer be
        redeemed through ``result``) and promoted to a fresh handle, which
        the returned stats carry in ``.handle``.  Tickets already flushed or
        collected cannot be promoted (the engine has released their graph);
        re-``open`` the edges instead.

        Small batches are absorbed by local repair (affected-region re-peel,
        see ``core/truss_inc.py``); large ones fall back to a full
        recompute.  ``stats.mode`` reports which path ran.  ``insert_mode``
        overrides the handle's insertion strategy for this call (§13).
        """
        h = self._resolve_handle(ticket_or_handle)
        st = h._inc.update(add_edges=add_edges, remove_edges=remove_edges,
                           insert_mode=insert_mode)
        self.stats["updates"] += 1
        if st.mode == "full":
            self.stats["updates_full"] += 1
        elif st.mode == "local":
            self.stats["updates_local"] += 1
        self.stats["update_seconds"] += st.seconds
        return dataclasses.replace(st, handle=h)

    def update_many(self, ticket_or_handle, batches, *,
                    insert_mode: str | None = None) -> UpdateStats:
        """Apply several queued update batches to one handle as one repair.

        The scheduler's coalescing entry point (DESIGN.md §12): ``batches``
        is a sequence of ``(add_edges, remove_edges)`` pairs in arrival
        order; their set-wise composition (``core.truss_inc.
        compose_update_batches``) is applied as a *single*
        :meth:`IncrementalTruss.update`, so n queued churn batches cost one
        affected-region repair instead of n.

        Args:
            ticket_or_handle: a :class:`TrussHandle` (or promotable ticket,
                as in :meth:`update`).
            batches: iterable of ``(add_edges, remove_edges)`` pairs;
                either element may be ``None``.
            insert_mode: per-call override of the handle's insertion
                strategy (``None``: handle default, §13).

        Returns:
            One :class:`UpdateStats` for the composed repair, with
            ``coalesced`` set to the number of merged batches and
            ``handle`` set to the target handle.  The final state is
            bitwise-identical to applying the batches one at a time.

        Raises:
            ValueError: closed handle, or invalid edge arrays.
            KeyError: a ticket that is not promotable.
        """
        h = self._resolve_handle(ticket_or_handle)
        st = h._inc.update_many(batches, insert_mode=insert_mode)
        self.stats["updates"] += 1
        if st.mode == "full":
            self.stats["updates_full"] += 1
        elif st.mode == "local":
            self.stats["updates_local"] += 1
        self.stats["update_seconds"] += st.seconds
        return dataclasses.replace(st, handle=h)

    def close(self, handle: TrussHandle) -> None:
        """Release a handle's retained state; further use raises."""
        if handle.closed:
            return
        handle.closed = True
        self._handles.pop(handle.hid, None)
        handle._inc = None

    def _resolve_handle(self, ticket_or_handle) -> TrussHandle:
        if isinstance(ticket_or_handle, TrussHandle):
            if ticket_or_handle.closed:
                raise ValueError(
                    f"handle {ticket_or_handle.hid} is closed")
            return ticket_or_handle
        ticket = int(ticket_or_handle)
        for i, p in enumerate(self._pending):
            if p.ticket == ticket:
                del self._pending[i]
                return self.open(p.E)
        raise KeyError(
            f"ticket {ticket!r} cannot be promoted to a handle: it is not "
            f"pending (already decomposed, collected, or unknown) — "
            f"open() the edges to get an updatable handle")

    # ------------------------------------------------------------ internals --
    def _size_class(self, g: CSRGraph, stab, ptab) -> SizeClass:
        m_pad = max(_MIN_M_PAD, _next_pow2(g.m))
        sup_pad = _next_pow2(max(1, stab.size))
        peel_pad = _next_pow2(max(1, ptab.size))
        chunk = wedge_common.pow2_chunk(peel_pad, self.chunk)
        n_chunks = peel_pad // chunk
        iters = int(np.ceil(np.log2(2 * m_pad + 1))) + 1
        n_pad = _next_pow2(g.n + 1) if self.table_mode == "device" else 0
        return SizeClass(m_pad, sup_pad, peel_pad, chunk, n_chunks, iters,
                         n_pad)

    def _make_csr_operand(self, g: CSRGraph, key: SizeClass) -> CSROperand:
        m_pad = key.m_pad
        two_m = 2 * g.m
        return CSROperand(
            N=jnp.asarray(_pad1(g.N, 2 * m_pad, _PAD_N)),
            Eid=jnp.asarray(_pad1(g.Eid, 2 * m_pad, m_pad)),
            Es=jnp.asarray(_pad1(g.Es, key.n_pad + 1, two_m)),
            Eo=jnp.asarray(_pad1(g.Eo, key.n_pad, two_m)),
            u=jnp.asarray(_pad1(g.El[:, 0], m_pad, 0)),
            v=jnp.asarray(_pad1(g.El[:, 1], m_pad, 0)),
            m_real=jnp.int32(g.m),
        )

    def _make_operand(self, g: CSRGraph, key: SizeClass, stab,
                      rows: support_mod.PeelRows) -> BatchOperand:
        m_pad = key.m_pad
        has_p, c_start, c_end = chunk_ranges(rows.off, key.chunk, m_out=m_pad)
        return BatchOperand(
            N=jnp.asarray(_pad1(g.N, 2 * m_pad, _PAD_N)),
            Eid=jnp.asarray(_pad1(g.Eid, 2 * m_pad, m_pad)),
            s_e1=jnp.asarray(_pad1(stab.e1, key.sup_pad, 0)),
            s_cand=jnp.asarray(_pad1(stab.cand_slot, key.sup_pad, 0)),
            s_lo=jnp.asarray(_pad1(stab.lo, key.sup_pad, 0)),
            s_hi=jnp.asarray(_pad1(stab.hi, key.sup_pad, 0)),
            p_e1=jnp.asarray(_pad1(rows.e1, key.peel_pad, m_pad)),
            p_e2=jnp.asarray(_pad1(rows.e2, key.peel_pad, m_pad)),
            p_e3=jnp.asarray(_pad1(rows.e3, key.peel_pad, m_pad)),
            c_start=jnp.asarray(c_start),
            c_end=jnp.asarray(c_end),
            has_entries=jnp.asarray(has_p),
            m_real=jnp.int32(g.m),
        )

    def discard(self, ticket: int) -> None:
        """Drop a ticket without computing or collecting it (scheduler hook).

        Args:
            ticket: a ticket returned by ``submit``; unknown tickets are
                ignored.  Removes the pending operand (or the materialized
                result) so cancelled or failed requests don't pin device
                arrays.
        """
        self._pending = [p for p in self._pending if p.ticket != ticket]
        self._results.pop(ticket, None)

    def bucket_of(self, ticket: int) -> SizeClass | None:
        """Size-class key of a still-pending ticket (scheduler hook).

        Args:
            ticket: a ticket returned by ``submit``.

        Returns:
            The pending submission's :class:`SizeClass` bucket key, or
            ``None`` when the ticket is not pending (empty graphs resolve at
            submit time; an auto-flush may have materialized the result) —
            its result, if any, is already available through ``result``.
        """
        for p in self._pending:
            if p.ticket == ticket:
                return p.key
        return None

    def flush(self, only=None, *, mode: str | None = None) -> None:
        """Decompose pending graphs, bucket by bucket.

        Args:
            only: optional iterable of :class:`SizeClass` keys — flush only
                the pending submissions in those buckets (the scheduler's
                per-bucket dispatch hook).  ``None`` flushes everything.
            mode: per-call peel-executor override (``None``: the engine's
                configured mode) — the resilience layer's degradation-
                ladder hook (DESIGN.md §15); results are bitwise-identical
                across modes.

        Ordering contract: each bucket's results are materialized (and its
        submissions removed from the pending queue) only after its batched
        dispatch succeeds, in submission order within the bucket.  If a
        dispatch raises, that bucket's submissions *and every bucket not yet
        dispatched* remain pending — their tickets stay redeemable by a
        later ``flush``/``result``, and a still-pending ticket can still be
        promoted to a handle by ``update`` (promotions observe the results
        of earlier ``submit`` calls flushed in the same batch: the flush
        and the promotion's from-scratch decomposition agree bitwise, see
        ``tests/test_truss_engine.py``).
        """
        eff_mode = self.mode if mode is None else mode
        if eff_mode not in PEEL_MODES:
            raise ValueError(
                f"mode must be one of {PEEL_MODES}, got {eff_mode!r}")
        if not self._pending:
            return
        by_key: dict[SizeClass, list[_Pending]] = {}
        keys = None if only is None else set(only)
        for p in self._pending:
            if keys is None or p.key in keys:
                by_key.setdefault(p.key, []).append(p)
        if not by_key:
            return

        for key, group in by_key.items():
            warm = key in self.stats["buckets"]
            t0 = time.perf_counter()
            fault_point("flush", rung=eff_mode)
            ops = jax.tree.map(lambda *xs: jnp.stack(xs),
                               *[p.operand for p in group])
            if self.table_mode == "device":
                S, S0, levels, subs = _batched_truss_dev(
                    ops, m=key.m_pad, chunk=key.chunk,
                    n_chunks=key.n_chunks, iters=key.iters, mode=eff_mode,
                    sup_pad=key.sup_pad, peel_pad=key.peel_pad)
            else:
                S, S0, levels, subs = _batched_truss(
                    ops, m=key.m_pad, chunk=key.chunk, n_chunks=key.n_chunks,
                    iters=key.iters, mode=eff_mode)
            S = np.asarray(S)
            for i, p in enumerate(group):
                truss = (S[i][: p.g.m] + 2).astype(np.int64)
                self._results[p.ticket] = align_to_input(
                    truss, p.g, None, p.n, keys=p.in_keys)
            # only now is the bucket done: drop its submissions from the
            # pending queue (a dispatch failure above leaves them — and
            # every bucket after them — pending and retryable)
            done = {p.ticket for p in group}
            self._pending = [p for p in self._pending
                             if p.ticket not in done]
            dt = time.perf_counter() - t0
            self.stats["batches"] += 1
            self.stats["buckets"].add(key)
            self.stats["graphs_done"] += len(group)
            self.stats["graph_seconds"] += dt
            if warm:
                self.stats["warm_seconds"] += dt
                self.stats["warm_graphs"] += len(group)
        self.stats["flushes"] += 1

    def flush_host(self, only=None) -> None:
        """Host-numpy fallback flush: the degradation ladder's last rung.

        Resolves the selected pending submissions with the pure-numpy
        reference decomposition (``core.ref.truss_numpy``) — no jax
        dispatch at all, so it stays available when every device executor
        is failing.  Results are bitwise-identical to :meth:`flush` (the
        reference is the repo's parity oracle); the same exception-safety
        contract applies (a failure leaves tickets pending and retryable).

        Args:
            only: optional iterable of :class:`SizeClass` keys, as in
                :meth:`flush`.
        """
        if not self._pending:
            return
        keys = None if only is None else set(only)
        group = [p for p in self._pending
                 if keys is None or p.key in keys]
        if not group:
            return
        t0 = time.perf_counter()
        fault_point("flush", rung="host")
        out = [align_to_input(truss_numpy(p.g.El), p.g, None, p.n,
                              keys=p.in_keys) for p in group]
        # commit only after every graph decomposed (exception safety)
        for p, truss in zip(group, out):
            self._results[p.ticket] = truss
        done = {p.ticket for p in group}
        self._pending = [p for p in self._pending if p.ticket not in done]
        self.stats["flushes"] += 1
        self.stats["graphs_done"] += len(group)
        self.stats["graph_seconds"] += time.perf_counter() - t0

    @property
    def throughput(self) -> float:
        """Graphs decomposed per second of engine compute.

        Based on warm dispatches only (buckets whose executable was already
        compiled); falls back to the all-in rate — which is dominated by XLA
        compile time — until any bucket has gone warm.
        """
        if self.stats["warm_seconds"] > 0:
            return self.stats["warm_graphs"] / self.stats["warm_seconds"]
        secs = self.stats["graph_seconds"]
        return self.stats["graphs_done"] / secs if secs > 0 else 0.0


def truss_batched(graphs, *, mode: str = "chunked",
                  table_mode: str = "device", chunk: int | None = None,
                  reorder: bool = True) -> list[np.ndarray]:
    """One-shot convenience: decompose a list of edge arrays, order-aligned."""
    graphs = list(graphs)
    eng = TrussEngine(mode=mode, table_mode=table_mode, chunk=chunk,
                      reorder=reorder, max_pending=len(graphs) or 1)
    return eng.map(graphs)
