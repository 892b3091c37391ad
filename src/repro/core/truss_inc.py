"""Incremental truss maintenance — local repair instead of full recompute.

The paper's PKT is a from-scratch decomposition; the serving workloads the
engine targets (per-user ego nets, rolling windows — DESIGN.md §7/§9) mutate
graphs by small edge batches, where a full recompute per update is the
dominant cost.  Following the streaming/local-repair line of work (Jakkula &
Karypis; Sarıyüce et al.; Huang et al.), this module absorbs a batch of edge
insertions and deletions with repair work bounded by the *affected region*
(the expensive parts — probing, peeling, incidence walks — stay
region-local; a few O(m) vectorized mask/bound passes per step remain):

  1. **Persistent triangle state** — besides CSR + trussness + support, a
     handle retains the graph's triangle list, maintained incrementally:
     deletions drop the rows containing a deleted edge, each insertion
     appends the rows it creates (enumerated by the same oriented-wedge
     probe the full pipeline uses, ``kernels/wedge_common``).  Support
     repair and affected-region search are then pure index operations — no
     per-update support pass.
  2. **Affected region** — trussness changes obey level-filtered triangle
     locality (Huang et al.): an edge at level k can *drop* only if it is
     triangle-connected in the old graph to a deleted edge through edges
     with ``T >= k`` (so deletions batch exactly; k = 2 can never drop), and
     can *rise* only if triangle-connected in the new graph to an inserted
     edge through edges whose new trussness reaches k+1.  Deletions batch
     exactly; for insertions the default ``insert_mode="batched"`` path
     (DESIGN.md §13, after Jakkula & Karypis) repairs the whole batch at
     once — the per-edge rise filter generalizes to the batch bound
     ``UB = min(S+2, T+b)`` and the per-edge candidate regions merge into
     one shared region re-peeled in a single dispatch — while
     ``insert_mode="sequential"`` keeps the one-at-a-time path (the tight
     ±1 filter, not-yet-inserted edges masked absent) as the bitwise
     parity oracle.
  3. **Local re-peel** — the region is re-peeled against a *pinned
     boundary*: exterior triangle partners are seeded at their known death
     level ``trussness − 2`` and shielded from decrements, replaying
     exactly the removal schedule the full peel would produce.  Small
     regions (the steady-state case) run a host-numpy mirror of the
     sub-level loop; larger ones run the live-edge compaction machinery
     (``core.pkt.peel_live_subset``, DESIGN.md §10): the region is gathered
     into a compacted pow2-bucketed edge space — device work bounded by the
     region, not the graph — and peeled there (all three peel executors
     support the pinned mask).
  4. **Fallback** — when a region exceeds ``local_frac`` of the edge set,
     local repair stops paying and the update falls back to the full
     (support + peel) pipeline, refreshing all retained state.

The serving layer wraps this in a persistent handle
(``TrussEngine.open / update / close`` in ``serve/truss_engine.py``);
``launch/truss.py --update-stream`` replays synthetic churn through it.
The benchmark cell ``community.kron11`` (``chipbench/``) measures update
speed on the chip: a delete-and-re-insert edge stream on a Kronecker graph
through the scheduler, with community queries after every batch.  Each
update is a ``repro.inc.update`` span (``repro.spans``) whose children
name the phases: ``inc.deletions``, ``inc.insertions``,
``inc.region_peel``, ``inc.full_rebuild`` and ``inc.triangle_list``.
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np

from repro import spans
from repro.graphs.csr import (CSRGraph, build_csr, canonical_edges_with_rows,
                              check_edge_array, degeneracy_order, edge_keys,
                              relabel)
from repro.core import support as support_mod
from repro.core.hierarchy import HIER_MODES, TrussHierarchy
from repro.core.pkt import (_COMPACT_FRAC, _COMPACT_MIN, PEEL_MODES,
                            align_to_input, peel_live_subset, pkt)
from repro.kernels import wedge_common
from repro.testing.chaos import fault_point

#: Insertion repair strategies (DESIGN.md §13): ``"batched"`` repairs the
#: whole insertion batch against one merged candidate region; ``"sequential"``
#: applies edges one at a time (the ±1 locality bound) and serves as the
#: bitwise parity oracle for the batched path.
INSERT_MODES = ("sequential", "batched")

#: ``mode`` counter of the ``inc.update`` span, by ``UpdateStats.mode``
MODE_CODES = {"noop": 0, "local": 1, "full": 2}


class IntegrityError(RuntimeError):
    """Maintained incremental state failed a consistency check.

    Raised by the pinned-boundary replay invariant in ``_region_peel``
    (before any corrupt trussness could be committed) and by
    :meth:`IncrementalTruss.check_invariants` (after commit, on a sampled
    edge set).  The serving layer treats it as a self-healing trigger:
    quarantine the handle and rebuild from the retained CSR
    (:meth:`IncrementalTruss.rebuild`) rather than retry (DESIGN.md §15).
    """


@dataclasses.dataclass(frozen=True)
class UpdateStats:
    """Outcome of one ``IncrementalTruss.update`` call."""

    mode: str            # "noop" | "local" | "full"
    m_before: int
    m_after: int
    inserted: int        # edges actually added (not already present)
    deleted: int         # edges actually removed (were present)
    affected: int        # total edges locally re-peeled across the batch
    boundary: int        # total pinned schedule edges across the batch
    rounds: int          # level-filtered BFS passes executed
    changed: int         # current edges whose trussness is new or different
    seconds: float
    handle: object = None  # set by TrussEngine.update
    coalesced: int = 1   # queued batches merged into this repair (§12)
    insert_mode: str | None = None  # path insertions took (None: no inserts)


def compose_update_batches(batches) -> tuple[np.ndarray, np.ndarray]:
    """Collapse a sequence of update batches into one equivalent batch.

    One ``update`` batch maps ``E → (E − remove) ∪ add`` (set-wise, add
    wins on overlap).  That composition is closed: applying batches
    ``(a_1, r_1) … (a_k, r_k)`` in order equals applying the single batch
    ``(A, R)`` with ``A`` the surviving adds (each ``a_i`` minus every
    *later* remove) and ``R`` the union of all removes — the scheduler's
    coalescing rule (DESIGN.md §12).

    Args:
        batches: iterable of ``(add_edges, remove_edges)`` pairs in arrival
            order; either element may be ``None`` or empty.

    Returns:
        ``(add, remove)`` int64 ``(k, 2)`` canonical edge arrays such that
        one ``update(add_edges=add, remove_edges=remove)`` produces the
        same graph as applying the batches sequentially.

    Raises:
        ValueError: any batch fails edge validation (self-loops, negative
            or overflowing vertex ids).
    """
    A: set[tuple[int, int]] = set()
    R: set[tuple[int, int]] = set()
    empty = np.zeros((0, 2), np.int64)
    for add, rem in batches:
        a = check_edge_array(add if add is not None else empty)
        r = check_edge_array(rem if rem is not None else empty)
        a_set = {(min(int(u), int(v)), max(int(u), int(v))) for u, v in a}
        r_set = {(min(int(u), int(v)), max(int(u), int(v))) for u, v in r}
        A -= r_set
        A |= a_set
        R |= r_set
    def to_arr(s):
        return np.array(sorted(s), np.int64) if s else empty

    return to_arr(A), to_arr(R)


# --------------------------------------------------------------- triangles --

def wedge_subtable(g: CSRGraph, anchors: np.ndarray) -> support_mod.WedgeTable:
    """Peel-phase wedge table restricted to ``anchors`` (sorted edge ids).

    Same layout and min-degree orientation policy as
    ``support.build_peel_table``, but only the anchor edges get entries; the
    ``off`` array still spans all ``m`` edges (non-anchors carry empty
    ranges), so ``support.resolve_peel_table`` resolves it unchanged.
    """
    anchors = np.asarray(anchors, dtype=np.int64)
    if anchors.size == 0 or g.m == 0:
        return support_mod.WedgeTable(
            e1=np.zeros(0, np.int32), cand_slot=np.zeros(0, np.int32),
            lo=np.zeros(0, np.int32), hi=np.zeros(0, np.int32),
            off=np.zeros(g.m + 1, np.int64))
    Es = g.Es.astype(np.int64)
    deg = Es[1:] - Es[:-1]
    u = g.El[anchors, 0].astype(np.int64)
    v = g.El[anchors, 1].astype(np.int64)
    swap = deg[u] > deg[v]
    cand = np.where(swap, v, u)          # scan this side's full adjacency
    prob = np.where(swap, u, v)          # binary-search this side
    cnt = deg[cand]
    off = np.zeros(g.m + 1, np.int64)
    off[anchors + 1] = cnt
    np.cumsum(off, out=off)
    e1 = np.repeat(anchors, cnt)
    intra = np.arange(int(off[-1]), dtype=np.int64) - off[e1]
    cand_rep = np.repeat(cand, cnt)
    prob_rep = np.repeat(prob, cnt)
    return support_mod.WedgeTable(
        e1=e1.astype(np.int32),
        cand_slot=(Es[cand_rep] + intra).astype(np.int32),
        lo=Es[prob_rep].astype(np.int32),
        hi=Es[prob_rep + 1].astype(np.int32),
        off=off,
    )


def triangles_through(g: CSRGraph,
                      anchors: np.ndarray) -> tuple[np.ndarray, np.ndarray,
                                                    np.ndarray]:
    """Every triangle through each anchor edge, as (anchor, e2, e3) id rows.

    A triangle through an anchor is reported exactly once *per anchor it
    contains*.  Runs on the host (``support.resolve_peel_table``, which
    probes with ``probe_np``) — update batches probe tiny,
    differently-shaped tables every call, the wrong regime for a jit trace.
    """
    anchors = np.asarray(anchors, dtype=np.int64)
    if anchors.size == 0 or g.m == 0:
        z = np.zeros(0, np.int64)
        return z, z.copy(), z.copy()
    rows = support_mod.resolve_peel_table(g, wedge_subtable(g, anchors))
    return (rows.e1.astype(np.int64), rows.e2.astype(np.int64),
            rows.e3.astype(np.int64))


def triangle_list(g: CSRGraph) -> np.ndarray:
    """All triangles of ``g``, each exactly once, as a (T, 3) edge-id array.

    Enumerated on the host with the full-adjacency wedge probe anchored at
    every edge (each triangle surfaces once per member edge) and kept at
    its lowest member id.  The tests' oracle and the hierarchy helper
    (``hierarchy.hierarchy_from_graph``); a handle's full rebuild takes
    its list from the peel table ``pkt`` builds instead
    (``PKTResult.triangles``), and updates maintain it incrementally.
    """
    if g.m == 0:
        return np.zeros((0, 3), np.int64)
    a, e2, e3 = triangles_through(g, np.arange(g.m, dtype=np.int64))
    keep = (a < e2) & (a < e3)
    return np.sort(np.stack([a[keep], e2[keep], e3[keep]], axis=1), axis=1)


def _canonical_triangles(tri: np.ndarray, m: int) -> np.ndarray:
    """``tri``'s rows, each sorted, in lexicographic order, as int64.

    Two edges of a triangle fix the third, so a row's lowest and middle
    ids key it uniquely.
    """
    a, b, c = (col.astype(np.int64) for col in tri.T)
    lo = np.minimum(np.minimum(a, b), c)
    hi = np.maximum(np.maximum(a, b), c)
    mid = a + b + c - lo - hi
    order = np.argsort(lo * m + mid)
    return np.stack([lo[order], mid[order], hi[order]], axis=1)


class _Incidence:
    """Edge → triangle-row CSR over a fixed (T, 3) triangle list."""

    def __init__(self, tri: np.ndarray, m: int):
        self.tri = tri
        flat = tri.ravel()
        order = np.argsort(flat, kind="stable")
        counts = np.bincount(flat, minlength=m) if flat.size else \
            np.zeros(m, np.int64)
        self.off = np.zeros(m + 1, np.int64)
        np.cumsum(counts, out=self.off[1:])
        self.idx = order // 3

    def rows_of(self, edges: np.ndarray) -> np.ndarray:
        """Triangle-row indices incident to any of ``edges`` (with repeats)."""
        if edges.size == 0 or self.idx.size == 0:
            return np.zeros(0, np.int64)
        cnt = self.off[edges + 1] - self.off[edges]
        pos = np.repeat(self.off[edges], cnt) + \
            (np.arange(int(cnt.sum()), dtype=np.int64)
             - np.repeat(np.cumsum(cnt) - cnt, cnt))
        return self.idx[pos]


def _tri_bfs(inc: _Incidence, side: np.ndarray, seeds: np.ndarray,
             allowed: np.ndarray) -> np.ndarray:
    """Edges triangle-reachable from ``seeds`` through ``allowed`` edges.

    Traversal steps through triangles (static ``inc`` rows plus the ``side``
    rows of the in-flight insertion phase) *all three* of whose edges are
    allowed — the certificate subgraphs of the locality lemmas are closed
    under their own triangles, so the stricter rule loses nothing.  Returns
    the sorted reached edge ids (seeds outside ``allowed`` are dropped).
    """
    m = allowed.shape[0]
    visited = np.zeros(m, bool)
    frontier = np.unique(seeds[allowed[seeds]]) if seeds.size else \
        np.zeros(0, np.int64)
    visited[frontier] = True
    in_side = side.size > 0
    while frontier.size:
        rows = inc.tri[np.unique(inc.rows_of(frontier))] \
            if inc.tri.size else np.zeros((0, 3), np.int64)
        if in_side:
            hit = np.isin(side, frontier).any(axis=1)
            rows = np.concatenate([rows, side[hit]])
        if rows.size == 0:
            break
        ok = allowed[rows].all(axis=1)
        cand = rows[ok].ravel()
        cand = np.unique(cand[~visited[cand]]) if cand.size else cand
        visited[cand] = True
        frontier = cand
    return np.nonzero(visited)[0].astype(np.int64)


def _h_values(inc: _Incidence, tau: np.ndarray,
              work: np.ndarray) -> np.ndarray:
    """Truss h-operator for each edge in ``work``: 2 + (largest s such that
    the edge is in >= s triangles whose other two edges both have current
    value >= s + 2).  Vectorized over the incidence structure."""
    if work.size == 0:
        return np.zeros(0, np.int64)
    cnt = inc.off[work + 1] - inc.off[work]
    owner = np.repeat(np.arange(work.shape[0], dtype=np.int64), cnt)
    rows = inc.tri[inc.rows_of(work)]
    h = np.zeros(work.shape[0], np.int64)
    if rows.size:
        e = work[owner]
        # partner-min in rho (= tau - 2) space, per membership
        t0, t1, t2 = tau[rows[:, 0]], tau[rows[:, 1]], tau[rows[:, 2]]
        val = np.where(
            rows[:, 0] == e, np.minimum(t1, t2),
            np.where(rows[:, 1] == e, np.minimum(t0, t2),
                     np.minimum(t0, t1))) - 2
        order = np.lexsort((-val, owner))
        owner_s, val_s = owner[order], val[order]
        starts = np.nonzero(np.diff(owner_s, prepend=-1))[0]
        rank = np.arange(owner_s.shape[0], dtype=np.int64) \
            - np.repeat(starts, np.diff(np.append(starts, owner_s.shape[0])))
        score = np.minimum(val_s, rank + 1)
        np.maximum.at(h, owner_s, np.maximum(score, 0))
    return h + 2


def _h_descent(inc: _Incidence, tau: np.ndarray, seeds: np.ndarray,
               totals, limit: float) -> bool:
    """Chaotic descent of the truss h-operator from a valid upper bound.

    Exact when ``tau`` starts pointwise >= the true decomposition (any
    h-operator post-fixpoint is <= truth via its own >=k-subgraph
    certificate, and monotone descent never goes below truth), which holds
    for pure deletions: the pre-deletion trussness bounds the post-deletion
    one.  Work is proportional to the edges that actually drop plus their
    triangle neighborhoods — no a-priori region needed.  Mutates ``tau``;
    returns False (request full-recompute fallback, ``tau`` then discarded)
    once more than ``limit`` edges have dropped — the local_frac policy.
    """
    changed = np.zeros(tau.shape[0], bool)
    work = np.unique(seeds)
    while work.size:
        totals["passes"] += 1
        h = _h_values(inc, tau, work)
        dropped = work[h < tau[work]]
        tau[dropped] = h[h < tau[work]]
        changed[dropped] = True
        if dropped.size == 0:
            break
        if int(changed.sum()) > limit:
            totals["affected"] += int(changed.sum())
            return False
        rows = inc.tri[np.unique(inc.rows_of(dropped))]
        work = np.unique(rows.ravel()) if rows.size else \
            np.zeros(0, np.int64)
    totals["affected"] += int(changed.sum())
    return True


# -------------------------------------------------------------- local peel --

def _host_peel(n_loc: int, tri_loc: np.ndarray, S0: np.ndarray,
               live0: np.ndarray, pinned: np.ndarray) -> np.ndarray:
    """Host-numpy mirror of the ``_peel_loop`` sub-level fixed point.

    Operates on a compact local edge space (``n_loc`` slots): ``tri_loc``
    holds the region's triangles as local-id rows, ``S0`` the start support
    (pinned edges: their death level), ``live0`` the live slots.  Same
    decrement formulas and tie-break as ``core.pkt._peel_loop``; the final
    values agree because the peel fixed point is schedule-independent.
    """
    S = S0.astype(np.int64).copy()
    processed = ~live0.copy()
    if tri_loc.size:
        e1 = tri_loc.ravel()
        oth = np.stack([tri_loc[:, [1, 2]], tri_loc[:, [0, 2]],
                        tri_loc[:, [0, 1]]], axis=1).reshape(-1, 2)
        e2, e3 = oth[:, 0], oth[:, 1]
    else:
        e1 = e2 = e3 = np.zeros(0, np.int64)
    while not processed.all():
        l = S[~processed].min()
        inCurr = ~processed & (S == l)
        while inCurr.any():
            valid = inCurr[e1] & ~processed[e2] & ~processed[e3]
            dec2 = valid & (S[e2] > l) & (~inCurr[e3] | (e1 < e3)) \
                & ~pinned[e2]
            dec3 = valid & (S[e3] > l) & (~inCurr[e2] | (e1 < e2)) \
                & ~pinned[e3]
            dec = np.bincount(e2[dec2], minlength=n_loc) \
                + np.bincount(e3[dec3], minlength=n_loc)
            S = np.where(~processed & ~inCurr & (dec > 0),
                         np.maximum(S - dec, l), S)
            processed = processed | inCurr
            inCurr = ~processed & (S == l)
    return S


# --------------------------------------------------------------- the state --

class IncrementalTruss:
    """A decomposed graph that absorbs edge insertions/deletions in place.

    State held across updates: the CSR graph, per-edge trussness *and*
    support (both aligned to ``g.El`` row order, which is canonical-key
    order), the triangle list, and the vertex-id space ``n`` (grows
    monotonically as updates introduce new vertex ids).

    ``update(add_edges=…, remove_edges=…)`` applies one batch:
    ``E_new = (E_old − remove) ∪ add``.  Inserting an edge that already
    exists, or removing one that doesn't, is a no-op for that row (the
    batch semantics are set-wise; an edge in both batches ends up present).
    Returns :class:`UpdateStats`.

    Args:
        edges: initial (k, 2) integer edge array (validated like every
            batch entry point).
        n: vertex-space size (default: max id + 1; grows with updates).
        mode: peel executor (see ``core.pkt.pkt``).
        table_mode: wedge-table builder ("device" / "numpy", §10).
        hier_mode: community-index builder ("device" / "host", §11).
        insert_mode: insertion repair strategy ("batched" / "sequential",
            §13) — one merged-region re-peel per batch vs one re-peel per
            inserted edge; bitwise-identical results.
        chunk: peel chunk size (pow2); ``None`` applies the tuned
            auto-chunk policy per table (``kernels.wedge_common``).
        local_frac: affected-region fraction above which an update falls
            back to full recompute.
        host_peel_max: region size ceiling for the host re-peel path;
            larger affected regions use the masked device re-peel.
        compact_frac: live-edge compaction threshold for full recomputes
            (``None`` disables; §10).
        compact_min: minimum live-edge count for compaction.

    Raises:
        ValueError: unknown mode axis, invalid edge array, or
            out-of-range ``local_frac``.
    """

    def __init__(self, edges, *, n: int | None = None, mode: str = "chunked",
                 table_mode: str = "device", hier_mode: str = "device",
                 insert_mode: str = "batched", chunk: int | None = None,
                 local_frac: float = 0.25, host_peel_max: int = 4096,
                 compact_frac: float | None = _COMPACT_FRAC,
                 compact_min: int = _COMPACT_MIN):
        if mode not in PEEL_MODES:
            raise ValueError(f"mode must be one of {PEEL_MODES}, got {mode!r}")
        if table_mode not in support_mod.TABLE_MODES:
            raise ValueError(
                f"table_mode must be one of {support_mod.TABLE_MODES}, "
                f"got {table_mode!r}")
        if hier_mode not in HIER_MODES:
            raise ValueError(
                f"hier_mode must be one of {HIER_MODES}, got {hier_mode!r}")
        if insert_mode not in INSERT_MODES:
            raise ValueError(
                f"insert_mode must be one of {INSERT_MODES}, "
                f"got {insert_mode!r}")
        if chunk is not None and chunk < 1:
            raise ValueError("chunk must be positive")
        if not 0.0 <= local_frac <= 1.0:
            raise ValueError("local_frac must be in [0, 1]")
        self.mode = mode
        self.table_mode = table_mode
        self.hier_mode = hier_mode
        self.insert_mode = insert_mode
        self._hier: TrussHierarchy | None = None
        self.compact_frac = compact_frac
        self.compact_min = int(compact_min)
        self.chunk = (None if chunk is None
                      else wedge_common.next_pow2(chunk))
        self.local_frac = float(local_frac)
        self.host_peel_max = int(host_peel_max)
        self.stats = {"updates": 0, "local": 0, "full": 0, "noop": 0,
                      "update_seconds": 0.0, "last": None}
        E, _, _, n_seen = canonical_edges_with_rows(edges)
        self.n = max(int(n or 0), n_seen)
        self._full_rebuild(E)

    # ------------------------------------------------------------ queries --
    @property
    def m(self) -> int:
        """Current canonical edge count."""
        return self.g.m

    @property
    def edges(self) -> np.ndarray:
        """Current canonical (m, 2) int64 edge list (key-sorted)."""
        return self.g.El.astype(np.int64)

    @property
    def trussness(self) -> np.ndarray:
        """Per-edge trussness aligned to ``edges`` rows (int64)."""
        return self.T.copy()

    @property
    def support(self) -> np.ndarray:
        """Per-edge triangle count aligned to ``edges`` rows (int32)."""
        return self.S.copy()

    @property
    def triangles(self) -> np.ndarray:
        """Current (T, 3) triangle list (edge-id rows, each once)."""
        return self.tri.copy()

    def edge_ids(self, edges) -> np.ndarray:
        """Canonical row ids of specific edges, aligned to the given rows.

        Rows may be endpoint-swapped or duplicated; an edge not currently in
        the graph raises the descriptive ``align_to_input`` ValueError.
        """
        rows = check_edge_array(edges)
        if rows.size == 0:
            return np.zeros(0, np.int64)
        lo = np.minimum(rows[:, 0], rows[:, 1])
        hi = np.maximum(rows[:, 0], rows[:, 1])
        if int(rows.max()) >= self.n:
            i = int(np.argmax(hi >= self.n))
            raise ValueError(
                f"edge ({int(lo[i])}, {int(hi[i])}) not present in the "
                f"graph's edge list (vertex id beyond the graph)")
        return align_to_input(np.arange(self.g.m, dtype=np.int64), self.g,
                              None, self.n, keys=edge_keys(lo, hi, self.n))

    def query(self, edges) -> np.ndarray:
        """Trussness for specific edges, aligned to the given rows."""
        return self.T[self.edge_ids(edges)]

    def hierarchy(self, *, mode: str | None = None) -> TrussHierarchy:
        """The community index over the current decomposition (lazy, cached).

        Built from the handle's own trussness + maintained triangle list on
        first access; levels themselves materialize lazily inside the index.
        The cache survives *local* ``update`` batches (untouched levels are
        id-remapped, repaired levels come back dirty — see ``_hier_update``)
        and is dropped whole by full rebuilds.  ``mode`` overrides the
        handle's ``hier_mode``: a *different* mode returns a standalone
        (uncached) index, so parity-oracle reads never evict the serving
        cache.
        """
        mode = self.hier_mode if mode is None else mode
        if mode not in HIER_MODES:
            raise ValueError(
                f"mode must be one of {HIER_MODES}, got {mode!r}")
        if mode != self.hier_mode:
            return TrussHierarchy(self.T, self.tri, mode=mode)
        if self._hier is None:
            self._hier = TrussHierarchy(self.T, self.tri, mode=mode)
        return self._hier

    # ------------------------------------------------------------- update --
    def update_many(self, batches, *,
                    insert_mode: str | None = None) -> UpdateStats:
        """Apply several update batches as one composed repair.

        Args:
            batches: iterable of ``(add_edges, remove_edges)`` pairs in
                arrival order (either element may be ``None``).
            insert_mode: per-call override of the handle's insertion
                strategy (``None``: use the handle default).

        Returns:
            The :class:`UpdateStats` of the single composed ``update``,
            with ``coalesced`` set to the number of merged batches.  The
            final state is bitwise-identical to applying the batches one
            at a time (see :func:`compose_update_batches`).

        Raises:
            ValueError: any batch fails edge validation.
        """
        batches = list(batches)
        add, rem = compose_update_batches(batches)
        st = self.update(add_edges=add, remove_edges=rem,
                         insert_mode=insert_mode)
        st = dataclasses.replace(st, coalesced=max(1, len(batches)))
        self.stats["last"] = st
        return st

    def update(self, add_edges=None, remove_edges=None, *,
               insert_mode: str | None = None) -> UpdateStats:
        """Apply one insert/delete batch: ``E → (E − remove) ∪ add``.

        Args:
            add_edges: ``(k, 2)`` integer edge array to insert (either
                endpoint order; duplicates collapse; inserting a present
                edge is a no-op for that row).  ``None`` means none.
            remove_edges: ``(k, 2)`` integer edge array to delete (removing
                an absent edge is a no-op for that row).  An edge in both
                batches ends up present.
            insert_mode: per-call override of the handle's insertion
                strategy (``"batched"`` / ``"sequential"``, §13; ``None``:
                use the handle default).

        Returns:
            :class:`UpdateStats` — ``mode`` reports whether the batch was
            absorbed by local repair (``"local"``), fell back to a full
            recompute (``"full"``), or changed nothing (``"noop"``).

        Raises:
            ValueError: edge arrays fail validation (self-loops, negative
                or overflowing vertex ids), or unknown ``insert_mode``.

        The call is a ``repro.inc.update`` span: attributes ``inserted``,
        ``deleted`` and ``m`` (edges after the batch); counters ``mode``
        (``MODE_CODES``), ``affected``, ``boundary``, ``passes`` and
        ``insert_candidates``, the insertion candidate region's size where
        its level scan ended (at the first level past ``local_frac`` when
        the batch falls back to a full recompute; absent where no scan ran).
        """
        with spans.span("inc.update") as sp:
            return self._update(add_edges, remove_edges, insert_mode, sp)

    def _update(self, add_edges, remove_edges, insert_mode, sp):
        t0 = time.perf_counter()
        imode = self.insert_mode if insert_mode is None else insert_mode
        if imode not in INSERT_MODES:
            raise ValueError(
                f"insert_mode must be one of {INSERT_MODES}, got {imode!r}")
        add = check_edge_array(add_edges if add_edges is not None
                               else np.zeros((0, 2), np.int64))
        rem = check_edge_array(remove_edges if remove_edges is not None
                               else np.zeros((0, 2), np.int64))
        hi_seen = max(int(add.max(initial=-1)), int(rem.max(initial=-1)))
        if hi_seen >= self.n:
            self.n = hi_seen + 1          # vertex space grows monotonically
        n = self.n
        m_before = self.g.m

        old_keys = edge_keys(self.g.El[:, 0].astype(np.int64),
                             self.g.El[:, 1].astype(np.int64), n)
        add_keys = self._batch_keys(add, n)
        rem_keys = self._batch_keys(rem, n)
        new_keys = np.union1d(
            np.setdiff1d(old_keys, rem_keys, assume_unique=True), add_keys)
        I_keys = np.setdiff1d(new_keys, old_keys, assume_unique=True)
        D_keys = np.setdiff1d(old_keys, new_keys, assume_unique=True)

        sp.set(inserted=int(I_keys.size), deleted=int(D_keys.size),
               m=int(new_keys.shape[0]))
        totals = {"affected": 0, "boundary": 0, "passes": 0,
                  "insert_candidates": None}
        T_old_ref = self.T      # for the changed count (old-id space)

        def done(mode):
            m_after = self.g.m
            if mode == "noop":
                changed = 0
            else:
                posn = np.searchsorted(
                    edge_keys(self.g.El[:, 0].astype(np.int64),
                              self.g.El[:, 1].astype(np.int64), n), old_keys)
                safe = np.minimum(posn, max(m_after - 1, 0))
                ok = np.zeros(m_before, bool)
                if m_after:
                    kn = edge_keys(self.g.El[:, 0].astype(np.int64),
                                   self.g.El[:, 1].astype(np.int64), n)
                    ok = (posn < m_after) & (kn[safe] == old_keys)
                changed = int((self.T[posn[ok]] != T_old_ref[ok]).sum()) \
                    + int(I_keys.size)
                if mode == "local" and self._hier is not None:
                    self._hier_update(old_keys, I_keys, T_old_ref, posn, ok,
                                      kn if m_after else None)
            st = UpdateStats(
                mode=mode, m_before=m_before, m_after=m_after,
                inserted=int(I_keys.size), deleted=int(D_keys.size),
                affected=totals["affected"], boundary=totals["boundary"],
                rounds=totals["passes"], changed=changed,
                seconds=time.perf_counter() - t0,
                insert_mode=imode if (I_keys.size and mode != "noop")
                else None)
            sp.set(mode=MODE_CODES[mode], affected=st.affected,
                   boundary=st.boundary, passes=st.rounds)
            if totals["insert_candidates"] is not None:
                sp.set(insert_candidates=totals["insert_candidates"])
            self.stats["updates"] += 1
            self.stats[mode] += 1
            self.stats["update_seconds"] += st.seconds
            self.stats["last"] = st
            return st

        if I_keys.size == 0 and D_keys.size == 0:
            return done("noop")

        E_new = np.stack([new_keys // n, new_keys % n], axis=1)
        limit = self.local_frac * max(1, new_keys.shape[0])

        # Both phases build the next state off to the side and it is
        # committed exactly once, after the whole batch has succeeded — an
        # exception mid-repair must leave the handle bitwise-untouched
        # (no half-applied batch, §13).
        state = (self.g, self.T, self.S, self.tri)

        # ---------------- phase D: all deletions as one exact batch -------
        if D_keys.size:
            with spans.span("inc.deletions"):
                state = self._apply_deletions(old_keys, D_keys, n, limit,
                                              totals)
            if state is None:
                self._full_rebuild(E_new)
                return done("full")

        # ---------------- phase I: insertions (batched or sequential) -----
        if I_keys.size:
            with spans.span("inc.insertions"):
                state = self._apply_insertions(state, new_keys, I_keys, n,
                                               limit, totals, imode)
            if state is None:
                self._full_rebuild(E_new)
                return done("full")

        self._commit(*state)
        return done("local")

    # ------------------------------------------------------- deletion phase --
    def _apply_deletions(self, old_keys, D_keys, n, limit, totals):
        """G → G − D, built off to the side (committed state untouched).

        Returns the repaired ``(g, T, S, tri)`` state tuple, or ``None`` to
        request full fallback.
        """
        g_old, T_old, S_old, tri_old = self.g, self.T, self.S, self.tri
        m_old = g_old.m
        del_old = np.searchsorted(old_keys, D_keys)
        is_del = np.zeros(m_old, bool)
        is_del[del_old] = True

        mid_keys = np.setdiff1d(old_keys, D_keys, assume_unique=True)
        E_mid = np.stack([mid_keys // n, mid_keys % n], axis=1)
        g_mid = build_csr(E_mid, n)
        m_mid = g_mid.m
        mid_of_old = np.full(m_old, -1, np.int64)
        mid_of_old[~is_del] = np.searchsorted(mid_keys, old_keys[~is_del])

        # triangle list and support delta (each lost row exactly once)
        lost_mask = is_del[tri_old].any(axis=1) if tri_old.size else \
            np.zeros(0, bool)
        lost = tri_old[lost_mask]
        tri_mid = mid_of_old[tri_old[~lost_mask]] if tri_old.size else \
            np.zeros((0, 3), np.int64)
        S_mid = S_old[~is_del].astype(np.int64)
        seeds = np.zeros(0, np.int64)
        if lost.size:
            members = lost.ravel()
            keep = ~is_del[members]
            seeds = mid_of_old[members[keep]]
            np.subtract.at(S_mid, seeds, 1)
        S_mid = S_mid.astype(np.int32)
        T_mid = T_old[~is_del].copy()

        # Deletions only lower trussness, so the old values are a valid
        # upper bound on the new decomposition and the local h-index
        # descent (Sarıyüce et al.) repairs exactly, discovering the
        # affected set lazily — the a-priori connectivity closure is far
        # too coarse on dense-core graphs, where every >=k level class is
        # one triangle-connected blob.
        if seeds.size:
            if np.unique(seeds).size > limit:
                return None         # repair would touch too much: recompute
            inc_mid = _Incidence(tri_mid, m_mid)
            if not _h_descent(inc_mid, T_mid, seeds, totals, limit):
                return None         # descent cascaded past local_frac
        return g_mid, T_mid, S_mid, tri_mid

    # ------------------------------------------------------ insertion phase --
    def _apply_insertions(self, state, new_keys, I_keys, n, limit, totals,
                          insert_mode):
        """G → G + I, built off to the side (committed state untouched).

        Builds the one new CSR, maps the mid-state values into the new edge
        space, and dispatches on ``insert_mode``: ``"sequential"`` repairs
        one edge at a time (the +1-per-insertion locality bound, with
        not-yet-inserted edges masked absent), ``"batched"`` repairs the
        whole batch against one merged candidate region (§13).  Returns the
        repaired ``(g, T, S, tri)`` state tuple, or ``None`` to request
        full fallback.
        """
        g_mid, T_mid, S_mid, tri_mid = state
        mid_keys = edge_keys(g_mid.El[:, 0].astype(np.int64),
                             g_mid.El[:, 1].astype(np.int64), n)
        E_new = np.stack([new_keys // n, new_keys % n], axis=1)
        g_new = build_csr(E_new, n)
        m_new = g_new.m
        new_of_mid = np.searchsorted(new_keys, mid_keys)
        ins_new = np.searchsorted(new_keys, I_keys)

        T_cur = np.full(m_new, -1, np.int64)
        T_cur[new_of_mid] = T_mid
        S_cur = np.zeros(m_new, np.int64)
        S_cur[new_of_mid] = S_mid
        present = np.zeros(m_new, bool)
        present[new_of_mid] = True

        tri_static = new_of_mid[tri_mid] if tri_mid.size else \
            np.zeros((0, 3), np.int64)
        inc_static = _Incidence(tri_static, m_new)
        if insert_mode == "batched":
            side_rows = self._insert_batched(
                g_new, inc_static, ins_new, T_cur, S_cur, present, limit,
                totals)
        else:
            side_rows = self._insert_sequential(
                g_new, inc_static, ins_new, T_cur, S_cur, present, limit,
                totals)
        if side_rows is None:
            return None
        tri_new = np.concatenate([tri_static, side_rows]) \
            if side_rows.size else tri_static
        return g_new, T_cur, S_cur.astype(np.int32), tri_new

    def _insert_sequential(self, g_new, inc_static, ins_new, T_cur, S_cur,
                           present, limit, totals):
        """One pinned-boundary re-peel per inserted edge (the parity oracle).

        Mutates ``T_cur``/``S_cur``/``present`` in the new edge space;
        returns the accumulated new triangle rows, or ``None`` to request
        full fallback.
        """
        m_new = g_new.m
        side_rows = np.zeros((0, 3), np.int64)

        for e_i in ins_new:
            present[e_i] = True
            # triangles gained by this one insertion (partners must already
            # be present — triangles with a not-yet-inserted edge are born
            # later, at that edge's own step)
            a, p2, p3 = triangles_through(g_new, np.array([e_i]))
            keep = present[p2] & present[p3]
            p2, p3 = p2[keep], p3[keep]
            S_cur[e_i] += p2.shape[0]
            np.add.at(S_cur, p2, 1)
            np.add.at(S_cur, p3, 1)
            if p2.size:
                rows = np.sort(np.stack(
                    [np.full(p2.shape[0], e_i, np.int64), p2, p3], axis=1),
                    axis=1)
                side_rows = np.concatenate([side_rows, rows])

            # affected region: one insertion moves any trussness by at most
            # one, so UB = min(S+2, T+1); an edge at level k can rise only
            # if connected to e_i through {UB >= k+1} — every such path
            # runs through e_i itself, so the levels to scan are capped by
            # e_i's own new trussness, bounded by its h-operator value
            # under UB (much tighter than S+2 in dense cores).
            UB = np.where(T_cur >= 0,
                          np.minimum(S_cur + 2, T_cur + 1), S_cur + 2)
            UB[~present] = 0             # absent edges block every path
            k_cap = int(self._h_cap(e_i, UB, inc_static, side_rows)) - 1
            cand = np.zeros(m_new, bool)
            for k in np.unique(T_cur[present & (T_cur >= 2)]):
                if k > k_cap:
                    break
                allowed = UB >= k + 1
                totals["passes"] += 1
                reach = _tri_bfs(inc_static, side_rows,
                                 np.array([e_i]), allowed)
                cand[reach[T_cur[reach] == k]] = True
                size = int(cand.sum())
                if size > limit:
                    totals["insert_candidates"] = max(
                        totals["insert_candidates"] or 0, size)
                    return None
            cand[e_i] = True
            A = np.nonzero(cand)[0]
            totals["insert_candidates"] = max(
                totals["insert_candidates"] or 0, int(A.size))
            if A.size > limit or totals["affected"] + A.size > limit:
                return None    # cumulative local work past paying: recompute
            tau = self._region_peel(g_new, inc_static, side_rows, A, S_cur,
                                    T_cur, totals, live_mask=present)
            T_cur[A] = tau

        return side_rows

    def _insert_batched(self, g_new, inc_static, ins_new, T_cur, S_cur,
                        present, limit, totals):
        """All insertions as one repair: one merged candidate region (§13).

        Every inserted edge goes present at once, the batch's new triangles
        land as one deduplicated support delta, and the per-edge
        level-filtered BFS regions are merged by seeding every inserted
        edge into the *same* traversal — one region, one pinned exterior
        boundary, one compacted re-peel dispatch.  The level filter uses
        the batch bound ``UB = min(S + 2, T + b)`` (a batch of ``b``
        insertions raises any trussness by at most ``b``), scanning levels
        up to the largest inserted-edge h-cap.  Mutates
        ``T_cur``/``S_cur``/``present``; returns the new triangle rows, or
        ``None`` to request full fallback.
        """
        m_new = g_new.m
        present[ins_new] = True

        # triangles born with the batch: every triangle of the new graph
        # through an inserted edge (all partners are present now), each
        # exactly once — triangles_through reports one row per inserted
        # member, so sort + unique dedupes multi-inserted-edge triangles
        a, p2, p3 = triangles_through(g_new, ins_new)
        keep = present[p2] & present[p3]
        a, p2, p3 = a[keep], p2[keep], p3[keep]
        if a.size:
            side_rows = np.unique(
                np.sort(np.stack([a, p2, p3], axis=1), axis=1), axis=0)
            np.add.at(S_cur, side_rows[:, 0], 1)
            np.add.at(S_cur, side_rows[:, 1], 1)
            np.add.at(S_cur, side_rows[:, 2], 1)
        else:
            side_rows = np.zeros((0, 3), np.int64)

        # batch bound: b insertions move any trussness up by at most b, so
        # UB = min(S+2, T+b) dominates every new value; an edge at level k
        # can rise only through a new-graph (k+1)-truss that contains an
        # inserted edge, so {UB >= k+1}-reachability from the batch merges
        # the per-edge candidate regions, and the levels to scan are capped
        # by the largest inserted-edge h-cap under UB.
        b = int(ins_new.shape[0])
        UB = np.where(T_cur >= 0, np.minimum(S_cur + 2, T_cur + b), S_cur + 2)
        UB[~present] = 0
        k_cap = max((int(self._h_cap(int(e_i), UB, inc_static, side_rows))
                     for e_i in ins_new), default=2) - 1
        cand = np.zeros(m_new, bool)
        for k in np.unique(T_cur[present & (T_cur >= 2)]):
            if k > k_cap:
                break
            allowed = UB >= k + 1
            totals["passes"] += 1
            reach = _tri_bfs(inc_static, side_rows, ins_new, allowed)
            cand[reach[T_cur[reach] == k]] = True
            size = int(cand.sum())
            if size > limit:
                totals["insert_candidates"] = size
                return None
        cand[ins_new] = True
        A = np.nonzero(cand)[0]
        totals["insert_candidates"] = int(A.size)
        if A.size > limit or totals["affected"] + A.size > limit:
            return None        # merged region past paying: recompute
        tau = self._region_peel(g_new, inc_static, side_rows, A, S_cur,
                                T_cur, totals, live_mask=present)
        T_cur[A] = tau
        return side_rows

    @staticmethod
    def _h_cap(e_i: int, UB: np.ndarray, inc: _Incidence,
               side: np.ndarray) -> int:
        """Upper bound on the inserted edge's new trussness: its h-operator
        value under the per-edge upper bounds (h is monotone in partner
        values, so this dominates the true value)."""
        rows = inc.tri[inc.rows_of(np.array([e_i]))]
        if side.size:
            rows = np.concatenate([rows, side[(side == e_i).any(axis=1)]])
        if rows.size == 0:
            return 2
        others = rows[rows != e_i].reshape(-1, 2)
        val = np.sort(np.minimum(UB[others[:, 0]], UB[others[:, 1]]) - 2)[::-1]
        rank = np.arange(val.shape[0], dtype=np.int64) + 1
        return 2 + int(np.maximum(np.minimum(val, rank), 0).max(initial=0))

    # ------------------------------------------------------------ region peel --
    def _region_peel(self, g: CSRGraph, inc: _Incidence, side: np.ndarray,
                     A: np.ndarray, S_vec: np.ndarray, T_fix: np.ndarray,
                     totals, live_mask: np.ndarray | None = None):
        """Re-peel region ``A`` with its exterior triangle partners pinned
        at their known death level.  Returns the new peel values + 2 for
        ``A`` (same order).  ``live_mask`` masks absent edges (insertion
        phase).  Dispatches to the host mirror for small regions and to the
        compacted ``peel_live_subset`` above ``host_peel_max``."""
        m = g.m
        rows = inc.tri[np.unique(inc.rows_of(A))] if inc.tri.size else \
            np.zeros((0, 3), np.int64)
        if side.size:
            hit = np.isin(side, A).any(axis=1)
            rows = np.concatenate([rows, side[hit]])
        if live_mask is not None and rows.size:
            rows = rows[live_mask[rows].all(axis=1)]
        in_A = np.zeros(m, bool)
        in_A[A] = True
        flat = rows.ravel()
        boundary = np.unique(flat[~in_A[flat]]) if flat.size else \
            np.zeros(0, np.int64)
        totals["affected"] += int(A.size)
        totals["boundary"] += int(boundary.size)

        L = np.union1d(A, boundary)
        path = "host" if L.shape[0] <= self.host_peel_max else "device"
        chaos = fault_point("region",
                            rung="host" if path == "host" else self.mode)
        with spans.span("inc.region_peel", region=int(A.size),
                        boundary=int(boundary.size), path=path):
            tau_L = self._peel_region(g, L, in_A, rows, S_vec, T_fix, path)
        if chaos == "corrupt" and boundary.size:
            # injected corruption (testing/chaos.py): bump one pinned slot so
            # the replay invariant below is guaranteed to trip — exercising
            # the detect → quarantine → rebuild path without ever letting a
            # wrong value reach committed state
            tau_L = tau_L.copy()
            tau_L[np.searchsorted(L, boundary[0])] += 1
        # replay invariant: pinned edges must die exactly at their schedule.
        # A real raise (not a bare assert, which -O strips): a violation
        # means the re-peel would commit corrupt trussness into the handle.
        if not np.array_equal(tau_L[~in_A[L]], T_fix[boundary]):
            raise IntegrityError(
                "incremental re-peel integrity violation: a pinned boundary "
                "edge left its death level — please report this graph")
        return tau_L[np.searchsorted(L, A)]

    def _peel_region(self, g: CSRGraph, L: np.ndarray, in_A: np.ndarray,
                     rows: np.ndarray, S_vec: np.ndarray, T_fix: np.ndarray,
                     path: str) -> np.ndarray:
        """Peel values + 2 of the region-plus-boundary edges ``L`` (sorted),
        boundary edges (``~in_A``) pinned at their death level."""
        S0 = np.where(in_A[L], S_vec[L], T_fix[L] - 2)
        if path == "host":
            # compact host path: local ids preserve the global id order, so
            # the tie-break picks the same winners
            lmap = np.full(g.m, -1, np.int64)
            lmap[L] = np.arange(L.shape[0])
            S_fin = _host_peel(L.shape[0], lmap[rows] if rows.size else
                               np.zeros((0, 3), np.int64),
                               S0, np.ones(L.shape[0], bool), ~in_A[L])
            return S_fin + 2
        # larger regions reuse the live-edge compaction machinery
        # (core.pkt.peel_live_subset): the region is gathered into a
        # compacted pow2-bucketed edge space — work bounded by |L|, not m —
        # with boundary edges pinned at their death level, and the driver
        # keeps compacting as the region itself peels away
        S_fin = peel_live_subset(
            g.El, L, S0, ~in_A[L], chunk=self.chunk, mode=self.mode,
            table_mode=self.table_mode,
            compact_frac=self.compact_frac, compact_min=self.compact_min)
        return S_fin.astype(np.int64) + 2

    # ---------------------------------------------------------- internals --
    def _hier_update(self, old_keys, I_keys, T_old, posn, ok, kn) -> None:
        """Carry the community index across a *local* repair (DESIGN.md §11).

        Every edge the repair touched bounds the levels whose community
        structure can differ: ``k_hi`` is the maximum trussness involved in
        any insertion, deletion, or trussness change (old or new value).
        Levels above ``k_hi`` keep their exact partition — only edge ids
        shifted — so they are remapped in O(m); levels at or below come
        back dirty and rebuild lazily on next query.  Full rebuilds (the
        past-``local_frac`` path) drop the index in ``_full_rebuild``.
        """
        m_before = old_keys.shape[0]
        m_after = self.g.m
        if m_after == 0 or kn is None or self._hier is None:
            self._hier = None
            return
        k_hi = 1
        if (~ok).any():                      # deletions: old death levels
            k_hi = max(k_hi, int(T_old[~ok].max()))
        t_new = self.T[posn[ok]]
        t_old = T_old[ok]
        diff = t_new != t_old
        if diff.any():                       # changed: both old and new
            k_hi = max(k_hi, int(t_old[diff].max()), int(t_new[diff].max()))
        if I_keys.size:                      # insertions: their new levels
            k_hi = max(k_hi, int(self.T[np.searchsorted(kn, I_keys)].max()))
        old_to_new = np.full(m_before, -1, np.int64)
        old_to_new[np.nonzero(ok)[0]] = posn[ok]
        self._hier = self._hier.remapped(self.T, self.tri, old_to_new, k_hi)

    @staticmethod
    def _batch_keys(batch: np.ndarray, n: int) -> np.ndarray:
        if batch.size == 0:
            return np.zeros(0, np.int64)
        lo = np.minimum(batch[:, 0], batch[:, 1])
        hi = np.maximum(batch[:, 0], batch[:, 1])
        return np.unique(edge_keys(lo, hi, n))

    def _commit(self, g_new: CSRGraph, T_new: np.ndarray, S_new: np.ndarray,
                tri_new: np.ndarray) -> None:
        self.g = g_new
        self.T = T_new.astype(np.int64)
        self.S = S_new.astype(np.int32)
        self.tri = tri_new.astype(np.int64)

    def _full_rebuild(self, E: np.ndarray) -> None:
        """From-scratch decomposition through the standard (KCO) pipeline,
        as a ``repro.inc.full_rebuild`` span (``pkt``'s spans nest in it)."""
        self._hier = None        # full rebuild: community index rebuilt lazily
        with spans.span("inc.full_rebuild", m=int(E.shape[0])):
            g = build_csr(E, self.n)
            if g.m == 0:
                self.open_phases = {}
                self._commit(g, np.zeros(0, np.int64), np.zeros(0, np.int32),
                             np.zeros((0, 3), np.int64))
                return
            perm = degeneracy_order(E, self.n)
            r_edges = relabel(E, perm)
            gr = build_csr(r_edges, self.n)
            res = pkt(gr, chunk=self.chunk, mode=self.mode,
                      table_mode=self.table_mode,
                      compact_frac=self.compact_frac,
                      compact_min=self.compact_min, phase_timings=True,
                      triangles=True)
            #: phase breakdown of the most recent full (re)build — the open
            #: path's table-build vs support vs peel cost (benchmarks read it)
            self.open_phases = dict(res.phases or {})
            u = g.El[:, 0].astype(np.int64)
            v = g.El[:, 1].astype(np.int64)
            rl, rh = perm[u], perm[v]
            keys = edge_keys(np.minimum(rl, rh), np.maximum(rl, rh), self.n)
            # gr's id of each of g's edges
            pos = align_to_input(np.arange(gr.m), gr, None, self.n, keys=keys)
            with spans.span("inc.triangle_list") as sp:
                rows, hits = res.triangles.read()
                sp.set(rows=rows.shape[0], table_hits=hits)
                g_id = np.empty(g.m, np.int32)
                g_id[pos] = np.arange(g.m, dtype=np.int32)
                tri = _canonical_triangles(g_id[rows], g.m)
            self._commit(g, res.trussness[pos], res.support[pos], tri)

    def check_invariants(self, *, sample: int = 64, seed: int = 0) -> int:
        """Cheap consistency check over a sampled edge set (DESIGN.md §15).

        Verifies, for a deterministic sample of ``sample`` edges (all edges
        when ``sample >= m``):

        1. the maintained support ``S[e]`` equals the edge's row count in
           the maintained triangle list;
        2. trussness bounds ``2 <= T[e] <= S[e] + 2``;
        3. the truss h-operator fixpoint ``T[e] == h(T)[e]`` — a necessary
           condition of a correct decomposition that any single-edge
           corruption of ``T`` violates at the edge itself or a triangle
           partner;
        4. sampled triangle rows are strictly increasing and in-range.

        Cost is one incidence-CSR build (O(|tri|)) plus O(sample) work —
        orders of magnitude below a re-peel — so the scheduler runs it
        after every repair.  It is *sampled*, not a proof: ``verify()``
        remains the full oracle.

        Returns:
            The number of edges checked.

        Raises:
            IntegrityError: any check fails (the handle should be healed
                via :meth:`rebuild`).
        """
        m = self.g.m
        if m == 0:
            return 0
        if sample >= m:
            idx = np.arange(m, dtype=np.int64)
        else:
            # deterministic, seed-keyed sample without a bias toward low ids
            rng = np.random.default_rng(seed)
            idx = np.unique(rng.choice(m, size=sample, replace=False))
        inc = _Incidence(self.tri, m)
        cnt = inc.off[idx + 1] - inc.off[idx]
        if not np.array_equal(cnt, self.S[idx].astype(np.int64)):
            raise IntegrityError(
                "invariant violation: maintained support disagrees with the "
                "triangle list on the sampled edges")
        if (self.T[idx] < 2).any() or (self.T[idx] > self.S[idx] + 2).any():
            raise IntegrityError(
                "invariant violation: trussness outside [2, support + 2] on "
                "the sampled edges")
        if not np.array_equal(_h_values(inc, self.T, idx), self.T[idx]):
            raise IntegrityError(
                "invariant violation: trussness is not an h-operator "
                "fixpoint on the sampled edges")
        if self.tri.size:
            rows = self.tri[inc.rows_of(idx)] if cnt.sum() else \
                np.zeros((0, 3), np.int64)
            if rows.size and not (
                    (rows[:, 0] < rows[:, 1]).all()
                    and (rows[:, 1] < rows[:, 2]).all()
                    and rows.min() >= 0 and rows.max() < m):
                raise IntegrityError(
                    "invariant violation: malformed triangle rows incident "
                    "to the sampled edges")
        return int(idx.shape[0])

    def rebuild(self) -> None:
        """Self-healing hook: rediscover all state from the retained CSR.

        Discards trussness, support, triangle list, and the community-index
        cache, and recomputes them with a from-scratch ``pkt`` over the
        current edge list — the recovery action for integrity violations
        (DESIGN.md §15).  The edge set itself is preserved exactly.
        """
        self._full_rebuild(self.edges)

    def verify(self) -> bool:
        """Debug helper: does the maintained state match a from-scratch PKT?"""
        if self.g.m == 0:
            return True
        from repro.core.pkt import truss_pkt
        ref = truss_pkt(self.edges)
        S_ref = support_mod.compute_support(self.g)
        if self.tri.size:
            tri_ok = (self.tri.shape[0] == int(S_ref.sum()) // 3
                      and (self.tri[:, 0] < self.tri[:, 1]).all()
                      and (self.tri[:, 1] < self.tri[:, 2]).all())
        else:
            tri_ok = int(S_ref.sum()) == 0
        return (np.array_equal(self.T, ref)
                and np.array_equal(self.S, S_ref) and bool(tri_ok))
