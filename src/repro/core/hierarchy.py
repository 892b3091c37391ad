"""Truss community index — the nested triangle-connected k-truss hierarchy.

The paper motivates truss decomposition by community detection, and Wang &
Cheng (Truss Decomposition in Massive Networks) define the query object that
serving actually needs: a *k-truss community* is a triangle-connected
component of the edges with trussness >= k — two edges belong together iff
they are linked by a chain of triangles all of whose edges survive at level
k.  Sariyuce et al. (Local Algorithms for Hierarchical Dense Subgraph
Discovery) observe these components nest as k grows, so the right serving
structure is a *hierarchy index* built once per decomposition and queried
many times (DESIGN.md §11):

  * **Per-level labels** — for each level k in [2, k_max], every live edge
    (trussness >= k) carries the id of the *minimum edge in its
    triangle-connected component*.  The min-id representative makes the
    labeling canonical: any correct builder produces bitwise-identical
    arrays, which is what the device/host parity gate checks.
  * **Parent links** — level-k communities refine level-(k-1) communities
    (every active-at-k triangle is active at k-1), so each community's
    parent is just the (k-1)-label of its representative edge.
  * **Two builders, one contract** (the PR-4 ``table_mode`` pattern):
    ``mode="device"`` floods min-labels over the triangle rows with a jitted
    scatter-min + pointer-jumping loop (O(log diameter) rounds);
    ``build_all`` runs a peel-order level sweep, finest level first, where
    each level warm-starts from the next-finer labels and a host-side
    convergence pre-check skips the dispatch entirely when the warm labels
    are already the fixed point (DESIGN.md §16 has the parity argument).
    ``mode="host"`` is an independent union-find oracle (union-by-min over
    triangles sorted by level, shared across levels top-down).  Both
    converge to the same canonical labels.

Triangle connectivity comes from the decomposition's triangle list — the
same (T, 3) edge-id rows a handle's full rebuild selects from its peel
table and maintains across updates, so a handle's index build does zero
extra triangle work (``hierarchy_from_graph`` enumerates them with
``core.truss_inc.triangle_list``).

Levels build lazily and cache; ``core/truss_inc.py`` keeps a handle's index
alive across ``update`` batches by remapping the untouched high levels
(edge-id translation only) and marking the levels the repair could have
reached (k <= ``k_hi``) dirty for lazy rebuild — see
``TrussHierarchy.remapped``.  The serving wrapper is
``serve.truss_engine.TrussHandle.communities / community``.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from repro import spans
from repro.kernels.wedge_common import next_pow2
from repro.testing.chaos import fault_point

#: where per-level labels are computed: jitted label propagation on device
#: (the serving path) or the independent host union-find (the parity oracle)
HIER_MODES = ("device", "host")


# ------------------------------------------------------- device label flood --

@functools.partial(jax.jit, static_argnames=("sz", "mp"))
def _labelprop(tri_all, lvl_all, start, k, L0, *, sz: int, mp: int):
    """Min-label flood over the *representative graph* to the fixed point.

    ``tri_all``/``lvl_all`` are the full level-sorted triangle table and
    its per-row levels (min member trussness); the flood runs on the
    ``sz``-row window at dynamic offset ``start`` (every row that can
    still merge components at level ``k`` — see the stratum windowing
    in ``_build_device``; slicing in-jit saves two eager dispatches per
    level).  ``k`` is the dynamic level, ``L0`` the (mp,) initial
    labels (live edges: any in-component id <= their own — warm starts
    pass a finer level's *flat* component minima; dead and padding
    slots: themselves).

    Each round gathers every active row's current representatives
    ``r = L[tri]``, scatter-mins the row's 3-way representative-label
    minimum into ``L[r]`` — the union step, expressed on the component
    graph so already-merged rows are no-ops — then pointer-jumps
    ``L <- min(L, L[L])``.  Labels only decrease and always point at
    in-component edge ids, so the fixed point is exactly the flat
    component-minimum labeling: at convergence ``L[L[e]] == L[e]``
    (labels are roots) and every active row's members share one root
    (DESIGN.md §16 gives the argument).  Warm-started levels converge
    in O(log merge-chain) rounds over only their fresh stratum.
    Returns the labels and the number of rounds run.
    """
    tri = jax.lax.dynamic_slice(tri_all, (start, 0), (sz, 3))
    act = jax.lax.dynamic_slice(lvl_all, (start,), (sz,)) >= k
    sink = jnp.int32(mp - 1)

    def body(state):
        L, _, rounds = state
        r = L[tri]
        lm = jnp.min(L[r], axis=1)
        idx = jnp.where(act[:, None], r, sink)
        lmw = jnp.where(act, lm, sink)
        L2 = (L.at[idx[:, 0]].min(lmw)
               .at[idx[:, 1]].min(lmw)
               .at[idx[:, 2]].min(lmw))
        L2 = jnp.minimum(L2, L2[L2])
        return L2, L, rounds + 1

    def cond(state):
        L, prev, _ = state
        return jnp.any(L != prev)

    L, _, rounds = jax.lax.while_loop(
        cond, body, (L0, jnp.full_like(L0, -1), jnp.int32(0)))
    return L, rounds


# Host-side flood seeding: active sets up to _SEED_ROWS_MAX rows run up to
# _SEED_ROUNDS of the flood body on the host (np.minimum.at is ~100
# ns/element, so larger sets would pay more on the host than the device
# rounds they save), skipping the device dispatch entirely when the rounds
# reach the flood's fixed point.  Larger levels with a small *fresh* stratum
# still get one host round folded into their warm start.
_SEED_ROWS_MAX = 4096
_SEED_ROUNDS = 2

#: The device flood's row window is a power of two no smaller than this, so
#: the small strata of a warm-started level share one compiled program
#: instead of one per power of two below it (a window's extra rows are
#: finer rows, no-ops under the warm start).
_FLOOD_MIN_ROWS = 4096


# ------------------------------------------------------ host union-find oracle

def _uf_find(parent: np.ndarray, x: int) -> int:
    while parent[x] != x:
        parent[x] = parent[parent[x]]
        x = parent[x]
    return int(x)


def _uf_union_min(parent: np.ndarray, a: int, b: int) -> None:
    """Union with the *smaller root winning* — the component root is then
    always the component's minimum edge id, the canonical representative."""
    ra, rb = _uf_find(parent, a), _uf_find(parent, b)
    if ra != rb:
        if ra < rb:
            parent[rb] = ra
        else:
            parent[ra] = rb


def _uf_roots(parent: np.ndarray, idx: np.ndarray) -> np.ndarray:
    """Vectorized root lookup for an index array (no mutation needed for
    correctness; unions keep doing their own path compression)."""
    r = parent[idx]
    while True:
        rr = parent[r]
        if np.array_equal(rr, r):
            return r
        r = rr


def host_level_labels(m: int, trussness: np.ndarray, tri: np.ndarray,
                      tri_lvl: np.ndarray, k: int) -> np.ndarray:
    """One level's labels by a fresh union-find — the standalone oracle."""
    labels = np.full(m, -1, np.int64)
    live = np.nonzero(trussness >= k)[0]
    if live.size == 0:
        return labels
    parent = np.arange(m, dtype=np.int64)
    for a, b, c in tri[tri_lvl >= k]:
        _uf_union_min(parent, int(a), int(b))
        _uf_union_min(parent, int(a), int(c))
    labels[live] = _uf_roots(parent, live)
    return labels


# --------------------------------------------------------------- the index --

class TrussHierarchy:
    """Nested k-truss community index over one finished decomposition.

    Construct from per-edge ``trussness`` (aligned to the graph's canonical
    edge rows) and the (T, 3) triangle list in the same edge-id space.
    Levels are k = 2 .. ``k_max``; each builds lazily on first access and is
    cached.  ``stats`` counts the work actually done (levels built per mode,
    levels carried across updates by remap, flood rounds are implicit in the
    device dispatch).
    """

    def __init__(self, trussness: np.ndarray, triangles: np.ndarray, *,
                 mode: str = "device"):
        if mode not in HIER_MODES:
            raise ValueError(
                f"mode must be one of {HIER_MODES}, got {mode!r}")
        self.mode = mode
        self.T = np.asarray(trussness, dtype=np.int64)
        self.m = int(self.T.shape[0])
        tri = np.asarray(triangles, dtype=np.int64)
        if tri.size == 0:
            tri = np.zeros((0, 3), np.int64)
        if tri.size and int(tri.max()) >= self.m:
            raise ValueError(
                f"triangle row references edge id {int(tri.max())} beyond "
                f"m={self.m}")
        self.tri = tri
        self.tri_lvl = (self.T[tri].min(axis=1) if tri.size
                        else np.zeros(0, np.int64))
        self.k_max = int(self.T.max(initial=1))
        self._labels: list[np.ndarray | None] = \
            [None] * max(0, self.k_max - 1)
        self._dev = None          # (tri_dev, lvl_dev, mp) device upload cache
        self._uf = None           # (parent, order, ptr, k_at) host UF state
        self.stats = {"device_levels": 0, "host_levels": 0,
                      "remapped_levels": 0, "converged_levels": 0,
                      "seeded_levels": 0}

    # ---------------------------------------------------------- level access

    @property
    def levels(self) -> range:
        """The populated levels: k = 2 .. k_max (empty when m == 0)."""
        return range(2, self.k_max + 1)

    def level_labels(self, k: int) -> np.ndarray:
        """(m,) int64 labels at level ``k``: for each edge with trussness
        >= k the minimum edge id of its triangle-connected component, else
        -1.  Built lazily (and cached) by the configured ``mode``."""
        k = int(k)
        if k < 2 or k > self.k_max:
            return np.full(self.m, -1, np.int64)
        li = k - 2
        if self._labels[li] is None:
            # one span per level built: ``rows`` the triangle rows entering
            # at k since the warm level, ``rounds`` the device flood's
            # rounds (0 where the host closed the level)
            with spans.span("hier.level", k=k, mode=self.mode) as sp:
                self._labels[li] = (self._build_device(k, sp)
                                    if self.mode == "device"
                                    else self._build_host(k, sp))
        return self._labels[li]

    def build_all(self) -> "TrussHierarchy":
        """Materialize every level eagerly, finest (highest k) first.

        Both modes sweep the same peel order: device mode warm-starts every
        level from the next-finer labels and skips the dispatch when the
        convergence pre-check proves the warm start is already the fixed
        point (the index-build cost ``benchmarks/hier_bench.py`` measures);
        host mode extends the shared top-down union-find with exactly each
        level's own triangle stratum (never a fresh rebuild).
        """
        for k in sorted(self.levels, reverse=True):
            if self._labels[k - 2] is None:
                self.level_labels(k)
        return self

    # ------------------------------------------------------------- queries --

    def communities(self, k: int) -> list[np.ndarray]:
        """Sorted edge-id arrays of every level-``k`` community, ordered by
        representative (= minimum member) edge id."""
        labels = self.level_labels(k)
        live = np.nonzero(labels >= 0)[0]
        if live.size == 0:
            return []
        order = np.argsort(labels[live], kind="stable")
        live = live[order]
        cuts = np.nonzero(np.diff(labels[live]))[0] + 1
        return np.split(live, cuts)

    def community_of(self, edge_id: int, k: int) -> np.ndarray:
        """Edge ids of the level-``k`` community containing ``edge_id``
        (empty when the edge is below level k)."""
        labels = self.level_labels(k)
        edge_id = int(edge_id)
        if not 0 <= edge_id < self.m or labels[edge_id] < 0:
            return np.zeros(0, np.int64)
        return np.nonzero(labels == labels[edge_id])[0].astype(np.int64)

    def parents(self, k: int) -> tuple[np.ndarray, np.ndarray]:
        """(reps, parent_reps): each level-``k`` community's representative
        and the representative of the level-(k-1) community containing it.
        At k == 2 the parents array equals the reps (no coarser level)."""
        labels = self.level_labels(k)
        reps = np.unique(labels[labels >= 0])
        if k <= 2 or reps.size == 0:
            return reps, reps.copy()
        return reps, self.level_labels(k - 1)[reps]

    # ------------------------------------------------------- device builder --

    def _pad_dims(self) -> tuple[int, int]:
        # Labels are pure jnp (no kernel tiling), so the label array only
        # needs *size-class* padding for compile reuse, not a full pow2:
        # round m+1 up to the nearest of {0.75 * 2^b, 2^b}.  Half-step
        # classes keep the O(log m) distinct compiled shapes while capping
        # padding waste at 33% instead of 100% (m itself a pow2 is common).
        p = max(8, next_pow2(self.m + 1))
        mp = 3 * p // 4 if self.m + 1 <= 3 * p // 4 else p
        tp = max(8, next_pow2(max(1, self.tri.shape[0])))
        return mp, tp

    def _device_tables(self):
        """Upload the padded triangle table once per hierarchy.

        Rows are sorted by level *descending* (stable), so the rows active
        at any level ``k`` form a prefix — each flood then dispatches on the
        pow2-padded active prefix only, instead of streaming the whole
        table per level.  Scatter-min is order-insensitive, so the
        reordering cannot change any label.
        """
        if self._dev is None:
            mp, tp = self._pad_dims()
            order = np.argsort(-self.tri_lvl, kind="stable")
            tri = np.full((tp, 3), mp - 1, np.int32)
            tri[: self.tri.shape[0]] = self.tri[order]
            lvl = np.full(tp, -1, np.int32)
            lvl[: self.tri.shape[0]] = self.tri_lvl[order]
            self._dev = (jnp.asarray(tri), jnp.asarray(lvl), mp)
        return self._dev

    def _warm_level(self, k: int) -> int:
        """Nearest already-built level finer than ``k`` (``k_max + 1`` when
        nothing finer is built — the cold, finest-level case)."""
        for jj in range(k + 1, self.k_max + 1):
            if self._labels[jj - 2] is not None:
                return jj
        return self.k_max + 1

    def _init_labels(self, k: int, mp: int, j: int) -> np.ndarray:
        """Initial (mp,) int32 labels for level ``k`` warm-started from
        level ``j`` (see ``_warm_level``): live edges take the finer
        level's labels where defined (in-component ids, so the flood only
        has fewer rounds to run); dead and padding slots point at
        themselves."""
        L0 = np.arange(mp, dtype=np.int32)
        if j <= self.k_max:
            warm = self._labels[j - 2]
            fine = warm >= 0
            L0[:self.m][fine] = warm[fine]
        dead = self.T < k
        L0[:self.m][dead] = np.nonzero(dead)[0]
        return L0

    def _build_device(self, k: int, sp) -> np.ndarray:
        fault_point("hierarchy", rung="device")
        j = self._warm_level(k)
        fresh = (self.tri_lvl >= k) & (self.tri_lvl < j)
        sp.set(rows=int(np.count_nonzero(fresh)), rounds=0)
        if not fresh.any():
            # Empty-stratum shortcut: no triangle enters between j and k,
            # so no merge is possible — level k's labels are level j's plus
            # self-labels for the newly live (triangle-isolated at k)
            # edges.  Skips the O(m) label-array construction entirely.
            self.stats["converged_levels"] += 1
            if j <= self.k_max:
                labels = self._labels[j - 2].copy()
                newly = (self.T >= k) & (labels < 0)
            else:
                labels = np.full(self.m, -1, np.int64)
                newly = self.T >= k
            labels[newly] = np.nonzero(newly)[0]
            return labels
        mp, _ = self._pad_dims()
        L0 = self._init_labels(k, mp, j)
        hi = int(np.count_nonzero(self.tri_lvl >= k))
        if hi <= _SEED_ROWS_MAX:
            # Tiny active sets pay more in per-round device dispatch latency
            # than their arithmetic is worth, so run up to _SEED_ROUNDS of
            # the *exact* flood body on the host — gather representatives
            # ``r = L0[tri]``, scatter-min each row's 3-way representative-
            # label minimum into ``L0[r]``, pointer-jump — checking the
            # flood's own fixed-point condition between rounds (every active
            # row's representative labels homogeneous, L0 flat under the
            # jump).  When the check passes the while_loop body is the
            # identity, so skipping the dispatch returns bitwise-exactly
            # what the device would; when the rounds run out the seeded L0
            # ships to the device flood, which converges to the canonical
            # component minima from any in-component lower bound (§16).
            tra = self.tri[self.tri_lvl >= k]
            for seeds in range(_SEED_ROUNDS + 1):
                r = L0[tra]
                rl = L0[r]
                lm = rl.min(axis=1)
                if (bool((lm == rl.max(axis=1)).all())
                        and bool((L0[L0] >= L0).all())):
                    key = "seeded_levels" if seeds else "converged_levels"
                    self.stats[key] += 1
                    return self._finish(L0, k)
                if seeds == _SEED_ROUNDS:
                    break
                np.minimum.at(L0, r.ravel(), np.repeat(lm, 3))
                np.minimum(L0, L0[L0], out=L0)
        else:
            # Convergence pre-check (host, O(rows newly active since the
            # warm level)): rows active at the warm level j are triangle-
            # connected at j, so their three edges share one warm component
            # minimum; if every *newly* active row (k <= tri_lvl < j) is
            # also label-homogeneous under L0, the scatter-min pass cannot
            # change any label.  L0 is idempotent by construction (warm
            # labels are component minima at j, everything else
            # self-labels), so the pointer jump is a no-op too: L0 is the
            # flood's exact fixed point and the dispatch can be skipped
            # bitwise-safely (DESIGN.md §16).
            rows = L0[self.tri[fresh]]
            if bool((rows.min(axis=1) == rows.max(axis=1)).all()):
                self.stats["converged_levels"] += 1
                return self._finish(L0, k)
            if rows.shape[0] <= _SEED_ROWS_MAX:
                # Fold one flood round over the fresh stratum into the
                # warm start (the full active set is too large to check a
                # fixed point on, so no skip — the seed just spares the
                # device its first merge round).
                rl = L0[rows]
                lm = rl.min(axis=1)
                np.minimum.at(L0, rows.ravel(), np.repeat(lm, 3))
                np.minimum(L0, L0[L0], out=L0)
        tri_dev, lvl_dev, _ = self._device_tables()
        # Dispatch on the *fresh stratum* window only: the device rows are
        # sorted by level descending, so rows entering between the warm
        # level j and this level k occupy positions [count(lvl >= j),
        # count(lvl >= k)).  Rows finer than the window are no-ops under a
        # warm start (their members already share a flat label) and rows
        # coarser than it are masked by the flood's own ``tri_lvl >= k``
        # predicate, so pow2-rounding the window backward is bitwise-safe
        # while bounding distinct compiled flood shapes to O(log T).
        lo = int(np.count_nonzero(self.tri_lvl >= j))
        sz = min(int(tri_dev.shape[0]),
                 max(_FLOOD_MIN_ROWS, next_pow2(hi - lo)))
        start = max(0, hi - sz)
        L, rounds = _labelprop(tri_dev, lvl_dev, jnp.int32(start),
                               jnp.int32(k), jnp.asarray(L0), sz=sz, mp=mp)
        self.stats["device_levels"] += 1
        L, rounds = jax.device_get((L, rounds))
        sp.set(rounds=int(rounds))
        return self._finish(L, k)

    def _finish(self, L: np.ndarray, k: int) -> np.ndarray:
        labels = L[: self.m].astype(np.int64)
        labels[self.T < k] = -1
        return labels

    # --------------------------------------------------------- host builder --

    def _build_host(self, k: int, sp) -> np.ndarray:
        """Shared top-down union-find: triangles sorted by level descending
        are unioned once in total across all levels; each level snapshot is
        a vectorized root lookup.  The shared state is only valid while
        requests descend — once it has advanced past level ``k`` its
        partition includes unions from coarser levels, so a request *above*
        the frontier answers from a fresh single-level union-find instead
        (``build_all`` walks levels coarse-to-fine, paying the shared cost
        exactly once)."""
        fault_point("hierarchy", rung="host")
        self.stats["host_levels"] += 1
        sp.set(rows=int(np.count_nonzero(self.tri_lvl >= k)))
        if self._uf is not None and k > self._uf["k_at"]:
            return host_level_labels(self.m, self.T, self.tri,
                                     self.tri_lvl, k)
        if self._uf is None:
            order = np.argsort(-self.tri_lvl, kind="stable")
            self._uf = {"parent": np.arange(self.m, dtype=np.int64),
                        "order": order, "ptr": 0,
                        "k_at": self.k_max + 1}
        uf = self._uf
        parent, order = uf["parent"], uf["order"]
        ptr = uf["ptr"]
        while ptr < order.size and self.tri_lvl[order[ptr]] >= k:
            a, b, c = self.tri[order[ptr]]
            _uf_union_min(parent, int(a), int(b))
            _uf_union_min(parent, int(a), int(c))
            ptr += 1
        uf["ptr"] = ptr
        uf["k_at"] = k
        labels = np.full(self.m, -1, np.int64)
        live = np.nonzero(self.T >= k)[0]
        if live.size:
            labels[live] = _uf_roots(parent, live)
        return labels

    # -------------------------------------------------- update survival ------

    def remapped(self, trussness: np.ndarray, triangles: np.ndarray,
                 old_to_new: np.ndarray, k_hi: int) -> "TrussHierarchy":
        """The index after a *local* repair touched nothing above ``k_hi``.

        ``old_to_new`` maps this index's edge ids to the post-update ids
        (-1 for deleted edges).  Levels k > ``k_hi`` have an unchanged
        edge set and active-triangle set — every inserted/deleted edge and
        every trussness change sits at or below ``k_hi``, and a triangle's
        level is the min over its members — so their partition survives
        verbatim; only the ids need translating.  Canonical-form bonus: the
        surviving edges keep their relative order under the key-sorted id
        space, so the old component minimum maps exactly onto the new one
        and the translated labels stay canonical without a re-scan.  Levels
        <= ``k_hi`` come back dirty and rebuild lazily.
        """
        h = TrussHierarchy(trussness, triangles, mode=self.mode)
        old_to_new = np.asarray(old_to_new, dtype=np.int64)
        for k in range(max(int(k_hi) + 1, 2), h.k_max + 1):
            old = (self._labels[k - 2]
                   if k - 2 < len(self._labels) else None)
            if old is None:
                continue
            src = np.nonzero(old >= 0)[0]
            dst = old_to_new[src]
            if dst.size and dst.min(initial=0) < 0:
                # defensive: a live-above-k_hi edge vanished — the caller's
                # k_hi was wrong; fall back to a dirty level
                continue
            lab = np.full(h.m, -1, np.int64)
            lab[dst] = old_to_new[old[src]]
            h._labels[k - 2] = lab
            h.stats["remapped_levels"] += 1
        return h


def hierarchy_from_graph(g, trussness: np.ndarray, *,
                         mode: str = "device") -> TrussHierarchy:
    """Index a plain (graph, trussness) pair — enumerates the triangle list
    first.  Handles (``TrussEngine.open``) skip this: they already maintain
    the triangle list incrementally."""
    from repro.core.truss_inc import triangle_list

    return TrussHierarchy(trussness, triangle_list(g), mode=mode)
