"""Plain truss decomposition: the yardstick every cell is compared with.

Numpy only, and nothing of the program under test: edges are made
canonical here, triangles are listed here, and the peel is the textbook
bottom-up one (Cohen 2008; Wang and Cheng 2012): at support level ``k``
remove every live edge whose support in the remaining graph is at most
``k``, destroy each triangle through a removed edge once, lower the
support of its surviving edges, and repeat at the same ``k`` until no edge
is at or below it.  An edge removed at level ``k`` has trussness ``k + 2``.

``cascade=False`` is the benchmark's control: it moves to the next level
after one removal pass, so edges pushed to or below ``k`` inside a level
are peeled one level late.  It is the shortcut a faster peel is tempted
by, and it breaks the exactness every configuration promises.
"""

from __future__ import annotations

import numpy as np


def canonical(edges) -> np.ndarray:
    """The unique undirected edges of ``edges`` as (u < v) rows, sorted."""
    e = np.asarray(edges, dtype=np.int64).reshape(-1, 2)
    if (e[:, 0] == e[:, 1]).any():
        raise ValueError("self-loops have no trussness")
    lo, hi = np.minimum(e[:, 0], e[:, 1]), np.maximum(e[:, 0], e[:, 1])
    return np.unique(np.stack([lo, hi], axis=1), axis=0)


def edge_key(E: np.ndarray, n: int) -> np.ndarray:
    """One int64 key per (u < v) row, ordered as the rows of ``canonical``."""
    return E[:, 0] * np.int64(n) + E[:, 1]


def _segments(off: np.ndarray, idx: np.ndarray) -> np.ndarray:
    """Concatenated positions ``off[i] .. off[i+1]`` for every ``i`` in idx."""
    cnt = off[idx + 1] - off[idx]
    start = np.repeat(off[idx] - np.cumsum(cnt) + cnt, cnt)
    return start + np.arange(int(cnt.sum()), dtype=np.int64)


def triangles(E: np.ndarray, n: int) -> np.ndarray:
    """Every triangle of the canonical edge list ``E`` as (t, 3) edge ids."""
    m = E.shape[0]
    if m == 0:
        return np.zeros((0, 3), np.int64)
    deg = np.bincount(E.ravel(), minlength=n)
    # orient each edge from its lower (degree, id) end: out-degrees stay
    # small on skewed graphs, so the wedges below stay few
    low_first = (deg[E[:, 0]] < deg[E[:, 1]]) | (
        (deg[E[:, 0]] == deg[E[:, 1]]) & (E[:, 0] < E[:, 1]))
    src = np.where(low_first, E[:, 0], E[:, 1])
    dst = np.where(low_first, E[:, 1], E[:, 0])
    order = np.lexsort((dst, src))
    src, dst, eid = src[order], dst[order], order.astype(np.int64)
    off = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(src, minlength=n), out=off[1:])
    # each out-slot pairs with every later slot of the same source vertex
    pos = np.arange(m, dtype=np.int64)
    later = off[src + 1] - pos - 1
    a = np.repeat(pos, later)
    b = a + 1 + (np.arange(a.shape[0], dtype=np.int64)
                 - np.repeat(np.cumsum(later) - later, later))
    v, w = dst[a], dst[b]
    keys = edge_key(E, n)
    k3 = np.minimum(v, w) * np.int64(n) + np.maximum(v, w)
    at = np.minimum(np.searchsorted(keys, k3), m - 1)
    hit = keys[at] == k3
    return np.stack([eid[a[hit]], eid[b[hit]], at[hit]], axis=1)


def trussness(edges, *, cascade: bool = True) -> tuple[np.ndarray, np.ndarray]:
    """``(E, t)``: the canonical edges of ``edges`` and each one's trussness."""
    E = canonical(edges)
    m = E.shape[0]
    if m == 0:
        return E, np.zeros(0, np.int64)
    n = int(E.max()) + 1
    tri = triangles(E, n)
    S = np.bincount(tri.ravel(), minlength=m).astype(np.int64)
    # edge -> incident triangles, as CSR
    by_edge = np.argsort(tri.ravel(), kind="stable") // 3
    off = np.zeros(m + 1, np.int64)
    np.cumsum(np.bincount(tri.ravel(), minlength=m), out=off[1:])
    alive = np.ones(m, bool)
    tri_alive = np.ones(tri.shape[0], bool)
    out = np.zeros(m, np.int64)
    k, left = 0, m
    while left:
        frontier = np.nonzero(alive & (S <= k))[0]
        if frontier.size == 0:
            k = int(S[alive].min())
            continue
        out[frontier] = k + 2
        alive[frontier] = False
        left -= frontier.size
        hit = np.unique(by_edge[_segments(off, frontier)])
        hit = hit[tri_alive[hit]]
        tri_alive[hit] = False
        survivors = tri[hit].ravel()
        survivors = survivors[alive[survivors]]
        S -= np.bincount(survivors, minlength=m)
        if not cascade:
            k += 1
    return E, out
