"""Trivially-correct truss decomposition oracle (numpy + python sets).

Definitionally faithful and slow: for k = 3, 4, ... repeatedly delete edges
whose support inside the remaining subgraph is < k-2; edges deleted while
moving to k have trussness k-1. Used as the ground truth for property tests.
"""

from __future__ import annotations

import numpy as np


def support_naive(edges: np.ndarray, alive: np.ndarray) -> np.ndarray:
    """Support of each alive edge within the alive subgraph (set intersection)."""
    adj: dict[int, set[int]] = {}
    for (u, v), a in zip(edges, alive):
        if a:
            adj.setdefault(int(u), set()).add(int(v))
            adj.setdefault(int(v), set()).add(int(u))
    S = np.zeros(edges.shape[0], dtype=np.int64)
    for e, ((u, v), a) in enumerate(zip(edges, alive)):
        if a:
            S[e] = len(adj.get(int(u), set()) & adj.get(int(v), set()))
    return S


def truss_numpy(edges: np.ndarray) -> np.ndarray:
    """Returns trussness (>= 2) per edge of a canonical u<v edge array."""
    m = edges.shape[0]
    truss = np.full(m, 2, dtype=np.int64)
    alive = np.ones(m, dtype=bool)
    k = 3
    while alive.any():
        while True:
            S = support_naive(edges, alive)
            drop = alive & (S < k - 2)
            if not drop.any():
                break
            truss[drop] = k - 1
            alive &= ~drop
        # all remaining edges are in a k-truss (support-wise); bump k
        truss[alive] = k
        k += 1
    return truss


def max_truss(edges: np.ndarray) -> int:
    """Largest k such that the k-truss is non-empty (numpy oracle)."""
    t = truss_numpy(edges)
    return int(t.max(initial=2))


def community_numpy(edges: np.ndarray, trussness: np.ndarray, q: int,
                    k: int) -> list[np.ndarray]:
    """The k-truss communities that contain vertex ``q`` (numpy oracle).

    A k-truss community (Huang et al., SIGMOD 2014) is a triangle-connected
    set of edges of trussness >= k: two such edges belong together iff a
    chain of triangles, each with all three edges at trussness >= k, links
    them.  Triangles are listed here from adjacency sets and joined by a
    plain union-find; nothing is shared with ``core/hierarchy.py``.

    Args:
        edges: canonical (m, 2) u < v edge array, unique rows.
        trussness: (m,) trussness aligned to ``edges``.
        q: the query vertex.
        k: the community level.

    Returns:
        One (c, 2) array per community that holds an edge of ``q``, each
        in the row order of ``edges``, the list ordered by its first row.
    """
    edges = np.asarray(edges, dtype=np.int64).reshape(-1, 2)
    T = np.asarray(trussness, dtype=np.int64)
    ok = T >= k
    eid = {(int(u), int(v)): i for i, (u, v) in enumerate(edges)}
    adj: dict[int, set[int]] = {}
    for (u, v), a in zip(edges.tolist(), ok.tolist()):
        if a:
            adj.setdefault(u, set()).add(v)
            adj.setdefault(v, set()).add(u)
    parent = list(range(edges.shape[0]))

    def find(x: int) -> int:
        while parent[x] != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x

    for (u, v), a in zip(edges.tolist(), ok.tolist()):
        if not a:
            continue
        e = eid[(u, v)]
        for w in adj[u] & adj[v]:
            for f in (eid[(min(u, w), max(u, w))],
                      eid[(min(v, w), max(v, w))]):
                re, rf = find(e), find(f)
                if re != rf:
                    parent[rf] = re
    mine = {find(i) for i in range(edges.shape[0])
            if ok[i] and q in (int(edges[i, 0]), int(edges[i, 1]))}
    out = []
    for root in mine:
        rows = [i for i in range(edges.shape[0]) if ok[i] and find(i) == root]
        out.append(edges[np.array(rows, np.int64)])
    return sorted(out, key=lambda c: tuple(c[0]))
