"""Plain k-truss community search: the yardstick of ``community.kron11``.

A k-truss community (Huang et al., SIGMOD 2014) is a triangle-connected
set of edges of trussness >= k: two such edges belong together iff a
chain of triangles, each with all three edges at trussness >= k, links
them.  The answer to a query (q, k) is every community that holds an edge
of q.

Nothing of the program under test is used: the triangles and trussness
are ``reference.py``'s, and the components are the connected components
of the graph whose nodes are edges and whose links join the edges of each
triangle active at level k (scipy's union of those links).  An edge of
trussness >= k in no active triangle is a community of its own.
"""

from __future__ import annotations

import numpy as np
from scipy.sparse import coo_matrix
from scipy.sparse.csgraph import connected_components

from chipbench import reference


def components(m: int, tri: np.ndarray, tri_level: np.ndarray,
               k: int) -> np.ndarray:
    """(m,) component label of every edge at level ``k`` (edges below
    ``k`` get labels of their own, never shared)."""
    act = tri[tri_level >= k]
    rows = np.concatenate([act[:, 0], act[:, 0]])
    cols = np.concatenate([act[:, 1], act[:, 2]])
    graph = coo_matrix((np.ones(rows.shape[0], np.int8), (rows, cols)),
                       shape=(m, m))
    return connected_components(graph, directed=False)[1]


class Round:
    """One round's graph: canonical edges, their trussness and triangles.

    ``E`` must be canonical (``reference.canonical`` order); ``T`` the
    reference's trussness of it.  Components are computed once per level.
    """

    def __init__(self, E: np.ndarray, T: np.ndarray):
        self.E = E
        self.T = T
        self.n = int(E.max()) + 1 if E.size else 0
        self.tri = reference.triangles(E, self.n)
        self.tri_level = (T[self.tri].min(axis=1) if self.tri.size
                          else np.zeros(0, np.int64))
        self._labels: dict[int, np.ndarray] = {}

    @classmethod
    def of(cls, edges) -> "Round":
        """The round of an edge array, trussness from ``reference.py``."""
        E, T = reference.trussness(edges)
        return cls(E, T)

    def labels(self, k: int) -> np.ndarray:
        if k not in self._labels:
            self._labels[k] = components(self.E.shape[0], self.tri,
                                         self.tri_level, k)
        return self._labels[k]

    def answer(self, q: int, k: int) -> list[np.ndarray]:
        """Edge ids of every level-``k`` community holding an edge of
        ``q``, each sorted, the list ordered by smallest id."""
        mine = ((self.E[:, 0] == q) | (self.E[:, 1] == q)) & (self.T >= k)
        lab = self.labels(k)
        out = [np.flatnonzero((lab == c) & (self.T >= k))
               for c in np.unique(lab[mine])]
        return sorted(out, key=lambda ids: int(ids[0]))

    def answer_triangles(self, q: int, k: int) -> dict[int, int]:
        """Triangles of each community of the answer, by its label."""
        mine = ((self.E[:, 0] == q) | (self.E[:, 1] == q)) & (self.T >= k)
        lab = self.labels(k)
        act = self.tri[self.tri_level >= k]
        tri_lab = lab[act[:, 0]]
        return {int(c): int(np.count_nonzero(tri_lab == c))
                for c in np.unique(lab[mine])}
