"""The control and the planted faults of ``community.kron11``.

Each is a context manager that breaks the served path underneath an
otherwise normal run, as ``faults.py`` does for ``oneshot.kron11``; none
is used by the benchmark's own runs.  ``control_community.py`` runs the
control on the chip and ``tests/test_community.py`` runs all of them on
the CPU at a small size.

* ``control_community``: a stale index.  Every (q, k) query is answered
  from the handle's community index as it stood before the last batch,
  the shortcut an index that is rebuilt lazily, or off the write path, is
  tempted by;
* ``fault_trussness_row``: one row of every trussness read raised by one;
* ``fault_missing_edge``: every answer's first community loses an edge.
"""

from __future__ import annotations

import contextlib

import numpy as np

from chipbench.faults import _patched


@contextlib.contextmanager
def control_community():
    """(q, k) queries answered from the index before the last batch."""
    from repro.core.truss_inc import IncrementalTruss
    from repro.serve.truss_engine import TrussHandle

    def make_update(original):
        def update(self, *args, **kwargs):
            # the index and edges as they stand before this batch
            self._stale_index = (self.edges, self.hierarchy())
            return original(self, *args, **kwargs)
        return update

    def make_community(original):
        def community(self, edge_or_vertex, k, **kwargs):
            stale = getattr(self._inc, "_stale_index", None)
            if stale is None or np.ndim(edge_or_vertex) != 0:
                return original(self, edge_or_vertex, k, **kwargs)
            E, h = stale
            v = int(edge_or_vertex)
            ids = np.nonzero((E[:, 0] == v) | (E[:, 1] == v))[0]
            labels = h.level_labels(k)[ids]
            return [E[h.community_of(int(r), k)]
                    for r in np.unique(labels[labels >= 0])]
        return community
    with _patched(IncrementalTruss, "update", make_update), \
            _patched(TrussHandle, "community", make_community):
        yield


def fault_trussness_row():
    """Every trussness read with one row raised by one."""
    from repro.serve.truss_engine import TrussHandle

    def make(original):
        def query(self, edges):
            out = np.array(original(self, edges))
            out[len(out) // 2] += 1
            return out
        return query
    return _patched(TrussHandle, "query", make)


def fault_missing_edge():
    """Every (q, k) answer with one edge of its first community left out."""
    from repro.serve.truss_engine import TrussHandle

    def make(original):
        def community(self, edge_or_vertex, k, **kwargs):
            out = original(self, edge_or_vertex, k, **kwargs)
            if np.ndim(edge_or_vertex) == 0 and out:
                out = [out[0][1:]] + list(out[1:])
            return out
        return community
    return _patched(TrussHandle, "community", make)


#: by generator name, for ``control_community.py``
CONTROLS = {"community": control_community}
