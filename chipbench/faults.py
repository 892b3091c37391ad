"""The control and the planted faults that ``correct`` has to catch.

Each is a context manager that breaks the timed path underneath an
otherwise normal run, and none is used by the benchmark's own runs:
``control.py`` runs the controls on the chip and ``tests/test_faults.py``
runs all of them on the CPU at a small size.

* ``control_oneshot``: the plain reference put in ``truss_pkt``'s place,
  with the level cascade dropped (``reference.trussness(cascade=False)``),
  the shortcut a faster peel is tempted by;
* ``fault_answer_oneshot``: one row's trussness altered where it is
  produced.
"""

from __future__ import annotations

import contextlib

import numpy as np

from chipbench import reference


@contextlib.contextmanager
def _patched(owner, name: str, make):
    original = getattr(owner, name)
    setattr(owner, name, make(original))
    try:
        yield
    finally:
        setattr(owner, name, original)


def control_oneshot():
    """``truss_pkt`` answered by the reference without the level cascade."""
    import repro.core

    def make(_original):
        def truss_pkt(edges, **_):
            E, t = reference.trussness(edges, cascade=False)
            n = int(E.max()) + 1
            e = np.asarray(edges, np.int64)
            keys = np.minimum(e[:, 0], e[:, 1]) * n + np.maximum(e[:, 0],
                                                                 e[:, 1])
            return t[np.searchsorted(reference.edge_key(E, n), keys)]
        return truss_pkt
    return _patched(repro.core, "truss_pkt", make)


def fault_answer_oneshot():
    """``truss_pkt`` with one row's trussness raised by one."""
    import repro.core

    def make(original):
        def truss_pkt(edges, **kw):
            out = np.array(original(edges, **kw))
            out[len(out) // 2] += 1
            return out
        return truss_pkt
    return _patched(repro.core, "truss_pkt", make)


#: by name, for ``control.py``
CONTROLS = {"oneshot": control_oneshot}
