"""Work counts and the chip's peaks: the yardstick of the roofline metrics.

The counts come from the graph alone, never from padded rows, chunks or
sub-levels, so a share of the roofline reads the same work whatever
implements it:

* support: the oriented wedge probes of the paper's support phase.  Each
  vertex is ranked by (coreness, label), the preprocessing PKT prescribes;
  edge (u, v) with u ranked below v probes one candidate for every
  neighbour of v ranked above v;
* peel: one probe per candidate of the smaller endpoint's adjacency, the
  sum over edges of min(deg u, deg v).

Bytes are a stated lower bound: every probe reads at least its 4-byte
candidate and one 4-byte word of the probed adjacency.
"""

from __future__ import annotations

import json
import pathlib

import numpy as np

#: bytes every probe reads at the least
BYTES_PER_PROBE = 8

PEAKS_FILE = pathlib.Path(__file__).resolve().parent / "peaks.json"


def peak(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown device is an error."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks known for device kind {device_kind!r}; "
                       f"add it to {PEAKS_FILE.name} with its source")
    return table[device_kind]


def _adjacency(E: np.ndarray, n: int) -> tuple[np.ndarray, np.ndarray]:
    """CSR (offsets, neighbours) of the undirected canonical edges ``E``."""
    src = np.concatenate([E[:, 0], E[:, 1]])
    dst = np.concatenate([E[:, 1], E[:, 0]])
    order = np.lexsort((dst, src))
    off = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(src, minlength=n), out=off[1:])
    return off, dst[order]


def coreness(E: np.ndarray, n: int) -> np.ndarray:
    """Core number of every vertex (Batagelj and Zaversnik's bucket peel)."""
    off, nbr = _adjacency(E, n)
    deg = np.diff(off)
    core = deg.copy()
    # vertices in buckets of current degree; pos/vert/start as in BZ
    start = np.zeros(int(deg.max(initial=0)) + 2, np.int64)
    np.cumsum(np.bincount(deg, minlength=start.shape[0] - 1), out=start[1:])
    vert = np.argsort(deg, kind="stable")
    pos = np.empty(n, np.int64)
    pos[vert] = np.arange(n)
    start = start[:-1].copy()
    core_l, pos_l, vert_l, start_l = (core.tolist(), pos.tolist(),
                                      vert.tolist(), start.tolist())
    off_l, nbr_l = off.tolist(), nbr.tolist()
    for i in range(n):
        v = vert_l[i]
        cv = core_l[v]
        for j in range(off_l[v], off_l[v + 1]):
            u = nbr_l[j]
            cu = core_l[u]
            if cu > cv:
                pu, pw = pos_l[u], start_l[cu]
                w = vert_l[pw]
                if u != w:
                    vert_l[pu], vert_l[pw] = w, u
                    pos_l[u], pos_l[w] = pw, pu
                start_l[cu] += 1
                core_l[u] = cu - 1
    return np.asarray(core_l, np.int64)


def support_probes(E: np.ndarray) -> int:
    """Oriented wedge probes of the support phase (see the module doc)."""
    if E.shape[0] == 0:
        return 0
    n = int(E.max()) + 1
    rank = np.empty(n, np.int64)
    rank[np.lexsort((np.arange(n), coreness(E, n)))] = np.arange(n)
    ru, rv = rank[E[:, 0]], rank[E[:, 1]]
    hi = np.maximum(ru, rv)
    lo = np.minimum(ru, rv)
    # |N+(x)|: neighbours ranked above x
    up = np.bincount(lo, minlength=n)
    return int(up[hi].sum())


def peel_probes(E: np.ndarray) -> int:
    """Probes of the peel phase: sum over edges of min(deg u, deg v)."""
    if E.shape[0] == 0:
        return 0
    deg = np.bincount(E.ravel())
    return int(np.minimum(deg[E[:, 0]], deg[E[:, 1]]).sum())
