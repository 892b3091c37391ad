"""Work counts of ``community.kron11``: the yardstick of ``hier_roofline``.

The count comes from the graph and the queries alone, never from padded
rows, windows or flood rounds.  A round's answers name communities; the
index that answers them is built anew after every full rebuild, so within
the round every triangle of every answered community has to be read at
least once, each as its three 4-byte edge ids.  That is a lower bound on
the bytes the label flood moves: the flood reads whole levels, and reads
a row again in every round it runs.
"""

from __future__ import annotations

import numpy as np

#: bytes a triangle row occupies at the least: three int32 edge ids
BYTES_PER_TRIANGLE = 12


def answer_bytes(rnd, queries) -> int:
    """Bytes of the distinct triangles of every community answered in
    one round (``rnd``: a ``reference_community.Round``; ``queries``:
    (q, k) pairs in its labels)."""
    read = np.zeros(rnd.tri.shape[0], bool)
    for q, k in queries:
        mine = ((rnd.E[:, 0] == q) | (rnd.E[:, 1] == q)) & (rnd.T >= k)
        lab = rnd.labels(k)
        comms = np.unique(lab[mine])
        read |= (rnd.tri_level >= k) & np.isin(lab[rnd.tri[:, 0]], comms)
    return BYTES_PER_TRIANGLE * int(read.sum())
