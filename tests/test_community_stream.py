"""(q, k) community search on a changing graph, through the scheduler.

A small Kronecker graph takes a delete-and-re-insert edge stream through
``TrussScheduler``; after every committed batch each live edge's
trussness is compared with the numpy oracle ``truss_numpy``, and every
``community_async`` answer with ``community_numpy`` (an independent
union-find over the oracle's trussness).  ``local_frac`` 1.0 keeps
insertion batches on the local repair path (where deletions and insertions
together stay under the edge count); 0.25 sends the dense-core batches to
the full rebuild.
"""

import ctypes
import platform

import numpy as np
import pytest

from repro.core.ref import community_numpy, truss_numpy
from repro.graphs.csr import canonical_edges_with_rows
from repro.graphs.gen import rmat_edges
from repro.serve import scheduler as scheduler_mod
from repro.serve.scheduler import TrussScheduler
from repro.testing.chaos import FaultPlan

ROUNDS, BATCH, LAG, QUERIES = 8, 16, 2, 4


def _canon(communities) -> list[tuple]:
    """Communities as sorted tuples of canonical (u, v) rows."""
    out = []
    for c in communities:
        c = np.asarray(c, np.int64).reshape(-1, 2)
        rows = zip(np.minimum(c[:, 0], c[:, 1]).tolist(),
                   np.maximum(c[:, 0], c[:, 1]).tolist())
        out.append(tuple(sorted(rows)))
    return sorted(out)


@pytest.mark.parametrize("local_frac", [0.25, 1.0])
def test_stream_matches_the_oracles_round_by_round(local_frac):
    E0 = canonical_edges_with_rows(rmat_edges(7, edge_factor=16, seed=0))[0]
    rng = np.random.default_rng(5)
    live = np.ones(E0.shape[0], bool)
    deleted = []
    modes = []
    with TrussScheduler() as sched:
        h = sched.open_async(E0, local_frac=local_frac).result(timeout=300)
        for r in range(ROUNDS):
            dele = rng.choice(np.flatnonzero(live), BATCH, replace=False)
            ins = deleted[r - LAG] if r >= LAG else np.zeros(0, np.int64)
            live[dele] = False
            live[ins] = True
            deleted.append(dele)
            st = sched.update_async(h, add_edges=E0[ins],
                                    remove_edges=E0[dele]).result(timeout=300)
            modes.append(st.mode)
            E = E0[live]
            T = sched.query_async(h, E).result(timeout=300)
            want_T = truss_numpy(E)
            assert np.array_equal(T, want_T), (r, st.mode)
            pool = np.flatnonzero(want_T >= 3)
            for e in rng.choice(pool, QUERIES, replace=False):
                q = int(E[e, rng.integers(0, 2)])
                top = int(want_T[(E[:, 0] == q) | (E[:, 1] == q)].max())
                k = int(rng.integers(3, top + 1))
                got = sched.community_async(h, q, k).result(timeout=300)
                want = community_numpy(E, want_T, q, k)
                assert want, (r, q, k)
                assert _canon(got) == _canon(want), (r, q, k, st.mode)
    # the re-insertion rounds take the path each parameter is there for:
    # at 0.25 the dense-core batches fall back to the full rebuild, at 1.0
    # some are repaired locally
    assert ("local" if local_frac == 1.0 else "full") in modes[LAG:]


def _two_cliques() -> np.ndarray:
    """K5 on 0..4 and K4 on {0, 5, 6, 7}: vertex 0 sits in both."""
    rows = [(u, v) for u in range(5) for v in range(u + 1, 5)]
    k4 = [0, 5, 6, 7]
    rows += [(k4[i], k4[j]) for i in range(4) for j in range(i + 1, 4)]
    return np.array(rows, np.int64)


def test_community_queued_behind_an_update_sees_it():
    E = _two_cliques()
    sched = TrussScheduler(start=False)
    try:
        h = sched.engine.open(E)
        before = sched.community_async(h, 0, 4)
        upd = sched.update_async(h, remove_edges=np.array([[5, 6]]))
        after = sched.community_async(h, 0, 4)
        sched.start()
        assert upd.result(timeout=300).deleted == 1
        assert len(before.result(timeout=300)) == 2     # K5 and K4
        got = after.result(timeout=300)
        assert _canon(got) == _canon([E[:10]])          # the K5 alone
    finally:
        sched.close()


def test_community_async_follows_the_hierarchy_ladder():
    """A hierarchy fault demotes the request to the host union-find rung,
    which answers with the same communities; the request is its own kind."""
    E = _two_cliques()
    with TrussScheduler(ladder={"demote_after": 1}) as sched:
        h = sched.open_async(E).result(timeout=300)
        want = _canon(sched.community_async(h, 0, 4).result(timeout=300))
        h2 = sched.open_async(E).result(timeout=300)
        with FaultPlan().add("hierarchy", times=1):
            got = sched.community_async(h2, 0, 4).result(timeout=300)
        st = sched.stats()
        assert _canon(got) == want
        assert st["resilience"]["hierarchy"]["rung"] == "host"
        assert st["counters"]["retries"] == 1
        assert st["counters"]["community"] == 2
        assert sched.community_async(h, 99, 3).result(timeout=300) == []


class _Mallinfo2(ctypes.Structure):
    _fields_ = [(name, ctypes.c_size_t) for name in (
        "arena", "ordblks", "smblks", "hblks", "hblkhd", "usmblks",
        "fsmblks", "uordblks", "fordblks", "keepcost")]


def test_scheduler_keeps_the_host_memory_a_round_frees():
    """A 16 MiB array freed on the serving process stays in the allocator
    (glibc's free bytes grow by it) instead of going back to the kernel,
    so the next round does not fault it in again."""
    TrussScheduler(start=False).close()
    glibc = platform.libc_ver()[0] == "glibc"
    assert scheduler_mod._keep_freed_host_memory() is glibc
    if not glibc:
        return
    mallinfo2 = ctypes.CDLL(None).mallinfo2
    mallinfo2.restype = _Mallinfo2
    a = np.ones(2 << 20, np.int64)
    free_before = mallinfo2().fordblks
    del a
    assert mallinfo2().fordblks - free_before >= 16 << 20
