"""Shared wedge-table machinery for the truss table builders and executors.

A *wedge table* has one row per (anchor edge, candidate adjacency slot)
pair, with a probe range ``[lo, hi)`` into the CSR adjacency array ``N``.
The support executor (``core.support``) walks the oriented AM4 table and
probes every row.  The peel's full-adjacency ProcessSubLevel table is
probed once, by its builder (``core.support.peel_rows`` and the host
``resolve_peel_table``), which keeps the hits as resolved triangle rows
``(e1, e2, e3)`` with the sentinel ``m`` in all three columns on padding
rows; the peel executors walk those rows and never search.  This module is
the single home of the table math they share:

  * **chunk layout** — tables are cut into fixed-size chunks, the unit the
    chunk-skipping peel visits; ``chunk_layout`` sanitizes a requested chunk
    size (clamped so that ``n_chunks >= 1`` always holds, including
    zero-entry tables) and ``pow2_chunk`` picks one for a pow2-padded table;
  * **BlockSpec helpers** — ``chunk_spec`` stages one chunk per grid step,
    ``replicated_spec`` replicates a whole array into VMEM at every step;
  * **the search primitive** — ``ranged_searchsorted`` is the branch-free
    vectorized lower-bound binary search every membership test uses, and
    ``probe`` fuses it with the candidate gather and hit predicate
    (``w ∈ N[lo:hi)``); ``probe_np`` is its host mirror.

Everything here is pure jax/numpy so it can be imported from kernels and
from ``core/`` without cycles (``core.support`` re-exports
``ranged_searchsorted`` for its established call sites).
"""

from __future__ import annotations

import json
import os
import pathlib

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


#: adjacency padding value: larger than any vertex id, so padded slots can
#: never match a probe (shared by the batched engine and the local re-peel)
PAD_N = np.int32(1 << 30)


def next_pow2(x: int) -> int:
    """Smallest power of two >= x (>= 1)."""
    return 1 << max(0, int(x - 1).bit_length())


#: auto-chunk policy: aim for this many chunks per wedge table, so that the
#: chunk-skipping while_loop has skippable units even on small graphs …
AUTO_CHUNK_TARGET = 16
#: … clamped to this band (below: per-chunk loop overhead dominates;
#: above: a chunk is too coarse for the frontier to skip much of it)
AUTO_CHUNK_MIN = 1 << 7
AUTO_CHUNK_MAX = 1 << 14


#: tuned-chunk table location: ``benchmarks/hillclimb.py`` measures the best
#: chunk per pow2 table-size bucket and writes it here (override with the
#: env var for experiments); missing/invalid files fall back to the
#: recorded-defaults formula below
TUNED_CHUNKS_ENV = "TRUSS_TUNED_CHUNKS"
TUNED_CHUNKS_PATH = pathlib.Path(__file__).with_name("tuned_chunks.json")

_TUNED_CHUNKS: dict[int, int] | None | bool = False  # False = not loaded yet


def _load_tuned_chunks() -> dict[int, int] | None:
    """Parse the tuned-chunk table: {log2(pow2 table bucket): chunk}.

    Any failure (missing file, wrong format version, non-pow2 values)
    disables the table for the whole process — the formula fallback keeps
    ``auto_chunk`` total, so a stale or corrupt tuning file can never break
    a decomposition, only untune it.
    """
    path = os.environ.get(TUNED_CHUNKS_ENV) or TUNED_CHUNKS_PATH
    try:
        with open(path) as f:
            doc = json.load(f)
        if doc.get("format") != 1:
            return None
        table = {}
        for bucket, chunk in doc["buckets"].items():
            b, c = int(bucket), int(chunk)
            if c < 1 or c & (c - 1):
                return None
            table[b] = c
        return table or None
    except (OSError, ValueError, KeyError, AttributeError, TypeError):
        return None


def reload_tuned_chunks() -> dict[int, int] | None:
    """Drop the cached tuned table and re-read it (test / autotuner hook)."""
    global _TUNED_CHUNKS
    _TUNED_CHUNKS = _load_tuned_chunks()
    return _TUNED_CHUNKS


def auto_chunk(size: int, *, target: int = AUTO_CHUNK_TARGET,
               lo: int = AUTO_CHUNK_MIN, hi: int = AUTO_CHUNK_MAX) -> int:
    """Derive a chunk size from the table size (used when none is requested).

    Consults the tuned-chunk table first: ``benchmarks/hillclimb.py`` sweeps
    chunk candidates per pow2 table-size bucket and records the winner in
    ``tuned_chunks.json``; a hit is clamped to ``[lo, hi]`` and returned.
    Buckets the autotuner never measured (and any load failure) fall back
    to the recorded-defaults formula: a power of two sized so the table
    splits into roughly ``target`` chunks, clamped to ``[lo, hi]``.  The
    old fixed ``1 << 14`` default made every table smaller than 16Ki
    entries a *single* chunk, so the work-efficient chunk-skipping executor
    scanned the whole table every sub-level while still paying the
    while_loop machinery — the chunked-slower-than-dense pathology
    BENCH_smoke.json showed on tiny graphs.  Large tables still get the
    cap ``hi``.
    """
    global _TUNED_CHUNKS
    size = max(1, int(size))
    if _TUNED_CHUNKS is False:
        _TUNED_CHUNKS = _load_tuned_chunks()
    if _TUNED_CHUNKS:
        bucket = next_pow2(size).bit_length() - 1
        tuned = _TUNED_CHUNKS.get(bucket)
        if tuned is not None:
            return int(min(hi, max(lo, tuned)))
    want = next_pow2(-(-size // max(1, int(target))))
    return int(min(hi, max(lo, want)))


def pow2_chunk(size_pad: int, chunk: int | None, *,
               size: int | None = None) -> int:
    """Chunk size for a pow2-padded table: a power of two dividing ``size_pad``.

    ``chunk=None`` applies the ``auto_chunk`` policy against the *real*
    table size (``size``, defaulting to ``size_pad``); an explicit chunk is
    rounded down to a power of two so it always divides the padded table.
    """
    if chunk is None:
        chunk = auto_chunk(size_pad if size is None else size)
    else:
        chunk = 1 << max(0, int(chunk).bit_length() - 1)
    return max(1, min(int(chunk), int(size_pad)))


def pad1(x: np.ndarray, size: int, fill) -> np.ndarray:
    """Right-pad a 1-D int array to ``size`` with ``fill`` (int32 out)."""
    out = np.full(size, fill, np.int32)
    out[: x.shape[0]] = x
    return out


def chunk_layout(size: int, chunk: int | None = None) -> tuple[int, int]:
    """Sanitize a requested chunk size against a table of ``size`` entries.

    Returns ``(chunk, n_chunks)`` with ``1 <= chunk`` and ``n_chunks >= 1``:
    a chunk larger than the table, zero, or negative is clamped; a zero-entry
    table yields one all-padding chunk of size 1 (callers that want to skip
    the peel entirely for empty tables early-exit before this).
    ``chunk=None`` derives the size from the table via ``auto_chunk``.
    """
    size = max(1, int(size))
    if chunk is None:
        chunk = auto_chunk(size)
    chunk = max(1, min(int(chunk), size))
    return chunk, -(-size // chunk)


def chunk_spec(chunk: int) -> pl.BlockSpec:
    """One table chunk per grid step."""
    return pl.BlockSpec((chunk,), lambda i: (i,))


def replicated_spec(size: int) -> pl.BlockSpec:
    """Whole array staged at every grid step (adjacency / edge state)."""
    return pl.BlockSpec((size,), lambda i: (0,))


def ranged_searchsorted(N: jnp.ndarray, w: jnp.ndarray, lo: jnp.ndarray,
                        hi: jnp.ndarray, iters: int) -> jnp.ndarray:
    """Vectorized lower-bound binary search of w in sorted N[lo:hi).

    Returns the insertion index (== hi when all elements < w). ``iters`` must
    be >= ceil(log2(max(hi - lo) + 1)).
    """
    def body(_, state):
        lo_, hi_ = state
        mid = (lo_ + hi_) >> 1
        val = N[mid]
        go_right = val < w
        lo_ = jnp.where(go_right & (lo_ < hi_), mid + 1, lo_)
        hi_ = jnp.where((~go_right) & (lo_ < hi_), mid, hi_)
        return lo_, hi_

    lo_f, _ = jax.lax.fori_loop(0, iters, body, (lo, hi))
    return lo_f


def ranged_searchsorted_np(N: np.ndarray, w: np.ndarray, lo: np.ndarray,
                           hi: np.ndarray, iters: int) -> np.ndarray:
    """Host-numpy mirror of ``ranged_searchsorted`` (same algorithm, same
    bounds contract).  Used by the incremental-maintenance layer, whose
    per-update table shapes vary too much to amortize a jit trace."""
    lo_ = lo.astype(np.int64, copy=True)
    hi_ = hi.astype(np.int64, copy=True)
    top = max(N.shape[0] - 1, 0)
    for _ in range(iters):
        adv = lo_ < hi_
        mid = (lo_ + hi_) >> 1
        val = N[np.minimum(mid, top)]
        go_right = val < w
        lo_ = np.where(adv & go_right, mid + 1, lo_)
        hi_ = np.where(adv & ~go_right, mid, hi_)
    return lo_


def probe_np(N: np.ndarray, cand_slot: np.ndarray, lo: np.ndarray,
             hi: np.ndarray, *, iters: int) -> tuple[np.ndarray, np.ndarray]:
    """Host-numpy mirror of ``probe``: (hit, safe) for w = N[cand_slot]."""
    if N.size == 0 or cand_slot.size == 0:
        z = np.zeros(cand_slot.shape[0], np.int64)
        return z.astype(bool), z
    w = N[cand_slot]
    idx = ranged_searchsorted_np(N, w, lo, hi, iters)
    safe = np.minimum(idx, N.shape[0] - 1)
    hit = (idx < hi) & (N[safe] == w)
    return hit, safe


def probe(N: jnp.ndarray, cand_slot: jnp.ndarray, lo: jnp.ndarray,
          hi: jnp.ndarray, *, iters: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fused wedge membership test: is ``w = N[cand_slot]`` in ``N[lo:hi)``?

    Returns ``(hit, safe)`` where ``safe`` is the (clamped) index of the
    matching slot — valid as a gather index whenever ``hit`` is True, and a
    harmless in-bounds index otherwise.  This is the inner loop of the
    support executor, and the one probe of the peel-table builder.
    """
    w = N[cand_slot]
    idx = ranged_searchsorted(N, w, lo, hi, iters)
    safe = jnp.minimum(idx, N.shape[0] - 1)
    hit = (idx < hi) & (N[safe] == w)
    return hit, safe
