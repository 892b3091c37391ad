"""Chunk-policy autotuner: measure, then let ``auto_chunk`` consume the result.

``kernels.wedge_common.auto_chunk`` picks the wedge-table chunk size (one
chunk-skipping unit) whenever the caller passes ``chunk=None``.  Its
recorded-defaults formula (split the table into ``AUTO_CHUNK_TARGET``
chunks, clamp to a band) is a heuristic; this bench closes the loop by
*measuring*: for every benchmark graph it sweeps the pow2 chunk candidates
over the peel executors that consume the chunk (``chunked``, the serving
default), scores each candidate by its normalized warm decomposition time
summed across them, and records the winner per pow2 peel-table-size
bucket.

The emitted table (``--emit``, default
``src/repro/kernels/tuned_chunks.json``) is exactly what ``auto_chunk``
loads at first use: ``{"format": 1, "buckets": {log2(table bucket): chunk}}``.
Buckets the sweep never measured fall back to the formula, so a partial
tuning run is always safe, and deleting the file reverts the whole policy to
the recorded defaults.

Usage::

    PYTHONPATH=src:. python benchmarks/hillclimb.py            # sweep, print
    PYTHONPATH=src:. python benchmarks/hillclimb.py --emit     # + write table
    PYTHONPATH=src:. python benchmarks/hillclimb.py --smoke    # 1 graph, CI
"""

from __future__ import annotations

import argparse
import json

from benchmarks.common import prep_graph, timeit

from repro.core import support as support_mod
from repro.core.pkt import pkt
from repro.kernels import wedge_common

#: default graph suite: one per size regime the serving fleet actually sees
GRAPHS = ("ba-small", "er-small", "rmat-small")

#: peel executors whose hot loop the chunk size shapes; scores are
#: normalized within each mode so no single executor's absolute speed
#: dominates the vote
MODES = ("chunked",)


def chunk_candidates(table_size: int) -> list[int]:
    """Pow2 candidates from the auto-chunk band that fit the table."""
    pad = wedge_common.next_pow2(max(1, table_size))
    hi = min(wedge_common.AUTO_CHUNK_MAX, pad)
    c = wedge_common.AUTO_CHUNK_MIN
    out = []
    while c <= hi:
        out.append(c)
        c <<= 1
    return out or [pad]


def sweep_graph(name: str, *, reps: int = 3, modes=MODES) -> dict:
    """Time every (chunk candidate × peel mode) on one graph.

    Returns ``{"name", "bucket", "table_size", "chunks": {chunk: score},
    "best": chunk}`` where score is the sum over peel modes of the
    candidate's warm time divided by the mode's best candidate time (1.0 =
    won that mode outright).
    """
    g, _ = prep_graph(name)
    ptab = support_mod.build_peel_table(g)
    pad = wedge_common.next_pow2(max(1, ptab.size))
    cands = chunk_candidates(ptab.size)
    times: dict[int, dict[int, float]] = {c: {} for c in cands}
    for pi, mode in enumerate(modes):
        for c in cands:
            times[c][pi] = timeit(
                lambda c=c, mode=mode: pkt(g, chunk=c, mode=mode),
                reps=reps)
    scores: dict[int, float] = {}
    for pi in range(len(modes)):
        best = min(times[c][pi] for c in cands)
        for c in cands:
            scores[c] = scores.get(c, 0.0) + times[c][pi] / max(best, 1e-12)
    best_chunk = min(cands, key=lambda c: scores[c])
    return {"name": name, "bucket": pad.bit_length() - 1,
            "table_size": int(ptab.size),
            "chunks": {str(c): round(scores[c], 4) for c in cands},
            "best": int(best_chunk)}


def tune(graphs=GRAPHS, *, reps: int = 3) -> dict:
    """Sweep the suite and vote per bucket (lowest summed score wins)."""
    sweeps = [sweep_graph(name, reps=reps) for name in graphs]
    votes: dict[int, dict[int, float]] = {}
    for sw in sweeps:
        b = votes.setdefault(sw["bucket"], {})
        for c_str, score in sw["chunks"].items():
            c = int(c_str)
            b[c] = b.get(c, 0.0) + score
    buckets = {str(b): int(min(cands, key=lambda c: cands[c]))
               for b, cands in votes.items()}
    return {"format": 1, "source": "benchmarks/hillclimb.py",
            "graphs": list(graphs), "buckets": buckets, "sweeps": sweeps}


def run(graphs=GRAPHS, *, reps: int = 3,
        emit_path: str | None = None) -> dict:
    """Bench-harness adapter: tune, optionally emit, return the table doc."""
    doc = tune(graphs, reps=reps)
    if emit_path:
        with open(emit_path, "w") as f:
            json.dump({k: doc[k] for k in
                       ("format", "source", "graphs", "buckets")}, f,
                      indent=1, sort_keys=True)
            f.write("\n")
        wedge_common.reload_tuned_chunks()
    return doc


def rows(quick: bool = False) -> list[str]:
    """CSV rows for benchmarks/run.py (no file emission)."""
    doc = tune(GRAPHS[:1] if quick else GRAPHS, reps=2 if quick else 3)
    out = []
    for sw in doc["sweeps"]:
        out.append(f"hillclimb/{sw['name']},bucket=2^{sw['bucket']},"
                   f"best_chunk={sw['best']}")
    return out


def main() -> None:
    """CLI: sweep chunk candidates, print scores, optionally emit the table."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--graphs", nargs="*", default=list(GRAPHS))
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--smoke", action="store_true",
                    help="single graph, 2 reps (CI)")
    ap.add_argument("--emit", nargs="?", const=str(
        wedge_common.TUNED_CHUNKS_PATH), default=None, metavar="PATH",
        help="write the tuned table (default: the path auto_chunk loads)")
    args = ap.parse_args()
    graphs = args.graphs[:1] if args.smoke else args.graphs
    reps = 2 if args.smoke else args.reps
    doc = run(graphs, reps=reps, emit_path=args.emit)
    for sw in doc["sweeps"]:
        print(f"{sw['name']}: table={sw['table_size']} "
              f"bucket=2^{sw['bucket']} best_chunk={sw['best']} "
              f"scores={sw['chunks']}")
    print(f"buckets: {doc['buckets']}"
          + (f" -> {args.emit}" if args.emit else " (dry run; use --emit)"))


if __name__ == "__main__":
    main()
