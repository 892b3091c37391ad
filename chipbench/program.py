"""The program's own spans and counters of the traced decompositions.

The program keeps every span it opens (``repro.spans``) in an in-memory
ring, each marked with whether a profiler trace was recording it.  The
traced decompositions are the ``truss_pkt`` spans so marked; the readers
take them, and the spans inside them, from the ring after the window.  A
program without ``repro.spans``, or a ring that no longer holds every
traced decomposition, gives nothing to read.
"""

from __future__ import annotations

import importlib


def traced_spans(obs: dict) -> list | None:
    """The spans of the traced decompositions, or None where none are
    kept: one ``truss_pkt`` root for each of ``obs["decompositions"]``."""
    try:
        spans = importlib.import_module("repro.spans")
    except ImportError:
        return None
    recs = [r for r in spans.records() if r.traced]
    roots = {r.decomp for r in recs if r.name == "truss_pkt"}
    if not roots or len(roots) != obs.get("decompositions"):
        return None
    return [r for r in recs if r.decomp in roots]


def self_ns(rec) -> int:
    """``rec``'s duration less the part its child spans cover (children
    of one span run one after another on its thread)."""
    inner = sum(c.end_ns - c.start_ns for c in rec.children)
    return rec.end_ns - rec.start_ns - inner
