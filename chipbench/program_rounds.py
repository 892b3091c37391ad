"""The program's spans of the traced rounds of a serving cell.

A serving cell's driver (``drivers/<generator>.py``) takes the spans a
profiler trace recorded (``repro.spans``, marked ``traced``) when its
trace stops, and says how many root spans of each name its traced rounds
opened (``obs["traced_roots"]``: one ``inc.update`` a round, one
``engine.community`` a query).  A reader names the roots it reads; it
gets those roots and every span inside them, or nothing where the
program has no such spans or the counts disagree.
"""

from __future__ import annotations


def traced(obs: dict, roots) -> list | None:
    """The traced spans under roots named in ``roots``, or None."""
    recs = obs.get("traced_spans")
    if not recs or not obs.get("rounds"):
        return None
    want = obs.get("traced_roots", {})
    ids = set()
    for name in roots:
        mine = {r.decomp for r in recs if r.parent is None and r.name == name}
        if not mine or len(mine) != want.get(name):
            return None
        ids |= mine
    return [r for r in recs if r.decomp in ids]
