"""Share of the table rows the peel scanned that its work needs: the
graph's peel probes (``work.py``) over the rows of every chunk body the
traced peel segments ran (``chunk_visits`` x ``chunk`` on each
``spec["span"]`` span)."""

from __future__ import annotations

from chipbench import program


def read(spec: dict, obs: dict, reduced, *, rehearse: bool = False):
    """100 x probes x decompositions / rows scanned."""
    recs = program.traced_spans(obs)
    if recs is None:
        return None
    rows = sum(r.attrs.get("chunk_visits", 0) * r.attrs.get("chunk", 0)
               for r in recs if r.name == spec["span"])
    if rows <= 0:
        return None
    return 100.0 * obs[spec["probes"]] * obs["decompositions"] / rows
