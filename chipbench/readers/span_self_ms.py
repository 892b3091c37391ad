"""Host milliseconds of one of the program's spans (``spec["span"]``,
without the ``repro.`` prefix), self time, per traced decomposition."""

from __future__ import annotations

from chipbench import program


def read(spec: dict, obs: dict, reduced, *, rehearse: bool = False):
    """Summed self time of every ``spec["span"]`` span over the traced
    decompositions, divided by their count."""
    recs = program.traced_spans(obs)
    if recs is None:
        return None
    ns = sum(program.self_ns(r) for r in recs
             if r.name == spec["span"])
    return ns / 1e6 / obs["decompositions"]
