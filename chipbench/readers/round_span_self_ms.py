"""Host milliseconds of the program's spans named in ``spec["spans"]``,
self time, per traced round.  ``spec["only"]`` keeps a span of a name
only where its attributes read the given values (such as re-peels on the
host path)."""

from __future__ import annotations

from chipbench import program, program_rounds


def read(spec: dict, obs: dict, reduced, *, rehearse: bool = False):
    """Summed self time of the named spans over the traced rounds,
    divided by their count."""
    recs = program_rounds.traced(obs, spec["roots"])
    if recs is None:
        return None
    only = spec.get("only", {})
    ns = sum(program.self_ns(r) for r in recs
             if r.name in spec["spans"]
             and all(r.attrs.get(a) == v
                     for a, v in only.get(r.name, {}).items()))
    return ns / 1e6 / obs["rounds"]
