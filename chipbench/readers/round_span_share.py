"""Share of the traced ``spec["span"]`` spans whose counter
``spec["counter"]`` reads ``spec["value"]`` (such as updates that fell
back to a full rebuild)."""

from __future__ import annotations

from chipbench import program_rounds


def read(spec: dict, obs: dict, reduced, *, rehearse: bool = False):
    """100 x matching spans / spans."""
    recs = program_rounds.traced(obs, spec["roots"])
    if recs is None:
        return None
    mine = [r for r in recs if r.name == spec["span"]]
    if not mine:
        return None
    hit = sum(r.attrs.get(spec["counter"]) == spec["value"] for r in mine)
    return 100.0 * hit / len(mine)
