"""Mean ratio of two attributes of the traced ``spec["span"]`` spans,
over the spans whose ``spec["where"]`` attribute is positive (such as the
insertion candidate region over the edge count, for updates with
insertions)."""

from __future__ import annotations

from chipbench import program_rounds


def read(spec: dict, obs: dict, reduced, *, rehearse: bool = False):
    """100 x mean of numerator / denominator."""
    recs = program_rounds.traced(obs, spec["roots"])
    if recs is None:
        return None
    mine = [r for r in recs if r.name == spec["span"]
            and r.attrs.get(spec["where"], 0) > 0
            and r.attrs.get(spec["denominator"], 0) > 0
            and spec["numerator"] in r.attrs]
    if not mine:
        return None
    return 100.0 * sum(r.attrs[spec["numerator"]]
                       / r.attrs[spec["denominator"]]
                       for r in mine) / len(mine)
