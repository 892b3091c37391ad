"""Device microseconds of the programs built from the listed jit
functions, per unit of a counter the program's spans carry: the sum of
``attrs[spec["counter"]]`` over the traced ``spec["span"]`` spans."""

from __future__ import annotations

from chipbench import program, trace


def read(spec: dict, obs: dict, reduced, *, rehearse: bool = False):
    """Summed device time of ``spec["jits"]`` over the summed counter."""
    recs = program.traced_spans(obs)
    if reduced is None or recs is None:
        return None
    count = sum(r.attrs.get(spec["counter"], 0) for r in recs
                if r.name == spec["span"])
    ns, runs = trace.module_ns(reduced, set(spec["jits"]))
    if not runs or count <= 0:
        return None
    return ns / 1e3 / count
