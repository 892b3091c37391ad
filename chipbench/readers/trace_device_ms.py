"""Device milliseconds of the programs built from the listed jit functions,
per unit of work (``per``: an observation such as ``decompositions``)."""

from __future__ import annotations

from chipbench import trace


def read(spec: dict, obs: dict, reduced, *, rehearse: bool = False):
    """Summed device time of ``spec["jits"]`` over ``obs[spec["per"]]``."""
    if reduced is None or not obs.get(spec["per"]):
        return None
    ns, runs = trace.module_ns(reduced, set(spec["jits"]))
    if not runs:
        return None
    return ns / 1e6 / obs[spec["per"]]
