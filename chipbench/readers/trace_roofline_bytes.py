"""Share of the HBM roofline: the least time the chip needs to move the
bytes the cell's driver counted (``obs[spec["bytes"]]``, a lower bound
from the graph), over the device time of the listed programs."""

from __future__ import annotations

from chipbench import trace, work


def read(spec: dict, obs: dict, reduced, *, rehearse: bool = False):
    """100 x (bytes / peak bandwidth) / device time."""
    if reduced is None or not obs.get(spec["bytes"]):
        return None
    ns, runs = trace.module_ns(reduced, set(spec["jits"]))
    if not runs or ns <= 0:
        return None
    try:
        bw = work.peak(obs["device_kind"])["hbm_bytes_per_s"]
    except KeyError:
        if rehearse:
            return None
        raise
    return 100.0 * (obs[spec["bytes"]] / bw) / (ns / 1e9)
