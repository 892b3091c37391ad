"""Share of the HBM roofline: the least time the chip needs to move a
phase's work bytes (``work.py``), over the device time of its programs."""

from __future__ import annotations

from chipbench import trace, work


def read(spec: dict, obs: dict, reduced, *, rehearse: bool = False):
    """100 x (probes x bytes per probe / peak bandwidth) / device time."""
    if reduced is None or not obs.get("decompositions"):
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
    moved = obs[spec["probes"]] * work.BYTES_PER_PROBE * obs["decompositions"]
    return 100.0 * (moved / bw) / (ns / 1e9)
