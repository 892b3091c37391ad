"""Share of the traced window in which no operation ran on the device."""

from __future__ import annotations

from chipbench import trace


def read(spec: dict, obs: dict, reduced, *, rehearse: bool = False):
    """100 x (1 - union of device operations / window)."""
    if reduced is None:
        return None
    lo, hi = reduced["window"]
    if hi <= lo or not trace.busy_intervals(reduced):
        return None
    return 100.0 * (1.0 - trace.busy_ns(reduced) / (hi - lo))
