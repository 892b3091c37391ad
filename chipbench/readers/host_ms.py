"""Milliseconds the benchmark timed on the host around its own call into
a layer (``obs["host_s"][spec["key"]]``)."""

from __future__ import annotations


def read(spec: dict, obs: dict, reduced, *, rehearse: bool = False):
    """The host time of ``spec["key"]`` in milliseconds."""
    s = obs.get("host_s", {}).get(spec["key"])
    return None if s is None else 1e3 * s
