"""Profiler trace of the measured window, and its reduction to intervals.

``Tracer`` records the window (or its first seconds) with JAX's profiler
inside the host span ``chipbench.window``, which puts the traced window on
the trace's own clock.  ``load`` reads the written ``.xplane.pb`` with nothing but JAX and
keeps four kinds of interval:

* ``ops``: every operation that ran on a device (TPU planes' "XLA Ops"
  line; off the chip, the host threads' events that carry an ``hlo_op``);
* ``modules``: every execution of a compiled program (the "XLA Modules"
  line), named by its jit function;
* ``spans``: the host spans the benchmark writes (``chipbench.*``);
* ``host``: the runtime's host events on the benchmark's thread, which
  say what the host was doing while the device sat idle.

Everything after ``load`` works on plain tuples, so a small recorded
trace (``tests/data``) checks the reduction without a chip.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import time

WINDOW_SPAN = "chipbench.window"
#: host threads whose events say what the host did in an idle gap: the
#: benchmark's own
HOST_THREADS = ("main", "python")


class Tracer:
    """The profiler over the start of the measured window.

    ``start`` opens the trace and the ``chipbench.window`` span; ``stop``
    closes both, at the latest when the window ends.  A driver asks
    ``due`` between requests and stops the trace once ``seconds`` have
    passed, because the chip's trace buffer holds only some seconds of a
    busy device and drops later events without a word.  The trace's
    metrics and ``busy_s`` / ``window_s`` then describe the traced part.
    With no ``log_dir`` nothing is traced and every call is free.
    """

    def __init__(self, log_dir: str | None, seconds: float | None = None):
        self.log_dir = log_dir
        self.seconds = seconds
        self.active = False
        self._span = None
        self._t0 = 0.0

    def start(self) -> None:
        """Open the trace and the window span (if tracing)."""
        import jax

        if self.log_dir is None:
            return
        # Python function events would cost a minute or more to write out
        # (a 6 s one-shot trace held the run for 116 s on the chip) and
        # stall the window; the runtime's own host events stay
        jax.profiler.start_trace(self.log_dir, profiler_options=_OPTIONS())
        self._span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._span.__enter__()
        self._t0 = time.perf_counter()
        self.active = True

    def due(self) -> bool:
        """True once the trace has run its ``seconds`` and should stop."""
        return (self.active and self.seconds is not None
                and time.perf_counter() - self._t0 >= self.seconds)

    def stop(self) -> None:
        """Close the window span and write the trace (idempotent)."""
        import jax

        if not self.active:
            return
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.active = False


def _OPTIONS():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def _jit_name(module: str) -> str:
    """``jit__peel_segment_jit(123)`` / ``jit__peel_segment_jit.4`` ->
    ``_peel_segment_jit``: the jit function a program was built from."""
    name = module.split("(")[0]
    name = re.sub(r"\.\d+$", "", name)
    return name[4:] if name.startswith("jit_") else name


def load(log_dir: str) -> dict:
    """The intervals of the newest trace under ``log_dir`` (see module doc).

    Each interval is ``[start_ns, end_ns, name]`` on the trace's clock;
    ``window`` is ``[start_ns, end_ns]`` of the ``chipbench.window`` span.
    """
    import jax

    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no trace was written under {log_dir}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    ops, modules, spans, host = [], [], [], []
    window = None
    device_planes = 0
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            device_planes += 1
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend([ev.start_ns, ev.end_ns, _op_name(ev.name)]
                               for ev in line.events)
                elif line.name == "XLA Modules":
                    modules.extend([ev.start_ns, ev.end_ns,
                                    _jit_name(ev.name)] for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("chipbench."):
                        if ev.name == WINDOW_SPAN:
                            window = [ev.start_ns, ev.end_ns]
                        else:
                            spans.append([ev.start_ns, ev.end_ns, ev.name])
                    elif line.name.startswith(HOST_THREADS):
                        host.append([ev.start_ns, ev.end_ns, ev.name])
                    elif not device_planes and line.name.startswith("tf_XLA"):
                        _cpu_op(ev, ops, modules)
    if window is None:
        raise ValueError(f"the trace holds no {WINDOW_SPAN} span")
    _name_by_module(ops, modules)
    return {"window": window, "ops": ops, "modules": modules,
            "spans": spans, "host": host}


def _op_name(hlo: str) -> str:
    """``%while.63 = (s32[..]..) while(..)`` -> ``while.63``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def _name_by_module(ops: list, modules: list) -> None:
    """Prefix each operation with the program it ran in (by time)."""
    modules.sort()
    starts = [m[0] for m in modules]
    for op in ops:
        i = bisect.bisect_right(starts, op[0]) - 1
        if i >= 0 and modules[i][1] >= op[0]:
            op[2] = f"{modules[i][2]}/{op[2]}"


def _cpu_op(ev, ops: list, modules: list) -> None:
    """Off the chip, XLA's CPU thunks stand in for device operations."""
    stats = dict(ev.stats)
    if "hlo_op" in stats:
        ops.append([ev.start_ns, ev.end_ns, _op_name(ev.name)])
        modules.append([ev.start_ns, ev.end_ns,
                        _jit_name(str(stats.get("hlo_module", "")))])


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """The parts of ``intervals`` inside ``[lo, hi]``, merged and sorted."""
    parts = sorted((max(s, lo), min(e, hi)) for s, e, *_ in intervals
                   if e > lo and s < hi)
    merged: list[list[float]] = []
    for s, e in parts:
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy_ns(reduced: dict) -> float:
    """Length of the union of device operations inside the window."""
    lo, hi = reduced["window"]
    return sum(e - s for s, e in clip(busy_intervals(reduced), lo, hi))


def busy_intervals(reduced: dict) -> list:
    """Device intervals: the operations, or the programs where ops lack."""
    return reduced["ops"] or reduced["modules"]


def module_ns(reduced: dict, jits) -> tuple[float, int]:
    """(summed device ns, executions) of the programs built from ``jits``."""
    lo, hi = reduced["window"]
    total, count = 0.0, 0
    for s, e, name in reduced["modules"]:
        if name in jits and e > lo and s < hi:
            total += min(e, hi) - max(s, lo)
            count += 1
    return total, count


def top_ops(reduced: dict, k: int = 10) -> list[list]:
    """The ``k`` device operations that took most time: [name, seconds]."""
    lo, hi = reduced["window"]
    by_name: dict[str, float] = {}
    for s, e, name in busy_intervals(reduced):
        if e > lo and s < hi:
            by_name[name] = by_name.get(name, 0.0) + min(e, hi) - max(s, lo)
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / 1e9] for name, ns in top]


def idle_gaps(reduced: dict, k: int = 10) -> list[list]:
    """The ``k`` longest idle gaps, each named by what the host was doing.

    A gap is named by the innermost benchmark span around its middle,
    followed by the innermost host event of ``HOST_THREADS`` there.
    """
    lo, hi = reduced["window"]
    busy = clip(busy_intervals(reduced), lo, hi)
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:k]
    return [[_label(reduced, (s + e) / 2), (e - s) / 1e9] for s, e in gaps]


def _innermost(intervals, t: float) -> str | None:
    best = None
    for s, e, name in intervals:
        if s <= t <= e and (best is None or e - s < best[0]):
            best = (e - s, name)
    return None if best is None else best[1]


def _label(reduced: dict, t: float) -> str:
    span = _innermost(reduced["spans"], t) or WINDOW_SPAN
    host = _innermost(reduced["host"], t)
    return span if host is None else f"{span} / {host}"
