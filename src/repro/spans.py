"""Named spans at the program's layer boundaries, and counters on them.

``span(name, **attrs)`` opens a ``jax.profiler.TraceAnnotation`` named
``repro.<name>``, so a profiler trace shows each span on its own clock
beside the device's events.  Its attributes and any counters the body
adds with ``Span.set`` (numbers known only at the end, such as sub-levels
read back from the device) are attached to the trace event when the span
exits.  Every span is also kept, whether or not a profiler runs, in a
bounded in-memory ring that ``records`` copies and ``drain`` empties:
name, parent, decomposition id, start and end on the host clock
(``perf_counter_ns``), attributes, and whether a profiler trace was
recording it.

A span opened while no other span is open on its thread starts a new
decomposition id; spans opened inside it share that id.  A span costs a
few microseconds of host time with no profiler running.
"""

from __future__ import annotations

import collections
import dataclasses
import itertools
import threading
import time
from contextlib import contextmanager

import jax

#: spans kept in memory; the oldest are dropped first
RING_SIZE = 4096

_ring: collections.deque = collections.deque(maxlen=RING_SIZE)
_ids = itertools.count(1)
_open = threading.local()


@dataclasses.dataclass
class Span:
    """One span: where it sits, when it ran and what it counted."""

    id: int
    name: str                 # without the ``repro.`` prefix
    parent: int | None        # id of the enclosing span on this thread
    decomp: int               # id shared by every span of one decomposition
    start_ns: int
    end_ns: int = 0
    traced: bool = False      # a profiler trace was recording at entry
    attrs: dict = dataclasses.field(default_factory=dict)
    children: list = dataclasses.field(default_factory=list, repr=False)

    @property
    def seconds(self) -> float:
        """Host duration of the span."""
        return (self.end_ns - self.start_ns) / 1e9

    def set(self, **values) -> None:
        """Attributes or counters to attach to the span when it exits."""
        self.attrs.update(values)


def _stack() -> list:
    stack = getattr(_open, "stack", None)
    if stack is None:
        stack = _open.stack = []
    return stack


@contextmanager
def span(name: str, **attrs):
    """Open the span ``repro.<name>``; yields its ``Span`` record."""
    stack = _stack()
    parent = stack[-1] if stack else None
    sid = next(_ids)
    with jax.profiler.TraceAnnotation(f"repro.{name}") as annotation:
        rec = Span(sid, name, None if parent is None else parent.id,
                   sid if parent is None else parent.decomp,
                   time.perf_counter_ns(),
                   traced=jax.profiler.TraceAnnotation.is_enabled(),
                   attrs=dict(attrs))
        stack.append(rec)
        try:
            yield rec
        finally:
            stack.pop()
            rec.end_ns = time.perf_counter_ns()
            if rec.attrs:
                annotation.set_metadata(**rec.attrs)
            if parent is not None:
                parent.children.append(rec)
            _ring.append(rec)


def seconds_by_name(root: Span, names: dict[str, str]) -> dict[str, float]:
    """Host seconds of ``root``'s direct children, summed by ``names[name]``.

    Every value of ``names`` is a key of the result, 0.0 where no child
    of that name ran.
    """
    out = dict.fromkeys(names.values(), 0.0)
    for child in root.children:
        if child.name in names:
            out[names[child.name]] += child.seconds
    return out


def records() -> list[Span]:
    """The spans in the ring, oldest first, without clearing it."""
    return list(_ring)


def drain() -> list[Span]:
    """The spans in the ring, oldest first; the ring is left empty."""
    out = []
    while _ring:
        out.append(_ring.popleft())
    return out
