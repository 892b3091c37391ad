"""What the harness and the drivers share: the driver protocol, logging,
JSON files and the compile counter."""

from __future__ import annotations

import json
import pathlib

from chipbench.trace import Tracer


def log(msg: str) -> None:
    """One progress line on stdout (never the last one)."""
    print(msg, flush=True)


def load_json(path) -> dict:
    """A JSON file of the benchmark."""
    return json.loads(pathlib.Path(path).read_text())


class Cell:
    """What ``run.py`` asks of a driver (``drivers/<generator>.py``).

    A driver is built from the cell's spec (``config``, ``traffic``), the
    run's seed and the window's length.  ``setup`` makes the inputs from
    the seed and warms every shape the window uses; ``window`` runs the
    measured work; ``close`` frees the program's state; ``check`` then
    compares what the window produced with the plain reference.
    """

    def __init__(self, spec: dict, *, seed: int, seconds: float):
        self.config = spec["config"]
        self.traffic = spec["traffic"]
        self.seed = seed
        self.seconds = seconds
        #: the window's profiler; ``run.py`` gives a traced run its own
        self.tracer = Tracer(None)

    def setup(self) -> None:
        raise NotImplementedError

    def window(self) -> None:
        raise NotImplementedError

    def close(self) -> None:
        """Free the program's state before the reference runs."""

    def check(self) -> dict:
        """``{name: {"value", "limit"}}``: each number compared."""
        raise NotImplementedError

    def end_to_end(self) -> dict:
        """Every end-to-end reading this driver takes, by metric name."""
        raise NotImplementedError

    def observations(self) -> dict:
        """Counters, host timings and work counts for the metric readers."""
        raise NotImplementedError

    def attempts(self) -> tuple[int, int]:
        """(requests attempted in the window, requests that failed)."""
        raise NotImplementedError


class CompileCounter:
    """Programs compiled or loaded, and cache loads, via ``jax.monitoring``.

    JAX reports a backend-compile duration for every program it obtains,
    whether the compiler built it or the persistent cache supplied it;
    cache loads are counted apart.  Listeners cannot be removed, so make
    one counter per process.
    """

    def __init__(self):
        import jax

        self.programs = self.cache_loads = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, seconds: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1
            self.seconds += seconds

    def _event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_loads += 1
