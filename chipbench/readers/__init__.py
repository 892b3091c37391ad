"""Per-layer metric readers, one module each, found by the name a
``metrics/<metric>.json`` file gives under ``reader``.

Each has ``read(spec, obs, reduced, *, rehearse) -> float | None``:
``spec`` is the metric file merged with its ``BENCHMARK.json`` entry,
``obs`` the driver's observations, ``reduced`` the window's trace
(``None`` where no trace was taken).  A reader that finds nothing to read
returns ``None`` and the metric is left out of the result.
"""
