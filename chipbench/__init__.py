"""Chip benchmark of the truss system (see ``run.py`` and ``PERF.md``)."""
