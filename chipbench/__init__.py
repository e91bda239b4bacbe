"""Chip benchmark of the MapReduce engine (see ``run.py`` and PERF.md)."""
