"""On-chip benchmark of the serving stack: one cell per run.

See ``run.py`` for the command and ``BENCHMARK.json`` at the root of
the repository for the cells and metrics.
"""
