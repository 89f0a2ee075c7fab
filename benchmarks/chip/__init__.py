"""On-chip benchmark of the DVNR in situ path: one cell per run.

See ``run.py`` for the command and ``BENCHMARK.json`` at the repository root
for the cells and metrics.
"""
