"""On-chip benchmark of the serving system: one cell (a model
configuration under a traffic mix) per run of ``bench/run.py``.

Everything that defines the yardstick lives here: traffic generation,
the plain references, the work counts, the peaks table, the trace
reduction and one reader per metric. The program under ``src/`` is only
driven through its entry points.
"""
