"""Chip benchmark of the PayloadPark simulator.

``run.py`` runs one cell of ``BENCHMARK.json`` once and prints one JSON
result line.  Everything the measurement rests on lives here: the cell
files (``configs/``, ``traffic/``), the per-metric readers (``metrics/``),
the traffic generator the reference replays, the plain numpy reference
(``reference.py``), the comparison that decides ``correct``, the profiler
trace reduction and the table of chip peaks.
"""
