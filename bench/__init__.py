"""The chip benchmark: one command (``bench/run.py``), cells found by name.

Configurations, traffic mixes and cell settings are data files under
``configs/``, ``traffic/`` and ``workloads/``; each per-layer metric is a
reader under ``metrics/``. The yardstick (traffic generation, op and
byte counts, peaks, trace reduction, the plain reference) lives here and
takes from the program only the system under test and its counters.
"""
