"""The chip benchmark's harness: cell lookup, traffic, the wall-clock client,
trace reduction, operation counts and the correctness check.

Everything a cell needs is found by name from ``BENCHMARK.json``: the
configuration file under ``configs/``, the traffic file under ``traffic/``,
the plain reference under ``references/`` and one reader per per-layer
metric under ``metrics/``.
"""
