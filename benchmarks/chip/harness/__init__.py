"""The chip benchmark's harness: everything a cell shares with every other.

Nothing in this package or in ``run.py`` names a cell, a configuration, a
driver or a per-layer metric: those are files under ``configs/``,
``drivers/``, ``traffic/`` and ``layer_metrics/``, found by the names that
``BENCHMARK.json`` gives. See ``../README.md``.
"""
