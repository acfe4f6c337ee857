"""Shared parts of the benchmark harness (``bench/run.py``).

Nothing here is specific to one configuration, traffic mix or per-layer
metric: those live in files of their own under ``bench/configs``,
``bench/traffic`` and ``bench/metrics``, found by the names that
``BENCHMARK.json`` gives them.
"""
