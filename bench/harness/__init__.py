"""General code of the chip benchmark: what every cell shares.

Per-configuration, per-traffic and per-metric code lives in files of
their own under ``bench/configs``, ``bench/traffic`` and ``bench/metrics``,
found by the names in ``BENCHMARK.json``.
"""
