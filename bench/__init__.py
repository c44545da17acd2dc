"""On-chip benchmark of Cobra's serving path.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` once. Configurations, traffic mixes
and per-layer metrics are files of their own under ``bench/configs``,
``bench/traffic`` and ``bench/metrics``, found by the names that
``BENCHMARK.json`` gives (:mod:`bench.registry`).
"""
