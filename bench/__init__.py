"""On-chip benchmark of the PAC fine-tuning and serving system.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` once on the chip it is started on.
Configurations (``bench/configs``), traffic mixes (``bench/traffic``),
correctness limits (``bench/limits``) and per-layer metric readers
(``bench/metrics``) are found by the names in ``BENCHMARK.json``; the
backbone's architecture (``bench/archs``) by the name its configuration
gives.
"""
