"""On-chip benchmark of the vec sweep path: one command runs one cell once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Cells, configurations, traffic mixes and per-layer metrics are data and
small readers found by name (``BENCHMARK.json``, ``bench/configs``,
``bench/traffic``, ``bench/metrics``, ``bench/reference``); the harness
itself never names one.
"""
