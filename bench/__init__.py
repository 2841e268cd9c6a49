"""The benchmark: one command runs one cell once (``bench/run.py``).

Configurations (``bench/configs/``), traffic mixes (``bench/traffic/``) and
per-layer metric readers (``bench/metrics/``) are found by the names in
``BENCHMARK.json``; the yardstick (traffic generation, the reference and
its comparison, the trace reduction, the peaks and the counts of
operations and bytes) lives here, apart from the program under test.
"""
