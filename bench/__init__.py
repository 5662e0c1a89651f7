"""The benchmark: cells, configurations, traffic mixes and metric readers
of ``BENCHMARK.json``, run by ``python3 bench/run.py``."""
