"""The on-chip benchmark: one cell (a graph configuration under one
traffic mix) per run of ``bench/run.py``. See ``BENCHMARK.json`` at the
checkout root for the cells and metrics, and ``PERF.md`` for why."""
