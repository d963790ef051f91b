"""On-chip serving benchmark: the cells of ``BENCHMARK.json``, run by
``run.py``."""
