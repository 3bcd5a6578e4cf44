"""The benchmark: harness, traffic generator, trace reduction, reference and
per-layer metric readers.  Entry point: ``python bench/run.py``."""
