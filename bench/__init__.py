"""Benchmark of the C3O hub on the chip; see ``bench/run.py``."""
