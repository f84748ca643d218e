"""The benchmark: one command, data-driven cells (see run.py, PERF.md)."""
