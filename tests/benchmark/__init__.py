"""The benchmark's fast CPU tests (the drivers' own, which compile the
programs at a tiny size, are under benchmarks/tests/)."""
