"""The driver's CPU tests: its build, warm-up, measure and verify at a
tiny size by calling the driver (counts and correctness only: no time
or rate of a CPU run is a device metric), the cell's lower-precision
control, and the timed path broken underneath.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q

They compile the program's collector, about half a minute a run, so
they are not among the repo's tier-1 tests; the fast tests of the
harness, the reference and the trace reducer are in tests/benchmark/.
"""
