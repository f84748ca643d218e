import os
import sys

# Run the test suite on a virtual 8-device CPU mesh so multi-chip sharding
# is exercised without TPU hardware.
sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
from __graft_entry__ import force_virtual_cpu_devices  # noqa: E402

force_virtual_cpu_devices(8)
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402

assert jax.default_backend() == "cpu", jax.default_backend()

# Persist XLA compilations across suite runs: most of the suite's wall
# time is compiles of the same programs every run. Cache entries are
# keyed by backend/topology, so the 8-device-CPU test programs coexist
# with the chip's in the same cache directory.
from sparksched_tpu.config import enable_compilation_cache  # noqa: E402

enable_compilation_cache()
