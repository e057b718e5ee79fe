"""benchmark/tests run on the CPU: `JAX_PLATFORMS=cpu python -m pytest
benchmark/tests -q` from the repo root. Four virtual devices, so that
the tensor-parallel cell's code path can be rehearsed."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=4").strip()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
