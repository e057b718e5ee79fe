"""Test harness: run everything on an 8-device emulated CPU mesh.

The reference ships zero tests (SURVEY.md §4); this suite is designed
from scratch. Sharding correctness is validated without TPU hardware by
forcing the JAX CPU backend with 8 virtual devices, so pjit/shard_map
paths compile and execute real collectives.
"""

import os

# The test suite always runs on the emulated 8-device CPU backend (the
# chip is driven by chip_smoke.py, not pytest). Both variables must be
# set before jax is imported.
os.environ["JAX_PLATFORMS"] = "cpu"
import re

flags = os.environ.get("XLA_FLAGS", "")
flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "", flags)
os.environ["XLA_FLAGS"] = (
    flags + " --xla_force_host_platform_device_count=8"
).strip()

import jax  # noqa: E402

# The persistent XLA compile cache must not leak between machines or
# between test runs — off for the whole suite, whatever the environment
# says.
jax.config.update("jax_enable_compilation_cache", False)

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def eight_devices():
    import jax

    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 emulated devices, got {len(devs)}"
    return devs


@pytest.fixture()
def default_config():
    from generativeaiexamples_tpu.config import AppConfig

    return AppConfig()
