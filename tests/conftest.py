"""Test config: CPU jax with 8 virtual devices, so multi-chip sharding
logic is exercised without TPU hardware (chip_smoke.py is what runs on
the chip; ``__graft_entry__.dryrun_multichip`` dry-runs the multi-chip
path on virtual devices).

Mirrors the reference's backend-parametrized test strategy (SURVEY.md §4):
the CPU platform is the correctness oracle.

Both variables are set before ``import jax`` and inherited by every
subprocess a test spawns. jax's persistent compilation cache is turned
off: the suite must not fill ``<repo>/.jax_cache`` (the fixed path
``optimize.aot_cache.place_compile_cache`` sets), which the chip tool
would then copy with the tree.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(12345)
