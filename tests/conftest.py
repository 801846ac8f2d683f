"""Test harness: force an 8-device virtual CPU platform BEFORE jax imports.

This is the JAX-native analog of a fake/mock distributed backend: every
pjit/shard_map/ring-collective test runs multi-device on CPU without TPU
hardware (SURVEY.md §4d). Must run before any test module imports jax.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
# PIN the 8-device virtual platform unconditionally — replacing any
# pre-existing xla_force_host_platform_device_count, not just appending
# when absent: an inherited =1 from the environment would silently turn
# every multi-device test into a skip/failure on a fresh checkout.
_flags = [
    f
    for f in os.environ.get("XLA_FLAGS", "").split()
    if "xla_force_host_platform_device_count" not in f
]
os.environ["XLA_FLAGS"] = " ".join(
    _flags + ["--xla_force_host_platform_device_count=8"]
)
# Determinism and precision: CPU tests compare against a float64 numpy oracle.
os.environ.setdefault("JAX_ENABLE_X64", "0")

# The suite checks behaviour, not start-up: no persistent compile cache, so a
# run neither reads nor leaves compiled programs in the checkout
# (utils/startup.enable_compile_cache places one for the entry points).
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "0"

import jax  # noqa: E402

# Also at the config level: an earlier plugin or import may have fixed
# jax_platforms before conftest ran.
jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(0)
