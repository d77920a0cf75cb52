"""Test harness: run everything on a virtual 8-device CPU mesh.

Multi-chip sharding (DP/SP) is validated without TPU hardware by forcing the
host platform to expose 8 XLA CPU devices (SURVEY.md §4).  Must run before
jax initialises a backend, hence module-level env mutation in conftest.
"""

import os

# Tests normally run on CPU (overriding any pinned accelerator platform) so
# the 8-device virtual mesh is available and numerics are deterministic.
# Both the env var and jax.config are set: jax may already be imported.
# FMDA_TESTS_KEEP_PLATFORM=1 leaves the pinned backend alone so the
# TPU-gated tests (test_pallas_gru.py::test_pallas_kernel_on_tpu_device)
# can reach hardware — without it they skip unconditionally.  Strictly
# "1", and only for running those tests in isolation; a full-suite run
# with this set would hard-fail the 8-device mesh tests on a 1-chip
# backend.  (chip_smoke.py is what runs the kernels on the chip.)
_KEEP_PLATFORM = os.environ.get("FMDA_TESTS_KEEP_PLATFORM", "") == "1"

if not _KEEP_PLATFORM:
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()

import jax  # noqa: E402

if not _KEEP_PLATFORM:
    jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    # tier-1 runs `-m 'not slow'`; the spawned-process chaos soak is the
    # first slow-marked test — register the marker so it stays declared
    config.addinivalue_line(
        "markers",
        "slow: excluded from tier-1 (long multi-process soaks; "
        "run explicitly: -m slow)")


@pytest.fixture
def rng():
    return np.random.default_rng(0)
