"""Test harness configuration.

All tests run on CPU with 8 virtual XLA devices so multi-chip shardings
(dp/fsdp/tp/sp meshes) are exercised without TPU hardware — the JAX analogue
of the reference's StandaloneTestingProcess multi-rank-on-one-GPU pattern
(realhf/base/testing.py:37-120). Both variables are set before jax is
imported and are inherited by the subprocesses the launcher tests start.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def cpu_devices():
    devices = jax.devices()
    assert len(devices) == 8, f"expected 8 virtual devices, got {len(devices)}"
    return devices


@pytest.fixture(scope="module", autouse=True)
def _reset_global_mesh():
    """Cross-module isolation: a test module must not inherit another
    module's process-global ambient mesh (engines that were never
    destroyed leave theirs installed, and a later module's differently-
    placed arrays would be constrained onto the wrong devices)."""
    yield
    from areal_tpu.parallel import mesh as mesh_lib

    mesh_lib.set_current_mesh(None)
