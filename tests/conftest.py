"""Test harness: force an 8-device virtual CPU platform before jax imports.

Mirrors the reference stack's test methodology tier (b) (SURVEY.md §5):
simulated multi-device meshes without hardware, via
``--xla_force_host_platform_device_count``.

What forces CPU for the test run is ``JAX_PLATFORMS=cpu`` (set here and, for
good measure, through ``jax.config.update`` below), plus XLA_FLAGS being set
before the CPU backend is first touched.  The suite never needs a chip; what
needs one is ``chip_smoke.py``, run through the chip tool.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402

from distributed_tensorflow_tpu.cluster import MeshConfig, build_mesh  # noqa: E402


@pytest.fixture(scope="session")
def devices8():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 forced CPU devices, got {devs}"
    return devs


@pytest.fixture(scope="session")
def mesh_dp(devices8):
    """Pure data-parallel mesh: data=8."""
    return build_mesh(MeshConfig(), devices8)


@pytest.fixture(scope="session")
def mesh_2d(devices8):
    """data=4 x tensor=2."""
    return build_mesh(MeshConfig(data=4, tensor=2), devices8)


@pytest.fixture(scope="session")
def mesh_4d(devices8):
    """data=2 x tensor=2 x pipe=1 x context=2 (exercises several axes)."""
    return build_mesh(MeshConfig(data=2, tensor=2, context=2), devices8)
