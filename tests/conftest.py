"""Test harness: an 8-device CPU-simulated world, no TPU required.

This replaces the reference's Gloo fallback (multi-GPU-training-torch.py:36-37)
as the multi-device-without-accelerators test avenue (SURVEY.md §4): XLA's
host platform is split into 8 virtual devices and the whole framework runs on
them via the backend ladder's CPU rung (TPUDDP_BACKEND=cpu).

Env must be set before jax initializes any backends, hence the top-of-conftest
placement.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("TPUDDP_BACKEND", "cpu")
# Keep test compiles off any real TPU attached to the session.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import pytest  # noqa: E402

WORLD = 8


@pytest.fixture(scope="session")
def cpu_devices():
    devs = jax.devices("cpu")
    assert len(devs) >= WORLD, (
        f"expected >= {WORLD} virtual CPU devices, got {len(devs)} — XLA_FLAGS "
        "was set too late (another conftest/plugin imported jax first?)"
    )
    return devs[:WORLD]


@pytest.fixture(scope="session")
def mesh(cpu_devices):
    from tpuddp.parallel import make_mesh

    return make_mesh(cpu_devices)
