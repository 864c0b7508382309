"""Regression tests for the driver entry hooks (``__graft_entry__.py``).

Round-1 lesson: the driver's multi-chip dryrun failed because unplaced
allocations routed to the default backend instead of the virtual CPU mesh.
These tests run the hooks the way the driver does — in a subprocess with the
platform NOT forced — so a hermeticity regression fails here, not at driver
time.
"""

from __future__ import annotations

import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

if REPO not in sys.path:
    sys.path.insert(0, REPO)


def _driver_env(n: int) -> dict:
    """The driver's env: virtual host devices forced, platform NOT forced.

    Drop the conftest's CPU-forcing vars so the subprocess sees the session
    default; keep only the host-device split the driver also sets.
    """
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env.pop("TPUDDP_BACKEND", None)
    flags = [
        f
        for f in env.get("XLA_FLAGS", "").split()
        if "host_platform_device_count" not in f
    ]
    flags.append(f"--xla_force_host_platform_device_count={n}")
    env["XLA_FLAGS"] = " ".join(flags)
    return env


@pytest.mark.slow
def test_dryrun_multichip_under_driver_env():
    proc = subprocess.run(
        [sys.executable, "-c", "import __graft_entry__ as g; g.dryrun_multichip(8)"],
        cwd=REPO,
        env=_driver_env(8),
        capture_output=True,
        text=True,
        timeout=600,
    )
    assert proc.returncode == 0, (
        f"dryrun_multichip(8) failed under driver env\n"
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    )
    assert "dryrun_multichip ok: 8 devices (platform cpu, kind cpu)" in proc.stdout


@pytest.mark.slow
def test_entry_lowers_and_compiles():
    import __graft_entry__ as g

    fn, args = g.entry()
    compiled = jax.jit(fn).lower(*args).compile()
    assert compiled is not None
