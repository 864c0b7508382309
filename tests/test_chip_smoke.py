"""chip_smoke.py's device check and the compile-cache placement rule — both
are about processes, so both run in subprocesses."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_fails_without_a_chip():
    """On the CPU world the smoke exits non-zero, names the platform it found
    and prints no result line."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert "platform 'cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout


_CACHE_PROBE = (
    "import jax; from tpuddp.utils import compile_cache; "
    "before = jax.config.jax_compilation_cache_dir; "
    "print(before, compile_cache.enable(), jax.config.jax_compilation_cache_dir)"
)


def _cache_probe(cwd, placed=None):
    env = dict(os.environ, PYTHONPATH=REPO)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if placed:
        env["JAX_COMPILATION_CACHE_DIR"] = placed
    out = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=120, check=True,
    )
    return out.stdout.split()


def test_compile_cache_placement(tmp_path):
    """Variable set: the cache lives inside that directory. Unset: inside
    one fixed path under the checkout, whatever the working directory. In
    both, in the subdirectory named for the version of the names device
    operations carry (a cache keeps the names it was compiled with)."""
    from tpuddp.observability.profiling import NAMES_VERSION

    placed = str(tmp_path / "placed")
    inside = os.path.join(placed, NAMES_VERSION)
    assert _cache_probe(str(tmp_path), placed) == [placed, inside, inside]
    fixed = os.path.join(REPO, ".jax_cache", NAMES_VERSION)
    assert _cache_probe(str(tmp_path)) == ["None", fixed, fixed]
    assert _cache_probe(REPO) == ["None", fixed, fixed]


@pytest.mark.slow
def test_smoke_body_at_tiny_size_on_cpu(tmp_path):
    """Both legs and every check, on the CPU backend with a toy model: the
    only failures are the two that say the device is not a chip."""
    sys.path.insert(0, REPO)
    import chip_smoke

    training = dict(chip_smoke.TRAINING, model="toy_cnn", image_size=None)
    failures = chip_smoke.smoke(
        "cpu", training, chip_smoke.probe_device(),
        out_dir=str(tmp_path / "out"), run_dir=str(tmp_path / "run"),
    )
    assert failures and all(
        "PEAK_FLOPS" in f for f in failures
    ), failures
