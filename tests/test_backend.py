"""Backend ladder + process-group lifecycle (SURVEY.md §2b #11)."""

import jax
import pytest

from tpuddp.parallel import backend


@pytest.fixture(autouse=True)
def fresh_state():
    backend.cleanup()
    yield
    backend.cleanup()


def test_ladder_prefers_env_override(monkeypatch):
    monkeypatch.setenv("TPUDDP_BACKEND", "cpu")
    assert backend.detect_backend() == "cpu"


def test_ladder_explicit_prefer():
    assert backend.detect_backend("cpu") == "cpu"


def test_named_backend_is_required(monkeypatch):
    """The CPU world has no TPU: a backend asked for by name (argument or
    environment) raises and never walks on to another; only with neither
    set does the ladder reach cpu."""
    with pytest.raises(backend.BackendUnavailableError, match="'tpu'"):
        backend.detect_backend("tpu")
    monkeypatch.setenv("TPUDDP_BACKEND", "tpu")
    with pytest.raises(backend.BackendUnavailableError, match="'tpu'"):
        backend.detect_backend()
    with pytest.raises(backend.BackendUnavailableError, match="'tpu'"):
        backend.resolve_devices()
    monkeypatch.delenv("TPUDDP_BACKEND")
    assert backend.detect_backend() == "cpu"


def test_serving_main_refuses_unavailable_device(tmp_path):
    """``python -m tpuddp.serving`` honours local.device or refuses it."""
    from tpuddp.serving.__main__ import main

    settings = tmp_path / "s.yaml"
    settings.write_text("local: {device: tpu}\n")
    with pytest.raises(backend.BackendUnavailableError, match="'tpu'"):
        main(["--settings", str(settings), "--demo", "1"])


def test_available_backends_contains_cpu():
    assert "cpu" in backend.available_backends()


def test_setup_cleanup_lifecycle():
    chosen = backend.setup(world_size=8, backend="cpu")
    assert chosen == "cpu"
    assert backend.is_initialized()
    assert backend.get_backend() == "cpu"
    assert backend.get_world_size() == 8
    assert backend.get_rank() == jax.process_index() == 0
    backend.cleanup()
    assert not backend.is_initialized()
    assert backend.get_backend() is None


def test_setup_rejects_oversized_world():
    with pytest.raises(ValueError):
        backend.setup(world_size=4096, backend="cpu")


def test_setup_twice_is_idempotent():
    backend.setup(world_size=4, backend="cpu")
    assert backend.setup(world_size=8, backend="cpu") == "cpu"
    assert backend.get_world_size() == 4  # second call ignored


def test_resolve_devices_slices_world():
    backend.setup(world_size=4, backend="cpu")
    devs = backend.resolve_devices()
    assert len(devs) == 4
