"""Distributed runtime bootstrap — the TPU-native process-group layer.

This is the tpuddp equivalent of the reference's process-group setup
(`multi-GPU-training-torch.py:29-51`):

- reference ``setup(rank, world_size)`` does a TCP rendezvous on
  ``MASTER_ADDR/MASTER_PORT`` and picks a backend with a NCCL -> Gloo -> error
  ladder, then pins the process to ``cuda:rank``;
- here, rendezvous is ``jax.distributed.initialize`` (only needed multi-host —
  on a TPU pod slice each host runs ONE process that owns all of its local
  chips, so there is no per-device process spawn). A backend named in the
  settings file or the environment is required; the **TPU -> CPU -> error**
  ladder walks only when none is named, and says which rung it took. The CPU
  backend uses XLA's host-platform devices
  (``--xla_force_host_platform_device_count=N``) and replaces the reference's
  Gloo fallback as the no-accelerator development/test path.

Device "binding" (reference ``torch.cuda.set_device(rank)``,
multi-GPU-training-torch.py:44) has no TPU analog: XLA owns all local chips and
placement is expressed through shardings on the mesh, not a per-process device.
"""

from __future__ import annotations

import functools
import logging
import os
from typing import Optional, Sequence

import jax
import numpy as np

logger = logging.getLogger("tpuddp")

# Names the backend from the environment, e.g. TPUDDP_BACKEND=cpu in CI.
_BACKEND_ENV = "TPUDDP_BACKEND"

# Module-level runtime state (the "process group").
_state = {
    "initialized": False,
    "backend": None,
    "world_size": None,
    "multihost": False,
}


class BackendUnavailableError(RuntimeError):
    """The backend that was asked for (or, with none named, every rung of the
    ladder) has no usable devices. Mirrors the reference's terminal error
    (`multi-GPU-training-torch.py:38-42`) raised when neither NCCL nor Gloo is
    available."""


def _devices(name: str):
    """``jax.devices(name)``, or :class:`BackendUnavailableError` carrying
    JAX's own message — a chip that fails to initialise or is held by another
    process must surface, not turn into a run on the host."""
    try:
        devs = jax.devices(name)
    except RuntimeError as e:
        raise BackendUnavailableError(f"backend {name!r} is not available: {e}") from e
    if not devs:
        raise BackendUnavailableError(f"backend {name!r} reports no devices")
    return devs


def available_backends() -> list:
    """List usable backends in ladder order (TPU first, then CPU)."""
    out = []
    for name in ("tpu", "cpu"):
        try:
            _devices(name)
        except BackendUnavailableError:
            continue
        out.append(name)
    return out


@functools.lru_cache(maxsize=None)
def _ladder() -> str:
    """The unnamed case: tpu -> cpu -> error, saying once per process which
    rung it took (JAX's backends do not change within a process)."""
    reasons = []
    for name in ("tpu", "cpu"):
        try:
            _devices(name)
        except BackendUnavailableError as e:
            reasons.append(str(e))
            continue
        logger.warning(
            "no backend named (local.device / $%s unset): using %s%s",
            _BACKEND_ENV, name,
            f" — {'; '.join(reasons)}" if reasons else "",
        )
        return name
    raise BackendUnavailableError(
        "Both backends tpu and cpu not available for multi-chip training with "
        "distributed data parallel. " + "; ".join(reasons)
    )


def detect_backend(prefer: Optional[str] = None) -> str:
    """A backend asked for by name — ``prefer`` (``local.device``) or
    $TPUDDP_BACKEND — is REQUIRED: if it has no devices this raises
    :class:`BackendUnavailableError` and never trains on another one. Only
    with neither set does the tpu -> cpu ladder walk, mirroring the NCCL ->
    Gloo -> raise ladder at multi-GPU-training-torch.py:34-42."""
    prefer = prefer or os.environ.get(_BACKEND_ENV)
    if prefer:
        _devices(prefer)
        return prefer
    return _ladder()


def setup(
    world_size: Optional[int] = None,
    backend: Optional[str] = None,
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> str:
    """Initialize the distributed runtime and return the selected backend name.

    Single-host: selects a backend via :func:`detect_backend` and records the
    world size (defaults to all local devices of that backend).

    Multi-host (TPU pod): pass ``coordinator_address`` (the analog of the
    reference's ``MASTER_ADDR:MASTER_PORT``, multi-GPU-training-torch.py:30-31)
    or set the standard TPU pod env so ``jax.distributed.initialize`` can
    auto-discover peers.
    """
    if _state["initialized"]:
        logger.warning("tpuddp.setup() called twice; ignoring second call")
        return _state["backend"]

    multihost = coordinator_address is not None or (
        num_processes is not None and num_processes > 1
    )
    if multihost:
        # import the submodule directly: the package __init__ re-exports the
        # retry FUNCTION under the same name, so `from tpuddp.resilience
        # import retry` binds the callable, not the module
        from tpuddp.resilience.retry import RetryPolicy as _RetryPolicy
        from tpuddp.resilience.retry import retry as _retry

        # The rendezvous is the classic transient failure: N hosts race to
        # come up and the coordinator may not be listening yet. Jittered
        # backoff (3 attempts) decorrelates the herd; the terminal RetryError
        # names the coordinator so the failure is actionable.
        _retry(
            lambda: jax.distributed.initialize(
                coordinator_address=coordinator_address,
                num_processes=num_processes,
                process_id=process_id,
            ),
            _RetryPolicy(max_attempts=3, base_delay=2.0, max_delay=15.0),
            describe=(
                f"jax.distributed.initialize (coordinator "
                f"{coordinator_address or 'auto-discovered'})"
            ),
        )

    chosen = detect_backend(backend)
    devices = _devices(chosen)
    if world_size is None:
        world_size = len(devices)
    if world_size > len(devices) and jax.process_count() == 1:
        raise ValueError(
            f"world_size={world_size} exceeds the {len(devices)} available "
            f"{chosen} devices on this host. For a CPU development world, set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=N before importing jax."
        )

    _state.update(
        initialized=True,
        backend=chosen,
        world_size=world_size,
        multihost=multihost or jax.process_count() > 1,
    )
    # Parity with the reference's post-init banner (multi-GPU-training-torch.py:46-47).
    logger.info(
        "Process group initialized with backend %s, process %d, world size %d.",
        chosen,
        jax.process_index(),
        world_size,
    )
    return chosen


def cleanup() -> None:
    """Tear down the runtime. Analog of ``dist.destroy_process_group()``
    (multi-GPU-training-torch.py:50-51)."""
    if _state.get("multihost") and jax.process_count() > 1:
        try:
            jax.distributed.shutdown()
        except Exception:  # pragma: no cover - shutdown is best-effort
            logger.exception("jax.distributed.shutdown failed")
    _state.update(initialized=False, backend=None, world_size=None, multihost=False)


def is_initialized() -> bool:
    return bool(_state["initialized"])


def get_backend() -> Optional[str]:
    """Analog of ``dist.get_backend()``."""
    return _state["backend"]


def get_rank() -> int:
    """Analog of ``dist.get_rank()`` — on TPU the unit is the *process* (host),
    each of which drives all of its local chips."""
    return jax.process_index()


def get_world_size() -> int:
    """Analog of ``dist.get_world_size()`` — the number of devices in the data
    axis (per-chip granularity, unlike get_rank's per-host granularity)."""
    if _state["world_size"] is not None:
        return _state["world_size"]
    return jax.device_count()


def resolve_devices(
    world_size: Optional[int] = None, backend: Optional[str] = None
) -> Sequence[jax.Device]:
    """Pick the devices that form the data-parallel world.

    Multi-process: always the full global device list (every process must agree
    on mesh devices). Single-process: the first ``world_size`` local devices of
    the detected backend.
    """
    chosen = backend or _state["backend"] or detect_backend()
    devices = _devices(chosen)
    if jax.process_count() > 1:
        return devices
    if world_size is None:
        world_size = _state["world_size"] or len(devices)
    if world_size > len(devices):
        raise ValueError(
            f"world_size={world_size} > available {chosen} devices ({len(devices)})"
        )
    return devices[:world_size]
