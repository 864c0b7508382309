"""Step-level numerical guard + cross-replica desync auditor (ISSUE 3).

PR 1 made tpuddp survive *process-level* failures and PR 2 compressed the
gradient wire; this module defends the *training math itself* — the two
silent killers neither layer sees:

1. **Non-finite gradient firewall** (``GuardConfig.enabled``): inside the
   compiled step, a cheap finiteness check on the *post-allreduce* gradient
   gates the optimizer update through ``lax.cond`` — the sum over replicas
   propagates any replica's NaN/Inf to every replica, so the predicate
   agrees by construction and a bad step becomes a bitwise no-op on
   params/opt-state/error-feedback residual, counted in
   ``TrainState.skipped_steps``.  The torch analog is a fused
   ``GradScaler``-style found-inf skip, minus the mixed-precision scaler.
   Cost model: one fused ``isfinite``-all reduction over the aggregated
   gradient per optimizer update (plus one scalar psum under
   weight-update sharding, whose shards must agree globally); config-off
   builds lower to the identical HLO as an unguarded build.

2. **Desync auditor** (:func:`audit_params`): a lightweight parameter-tree
   fingerprint — per-leaf chunked sums, reduced across the data axis via
   ``pmax - pmin == 0`` — the TPU-mesh analog of torch DDP's wrap-time
   ``_verify_params_across_processes`` and of veScale's first-class
   consistency contract (PAPERS.md).  Run at DDP wrap / Accelerator prepare
   time and every ``audit_every_n_epochs`` epochs; a divergent replica
   surfaces as :class:`ReplicaDesync` -> exit ``EXIT_DESYNC`` (77), the
   "requeue me into auto-resume" signal, or as a rollback to the last
   integrity-verified checkpoint when ``on_desync="rollback"``.  Cost model:
   ONE fingerprint reduction (a chunked-sum pass over the parameters plus a
   pmax/pmin pair on the small fingerprint vectors) per audit — nothing per
   step.

The third leg, **rollback-to-last-good**, lives in the epoch driver
(``training/loop.py``): when ``max_consecutive_skips`` is exceeded, or the
auditor trips with ``on_desync="rollback"``, the driver restores the newest
integrity-verified checkpoint, re-derives the data order for the redone
epoch (``set_epoch``), and records the rollback in ``history.jsonl``.
"""

from __future__ import annotations

import dataclasses
import difflib
import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from tpuddp.parallel.mesh import DATA_AXIS
from tpuddp.resilience.preemption import EXIT_DESYNC

_ON_DESYNC = ("exit", "rollback")


class ReplicaDesync(RuntimeError):
    """Raised when the auditor finds a parameter leaf whose per-replica
    fingerprints disagree (or went non-finite). ``spawn.run_ddp_training``
    converts it into ``sys.exit(EXIT_DESYNC)`` (77) so a scheduler can
    requeue the run into auto-resume."""

    def __init__(self, leaf: str, where: str = "audit"):
        self.leaf = leaf
        self.where = where
        super().__init__(
            f"cross-replica desync at {where}: parameter leaf {leaf!r} differs "
            "between replicas (or is non-finite on all of them); exit "
            f"{EXIT_DESYNC} requeues into auto-resume"
        )


@dataclasses.dataclass(frozen=True)
class GuardConfig:
    """The ``training.guard`` block. ``enabled=False`` (the default) is a
    strict no-op: the step builders take the exact pre-guard code path and
    lower to the identical HLO."""

    enabled: bool = False
    # rollback to the last intact checkpoint once MORE than this many
    # consecutive optimizer updates were skipped by the firewall (a single
    # cosmic-ray step rides through; a persistently-poisoned stream doesn't)
    max_consecutive_skips: int = 3
    # run the desync auditor at the start of every Nth epoch (None: only at
    # wrap/prepare time — the torch _verify_params_across_processes moment)
    audit_every_n_epochs: Optional[int] = None
    on_desync: str = "exit"  # or "rollback" (needs checkpoints in save_dir)
    # rollback-loop bound: after this many rollbacks the run raises instead
    # of replaying a poisoned epoch forever
    max_rollbacks: int = 2


DISABLED = GuardConfig()

_GUARD_KEYS = {f.name for f in dataclasses.fields(GuardConfig)}


def resolve_guard(raw: Any) -> GuardConfig:
    """Parse the ``training.guard`` knob: None/False -> disabled, True -> all
    defaults, a mapping -> overrides (unknown keys refused with a
    did-you-mean hint, same contract as ``config.training_config``), an
    existing :class:`GuardConfig` -> itself."""
    if raw is None or raw is False:
        return DISABLED
    if isinstance(raw, GuardConfig):
        return raw
    if raw is True:
        return GuardConfig(enabled=True)
    if not isinstance(raw, dict):
        raise ValueError(
            f"training.guard must be a bool or a mapping, got {type(raw).__name__}"
        )
    unknown = set(raw) - _GUARD_KEYS
    if unknown:
        hints = []
        for k in sorted(unknown):
            close = difflib.get_close_matches(k, _GUARD_KEYS, n=1)
            hints.append(f"{k!r}" + (f" (did you mean {close[0]!r}?)" if close else ""))
        raise ValueError(
            f"unknown training.guard key(s): {', '.join(hints)}. Known keys: "
            f"{sorted(_GUARD_KEYS)}"
        )
    cfg = dict(raw)
    cfg.setdefault("enabled", True)  # writing the block means wanting it on
    out = GuardConfig(**cfg)
    if out.on_desync not in _ON_DESYNC:
        raise ValueError(
            f"training.guard.on_desync must be one of {_ON_DESYNC}, got "
            f"{out.on_desync!r}"
        )
    if out.max_consecutive_skips < 0:
        raise ValueError("training.guard.max_consecutive_skips must be >= 0")
    if out.audit_every_n_epochs is not None and int(out.audit_every_n_epochs) < 1:
        raise ValueError("training.guard.audit_every_n_epochs must be >= 1")
    return out


# ------------------------------------------------------- skipped counters --


def init_skip_counters():
    """Zeros for ``TrainState.skipped_steps``: total skips since init (the
    monotone record that checkpoints) and the consecutive-run length the
    rollback policy watches (reset by every applied update)."""
    return {
        "total": jnp.zeros((), jnp.int32),
        "consecutive": jnp.zeros((), jnp.int32),
    }


def bump_skip_counters(skipped):
    """The skip branch's counter update (in-jit): total and the consecutive
    run both advance."""
    return {
        "total": skipped["total"] + 1,
        "consecutive": skipped["consecutive"] + 1,
    }


def reset_consecutive(skipped):
    """The apply branch's counter update (in-jit): an applied update ends
    any consecutive-skip run."""
    return {
        "total": skipped["total"],
        "consecutive": jnp.zeros((), jnp.int32),
    }


def read_skip_counters(state) -> Tuple[int, int]:
    """Host ``(total, consecutive)`` of a state's skip counters; (0, 0) for
    unguarded states. One tiny fetch — the epoch driver calls it once per
    epoch, never per step."""
    counters = getattr(state, "skipped_steps", None)
    if counters is None:
        return 0, 0
    total, consec = jax.device_get((counters["total"], counters["consecutive"]))
    return int(total), int(consec)


def tree_all_finite(tree):
    """ONE fused finiteness reduction over a pytree: scalar bool, True iff
    every element of every leaf is finite."""
    leaves = jax.tree_util.tree_leaves(tree)
    ok = jnp.bool_(True)
    for leaf in leaves:
        ok = jnp.logical_and(ok, jnp.all(jnp.isfinite(leaf)))
    return ok


# --------------------------------------------------------- desync auditor --

_FP_CHUNK = 4096  # fingerprint granularity: chunked sums localize a
# divergence to a ~16 KB span without carrying O(params) audit output


def _leaf_fingerprint(leaf):
    flat = jnp.ravel(leaf).astype(jnp.float32)
    pad = (-flat.size) % _FP_CHUNK
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), jnp.float32)])
    return jnp.sum(flat.reshape(-1, _FP_CHUNK), axis=1)


def _make_audit_check(mesh):
    from tpuddp.parallel.mesh import data_axes

    axis = data_axes(mesh)  # the flat "data" axis (also on a 2-D
    # ("data", "model") mesh — TP shards are compared across data replicas
    # ONLY), or the factored ("host", "local") tuple on a hierarchical mesh

    def check(tree):
        fp = jax.tree_util.tree_map(_leaf_fingerprint, tree)
        # identical replicas <=> pmax == pmin elementwise. NaN params poison
        # the subtraction into NaN != 0 — a non-finite parameter tree is
        # reported too (it is never a state worth training on).
        return jax.tree_util.tree_map(
            lambda v: lax.pmax(v, axis) - lax.pmin(v, axis), fp
        )

    return check


@functools.lru_cache(maxsize=8)
def _audit_program(mesh):
    """The compiled fingerprint-and-compare pass for ``mesh`` (cached per
    mesh; jax.jit then caches per parameter tree structure, so repeated
    audits on the same model never recompile)."""
    return jax.jit(
        shard_map(
            _make_audit_check(mesh), mesh=mesh, in_specs=(P(),),
            out_specs=P(), check_vma=False,
        )
    )


def _tp_audit_program(mesh, specs):
    """The tensor-parallel variant: ``specs`` is the parameter tree's
    PartitionSpec pytree (model-axis shards), so every device fingerprints
    its OWN shard and the pmax-pmin compare runs across DATA replicas only —
    a TP shard legitimately differs from its model-axis neighbor and must
    never be convicted for it. The per-shard diff vectors are exposed per
    model index (out spec over the model axis), so a divergence on ANY
    shard group is visible from the host. Built per call — audits run once
    per wrap plus every guard.audit_every_n_epochs, never per step."""
    from tpuddp.parallel.mesh2d import MODEL_AXIS

    out_spec = jax.tree_util.tree_map(lambda _: P(MODEL_AXIS), specs)
    return jax.jit(
        shard_map(
            _make_audit_check(mesh), mesh=mesh, in_specs=(specs,),
            out_specs=out_spec, check_vma=False,
        )
    )


def audit_params(mesh, params, specs=None) -> Optional[str]:
    """Compare every replica's copy of (nominally replicated) ``params``.

    Returns the keystr path of the FIRST divergent leaf, or None when all
    replicas hold bitwise-agreeing fingerprints. Each device hashes its own
    local copy of the buffer, so single-device corruption of a replicated
    array (bad host, bit flip, desynced update) is visible even though JAX
    treats the array as one logical value.

    ``specs`` (a PartitionSpec pytree, the TP wrap's ``tp_param_specs``)
    marks model-axis-sharded parameters on a 2-D mesh: fingerprints then
    cover each device's own shard and the comparison runs across data
    replicas only.
    """
    program = (
        _audit_program(mesh) if specs is None else _tp_audit_program(mesh, specs)
    )
    diffs = program(params)
    flat = jax.tree_util.tree_flatten_with_path(diffs)[0]
    # ONE host fetch for every (small) per-leaf diff vector
    host = jax.device_get([d for _, d in flat])
    for (path, _), diff in zip(flat, host):
        bad = np.asarray(diff)
        if np.any(bad != 0) or not np.all(np.isfinite(bad)):
            return jax.tree_util.keystr(path)
    return None


def audit_or_raise(mesh, params, where: str, specs=None) -> None:
    """Run :func:`audit_params`; raise :class:`ReplicaDesync` naming the
    first divergent leaf. The wrap-time entry point (DDP init_state /
    Accelerator prepare). ``specs`` as in :func:`audit_params`."""
    leaf = audit_params(mesh, params, specs=specs)
    if leaf is not None:
        raise ReplicaDesync(leaf, where=where)
