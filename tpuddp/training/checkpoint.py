"""Checkpointing — save/restore of arbitrary pytrees with the reference's
single-writer discipline, plus the resume path the reference lacks.

Reference contract (multi-GPU-training-torch.py:217-223; SURVEY.md §2b #18):
rank 0 saves ``ckpt_{epoch}`` every ``checkpoint_epoch`` epochs, then a
barrier so no reader races the writer. Divergences, deliberate and documented:

- the saved tree is the *unwrapped* state (quirk Q4: the reference saves the
  DDP-wrapped, ``module.``-prefixed state dict; the accelerate path saves
  unwrapped — tpuddp follows the accelerate/unwrapped convention);
- a load/resume path exists (the reference only documents loading,
  README.md:51-52).

Format: a single ``.npz`` holding flattened leaves keyed by their pytree
paths. PRNG key arrays are stored via ``jax.random.key_data`` and re-wrapped
on load. Loading requires a template ("like") pytree for the treedef — the
natural JAX analog of ``model.load_state_dict``.

Resilience (ISSUE 1): every save publishes a ``.sha256`` sidecar manifest;
``latest()`` verifies candidates newest-first and *skips* corrupt/truncated
files with a logged warning instead of crashing the resume path; a small
``__meta__*`` record inside the npz distinguishes end-of-epoch checkpoints
(``completed=1`` -> resume at epoch+1) from preemption-drain emergency saves
(``completed=0`` -> redo the interrupted epoch); ``keep_last`` pruning bounds
checkpoint disk on long runs.

Elastic resume (ISSUE 7): checkpoints written through ``save_on_main`` carry
a **format-v2 topology record** — world size, mesh axes/shape, and a per-leaf
shard tag for every world-size-DEPENDENT leaf (the weight-update-sharded flat
optimizer vectors, padded to a world multiple, and the bf16_ef per-replica
error-feedback residual). Replicated leaves are world-independent and carry
no tag. On ``load``/``restore_latest`` onto a *different* world size M (the
checkpoint's was N):

- untagged (replicated) leaves load unchanged — the broadcast is implicit;
- ``data_flat`` leaves (flat vectors zero-padded to a world multiple) are
  re-padded to the new world's length — exact, because the tail past the raw
  element count is zeros by construction;
- ``per_replica`` leaves (the ``(N * per,)`` bf16_ef residual) are
  redistributed **sum-preservingly** when M | N or N | M
  (:func:`tpuddp.parallel.comm.redistribute_residual`), and RESET to zero
  (with a typed ``comm_state_reset`` event handed to the caller's
  ``reshard_log``) when neither divides — the documented fallback.

Same-topology loads take the identical byte-for-byte path as before (shapes
match, no reshard). v1 checkpoints (no topology record) keep loading
unchanged on their original topology; loaded onto a DIFFERENT world size
their world-dependent leaves mismatch and raise :class:`TopologyMismatch`
pointing at the v2 elastic path instead of reshaping or mis-slicing.

2-D mesh (format v3, ISSUE 14): checkpoints written on a ``("data",
"model")`` mesh additionally record the **model width** and a per-leaf
``placement`` map (which mesh axes each sharded leaf's dimensions split
over). Parameter/moment leaves are stored as their FULL logical arrays (the
single-controller save gathers shards transparently), so they are
model-width-independent on disk — what is NOT width-independent is the
per-``(data, model)``-device error-feedback residual. ``load`` /
``restore_latest`` take the current ``model_size``; by default a
cross-model-width restore REFUSES with a typed :class:`TopologyMismatch`
instead of mis-slicing. With ``reshard_on_mismatch=True`` (the
``training.reshard_on_mismatch`` knob) the payload is first re-shaped
in-memory by :mod:`tpuddp.training.reshard` — the cross-topology reshaper
behind ``tpuddp_inspect reshard`` — and then loads on the target mesh; see
that module's doc for the exact/reset contract (README "2-D mesh"). A v2
file written on a 2-D mesh carries the mesh axes/shape, so the same rules
apply to it; a v1 file (no topology record) still refuses either way.
"""

from __future__ import annotations

import json
import logging
import os
import re
from typing import Any, Dict, List, Optional, Tuple

import jax
import ml_dtypes
import numpy as np

from tpuddp.parallel import collectives as col
from tpuddp.parallel.mesh import place_like
from tpuddp.resilience import faults, integrity

logger = logging.getLogger("tpuddp")

FORMAT_VERSION = 4  # v2 = topology record present (elastic resume);
# v3 = the record additionally carries model_size + per-leaf mesh-axis
# placement tags (the 2-D ("data", "model") mesh — ISSUE 14). v2 files keep
# loading: readers key on record CONTENTS, and a v2 record written on a 2-D
# mesh already names its mesh axes/shape, so the cross-model-width refusal
# covers it too. v4 = the file MAY carry a ``__cursor__`` data-cursor record
# (epoch, step, sampler epoch-plan key, partial metric accumulator) written
# by step-granular snapshots — restore_latest resumes EXACTLY mid-epoch from
# it instead of redoing the interrupted epoch. Cursor-less v4 files are
# byte-compatible with v3; v3 readers never see the cursor (template
# iteration skips dunder entries, like the meta/topology records).

_KEY_MARK = "__prngkey__"
_BF16_MARK = "__bf16__"  # npz can't serialize ml_dtypes natively (loads back
# as void16); bf16 leaves — e.g. Adam moments under optimizer_state_dtype —
# are stored as a uint16 bit view and re-viewed on load.
_META_MARK = "__meta__"  # scalar bookkeeping (epoch, completed flag) stored
# alongside the leaves; load() iterates the template's leaves so meta keys are
# invisible to it, and read_meta() reads them without needing a template.
_TOPO_MARK = "__topology__"  # v2: one JSON record (world size, mesh axes, and
# per-leaf shard tags for world-size-dependent leaves) — the metadata the
# elastic reshard path needs; invisible to template iteration like the meta.
_CURSOR_MARK = "__cursor__"  # v4: one JSON record — the DATA CURSOR of a
# step-granular snapshot (epoch, step = real micro-batches applied, the
# sampler epoch-plan key, and the names of the partial-accumulator arrays
# stored under _CURSOR_ACC_MARK). Its presence marks a mid-epoch snapshot;
# restore_latest surfaces it so the driver replays ZERO batches.
_CURSOR_ACC_MARK = "__cursor_acc__"  # v4: the partial per-epoch metric
# accumulator (e.g. {loss_sum, n} device fold) at the snapshot step, one
# array per entry — seeding the resumed epoch's fold keeps the loss
# trajectory bitwise-equal to an uninterrupted run.


class TopologyMismatch(ValueError):
    """A checkpoint's world-size-dependent state cannot be fitted onto the
    current topology: either the file predates the v2 topology record (v1
    checkpoints have no resharding story) or the elastic reshard lacks the
    information it needs (e.g. the current world size)."""


def _path_str(path) -> str:
    return jax.tree_util.keystr(path)


# Leaf-path anchors for world-size-dependent state. Anchored to the
# TrainState fields / managed state-dict entries — a model parameter whose
# own name merely CONTAINS "comm_state" must not match.
_COMM_FLAT_KEYS = (".comm_state", "['comm_state']")  # the flat residual vector


def _is_opt_state_key(key: str) -> bool:
    return key.startswith(".opt_state") or key.startswith("['opt_state']")


def _is_world_dependent_key(key: str) -> bool:
    """Could this leaf's shape depend on the world size? (The flat bf16_ef
    residual and the weight-update-sharded flat optimizer vectors do; params,
    buffers, counters, and tree-shaped moments never do.)"""
    return key in _COMM_FLAT_KEYS or _is_opt_state_key(key)


def derive_topology(tree: Any, world_size: Optional[int] = None) -> Optional[dict]:
    """The v2 topology record for ``tree``: world size, mesh axes/shape, and
    a shard tag per world-size-dependent leaf. Derived from the leaves' live
    ``NamedSharding``s (the common case: a training state still on the mesh);
    ``world_size`` overrides/supplies the world when shardings are absent
    (host-array trees, multi-host states already gathered). Returns None when
    no world size is derivable — the save then carries no topology record
    and loads with v1 semantics."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    mesh_axes = mesh_shape = None
    world = int(world_size) if world_size else None

    def sharding_of(leaf):
        if isinstance(leaf, jax.Array):
            sh = getattr(leaf, "sharding", None)
            if sh is not None and getattr(sh, "mesh", None) is not None:
                return sh
        return None

    for _p, leaf in flat:
        sh = sharding_of(leaf)
        if sh is not None:
            try:
                mesh = sh.mesh
                mesh_axes = [str(a) for a in mesh.axis_names]
                mesh_shape = [int(d) for d in np.shape(mesh.devices)]
                if world is None:
                    world = int(np.prod(mesh_shape))
            except Exception:  # AbstractMesh etc.: keep what we have
                pass
            break
    if world is None:
        return None
    model = 1
    if mesh_axes and mesh_shape and "model" in mesh_axes:
        model = int(mesh_shape[mesh_axes.index("model")])

    def spec_axes(sh):
        """JSON-able per-dimension mesh-axis placement of a NamedSharding's
        spec (tuple entries become lists) — the v3 leaf placement tag."""
        try:
            out = []
            for entry in tuple(sh.spec):
                if entry is None:
                    out.append(None)
                elif isinstance(entry, (tuple, list)):
                    out.append([str(a) for a in entry])
                else:
                    out.append(str(entry))
            return out
        except Exception:
            return None

    leaves: Dict[str, dict] = {}
    placement: Dict[str, list] = {}
    for p, leaf in flat:
        key = _path_str(p)
        sh = sharding_of(leaf)
        sharded = sh is not None and not sh.is_fully_replicated
        if sharded:
            # v3: every sharded leaf names the mesh axes each dimension
            # splits over — params/moments on the model axis included (they
            # are SAVED as full gathered arrays, so the tag is provenance
            # plus the refusal surface, not a reshape instruction)
            axes = spec_axes(sh)
            if axes is not None:
                placement[key] = axes
        if np.ndim(leaf) != 1:
            continue
        n = int(np.shape(leaf)[0])
        if key in _COMM_FLAT_KEYS:
            if sharded and n % world == 0:
                # shard_map EF residual: (world * per,) per-replica slices.
                # On a 2-D mesh the slices key by (data_index, model_index)
                # — "model" > 1 marks them NON-redistributable across any
                # width change (the typed-refusal path).
                leaves[key] = {
                    "kind": "per_replica", "world": world, "per": n // world,
                    "model": model,
                }
            else:
                # auto-mode bf16_ef: the replicated (total,) aggregate
                # residual — world-dependent only through its padding
                leaves[key] = {"kind": "data_flat"}
        elif _is_opt_state_key(key) and sharded and model == 1:
            # weight-update-sharded flat moment vector: (total,) padded to a
            # world multiple, sharded over the data axis — re-padded on load
            leaves[key] = {"kind": "data_flat"}
    return {
        "format": FORMAT_VERSION,
        "world_size": world,
        "model_size": model,
        "mesh_axes": mesh_axes,
        "mesh_shape": mesh_shape,
        "leaves": leaves,
        "placement": placement,
    }


def read_topology(path: str) -> Optional[dict]:
    """The v2/v3 topology record of a checkpoint (None for v1 files)."""
    with np.load(path) as data:
        if _TOPO_MARK not in data.files:
            return None
        return json.loads(str(np.asarray(data[_TOPO_MARK]).item()))


def topology_model_size(topo: Optional[dict]) -> int:
    """The model-axis width a checkpoint was written under: the explicit v3
    field, else derived from the v2 record's mesh axes (a v2 file written on
    a 2-D mesh already named them), else 1 — every 1-D data mesh IS the
    model=1 case."""
    if not topo:
        return 1
    if topo.get("model_size") is not None:
        return int(topo["model_size"])
    axes, shape = topo.get("mesh_axes"), topo.get("mesh_shape")
    if axes and shape and "model" in axes:
        return int(shape[list(axes).index("model")])
    return 1


def _check_model_width(path: str, topo: Optional[dict], model_size) -> None:
    """The cross-``model``-width refusal (ISSUE 14 satellite): a checkpoint
    written under one tensor-parallel width restored under another would
    mis-slice its per-device state (and a v1 file has no mesh record at
    all) — raise the typed mismatch instead. Same width passes; the data
    axis keeps its own elastic rules."""
    cur = 1 if model_size is None else int(model_size)
    if topo is None:
        if cur > 1:
            raise TopologyMismatch(
                f"checkpoint {path} predates the topology record (format v1) "
                f"and cannot be restored onto a model={cur} tensor-parallel "
                "mesh: it carries no shard provenance, so even the reshaper "
                "refuses it. Resume it on a pure-DP world (model=1) or "
                "re-save it through save_on_main (format v3) first."
            )
        return
    saved = topology_model_size(topo)
    if saved != cur:
        raise TopologyMismatch(
            f"checkpoint {path} was written on a model={saved} mesh but the "
            f"current run is model={cur}. Cross-topology restore is opt-in: "
            "set training.reshard_on_mismatch=true to reshard on load, or "
            "reshape the file offline with `tpuddp_inspect reshard "
            f"--to data=D,model={cur}` (README '2-D mesh' documents which "
            "reshapes are exact and which reset the comm residual)."
        )


def save(
    path: str,
    tree: Any,
    meta: Optional[Dict[str, int]] = None,
    topology: Optional[dict] = None,
    cursor: Optional[dict] = None,
    cursor_acc: Optional[Any] = None,
) -> str:
    """Serialize a pytree to ``path`` (.npz). Caller handles rank gating.
    ``meta``: optional dict of int scalars (e.g. epoch, completed) stored as
    ``__meta__*`` entries, readable via :func:`read_meta` without a template.
    ``topology``: the v2 elastic record (see :func:`derive_topology`) —
    stored as a ``__topology__`` JSON entry whose presence marks the file
    format v2; None writes a v1-compatible file (no resharding story).
    ``cursor``: the v4 data-cursor record of a step-granular snapshot
    (JSON-able dict; see :mod:`tpuddp.training.snapshot`), with
    ``cursor_acc`` the partial metric-accumulator pytree stored alongside it.
    A ``.sha256`` manifest sidecar is published after the data file so
    ``latest()`` can verify integrity before trusting a checkpoint.
    The publish is durable: the staged bytes are fsync'd before the atomic
    rename, so a host crash right after ``save`` returns cannot leave a
    checkpoint that is intact in the page cache but torn on disk."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    payload = {}
    for p, leaf in flat:
        key = _path_str(p)
        arr = leaf
        if hasattr(arr, "dtype") and jax.dtypes.issubdtype(arr.dtype, jax.dtypes.prng_key):
            payload[_KEY_MARK + key] = np.asarray(jax.random.key_data(arr))
        elif hasattr(arr, "dtype") and arr.dtype == ml_dtypes.bfloat16:
            payload[_BF16_MARK + key] = np.asarray(arr).view(np.uint16)
        else:
            payload[key] = np.asarray(arr)
    if topology is not None:
        # the record's presence IS the v2 marker (read_topology returns None
        # for v1 files); the meta scalars stay exactly the v1 set so
        # pre-elastic readers of read_meta() see an unchanged contract
        payload[_TOPO_MARK] = np.asarray(json.dumps(topology))
    if cursor is not None:
        acc_payload = {}
        if cursor_acc is not None:
            for p, leaf in jax.tree_util.tree_flatten_with_path(cursor_acc)[0]:
                k = _path_str(p)
                if hasattr(leaf, "dtype") and leaf.dtype == ml_dtypes.bfloat16:
                    acc_payload[_CURSOR_ACC_MARK + _BF16_MARK + k] = (
                        np.asarray(leaf).view(np.uint16)
                    )
                else:
                    acc_payload[_CURSOR_ACC_MARK + k] = np.asarray(leaf)
        record = dict(cursor)
        record["acc_keys"] = sorted(acc_payload)
        payload[_CURSOR_MARK] = np.asarray(json.dumps(record, sort_keys=True))
        for k in sorted(acc_payload):
            payload[k] = acc_payload[k]
    for k, v in (meta or {}).items():
        payload[_META_MARK + k] = np.asarray(int(v), dtype=np.int64)
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        np.savez(f, **payload)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)  # atomic publish, no torn checkpoints
    integrity.write_manifest(path)
    return path


def read_cursor(path: str) -> Optional[dict]:
    """The v4 data-cursor record of a step-granular snapshot, with its
    partial accumulator re-inflated under ``"acc"`` (a flat dict keyed by
    the original pytree paths). None for epoch-granular / pre-v4 files."""
    with np.load(path) as data:
        if _CURSOR_MARK not in data.files:
            return None
        record = json.loads(str(np.asarray(data[_CURSOR_MARK]).item()))
        acc: Dict[str, np.ndarray] = {}
        for k in record.pop("acc_keys", []):
            if k not in data.files:
                continue
            name = k[len(_CURSOR_ACC_MARK):]
            if name.startswith(_BF16_MARK):
                acc[name[len(_BF16_MARK):]] = np.asarray(data[k]).view(
                    ml_dtypes.bfloat16
                )
            else:
                acc[name] = np.asarray(data[k])
        record["acc"] = acc or None
        return record


def read_meta(path: str) -> Dict[str, int]:
    """The ``__meta__*`` scalars of a checkpoint (empty for pre-meta files)."""
    out: Dict[str, int] = {}
    with np.load(path) as data:
        for k in data.files:
            if k.startswith(_META_MARK):
                out[k[len(_META_MARK) :]] = int(data[k])
    return out


def _check_dtype(path: str, key: str, stored: np.ndarray, template: Any) -> None:
    t_dtype = np.asarray(template).dtype if not hasattr(template, "dtype") else template.dtype
    if stored.dtype != t_dtype:
        raise ValueError(
            f"checkpoint {path}: leaf {key!r} has dtype {stored.dtype} but "
            f"the model expects {t_dtype} (if this is optimizer state, check "
            "training.optimizer_state_dtype matches the saved run)"
        )


def _refit_flat(path: str, key: str, stored: np.ndarray, t_shape) -> np.ndarray:
    """Re-pad a flat world-padded vector (WUS moments, the auto-mode bf16_ef
    residual) to the current world's length. Exact: both lengths are the raw
    element count padded up to a world multiple, and every element past the
    raw count is zero by construction — so truncating a longer vector may
    only drop zeros (verified), and growing one appends zeros."""
    n_new = int(t_shape[0])
    n_old = int(stored.shape[0])
    if n_new < n_old and np.any(stored[n_new:]):
        raise TopologyMismatch(
            f"checkpoint {path}: flat leaf {key!r} has {n_old} elements but "
            f"the current topology expects {n_new}, and the tail past "
            f"{n_new} is non-zero — this is not world-multiple padding (was "
            "the model changed, not just the world size?)"
        )
    out = np.zeros((n_new,), stored.dtype)
    out[: min(n_old, n_new)] = stored[: min(n_old, n_new)]
    return out


def _fit_leaf(
    path: str,
    key: str,
    stored: np.ndarray,
    template: Any,
    topo: Optional[dict],
    world_size: Optional[int],
    actions: Optional[List[dict]],
) -> np.ndarray:
    """Shape/dtype validation against the template leaf — the analog of
    torch ``load_state_dict``'s size-mismatch error — PLUS the elastic
    reshard path: a v2-tagged world-size-dependent leaf whose shape differs
    from the template's is re-fitted to the current topology instead of
    failing. A same-layout checkpoint with different widths (e.g. a 12-class
    head into a 10-class model) must still fail loudly here, not train
    silently with wrong-width logits."""
    t_shape = tuple(np.shape(template))
    if tuple(stored.shape) == t_shape:
        _check_dtype(path, key, stored, template)
        return stored  # same topology: byte-identical fast path
    info = ((topo or {}).get("leaves") or {}).get(key)
    if info is None:
        if topo is None and _is_world_dependent_key(key) and stored.ndim == 1 and len(t_shape) == 1:
            raise TopologyMismatch(
                f"checkpoint {path}: world-size-dependent leaf {key!r} has "
                f"shape {tuple(stored.shape)} but the current topology "
                f"expects {t_shape}. This checkpoint predates the format-v2 "
                "topology record and cannot be resharded onto a different "
                "world size — resume it on the topology that wrote it, or "
                "re-save it through save_on_main (elastic v2) first."
            )
        raise ValueError(
            f"checkpoint {path}: leaf {key!r} has shape {tuple(stored.shape)} "
            f"but the model expects {t_shape}"
        )
    _check_dtype(path, key, stored, template)
    from_world = int((topo or {}).get("world_size") or 0) or None
    if info["kind"] == "data_flat":
        out = _refit_flat(path, key, stored, t_shape)
        if actions is not None:
            actions.append({
                "leaf": key, "action": "repadded",
                "from_shape": list(stored.shape), "to_shape": list(t_shape),
            })
        return out
    if info["kind"] == "per_replica":
        if int(info.get("model", 1) or 1) > 1:
            # a 2-D-mesh residual keys by (data_index, model_index); the
            # row-group redistribution below assumes pure data rows. The
            # reshaper (tpuddp.training.reshard) redistributes it per model
            # column — this in-loader path refuses so the opt-in stays the
            # single entry point for cross-topology fitting.
            raise TopologyMismatch(
                f"checkpoint {path}: per-replica leaf {key!r} was written on "
                f"a model={info['model']} mesh under a different data width; "
                "set training.reshard_on_mismatch=true (or reshape offline "
                "with `tpuddp_inspect reshard`) to redistribute it, or "
                "resume on the same (data, model) grid"
            )
        if world_size is None:
            raise TopologyMismatch(
                f"checkpoint {path}: per-replica leaf {key!r} (saved on a "
                f"{info['world']}-replica world) needs the CURRENT world "
                "size to redistribute; pass world_size= to load/"
                "restore_latest (the epoch drivers do)"
            )
        from tpuddp.parallel.comm import redistribute_residual

        n_from, per_from = int(info["world"]), int(info["per"])
        if stored.shape[0] != n_from * per_from:
            raise TopologyMismatch(
                f"checkpoint {path}: per-replica leaf {key!r} has "
                f"{stored.shape[0]} elements but its topology record says "
                f"{n_from} x {per_from}"
            )
        if int(t_shape[0]) % int(world_size) != 0:
            raise TopologyMismatch(
                f"checkpoint {path}: per-replica leaf {key!r} target length "
                f"{t_shape[0]} is not a multiple of world_size={world_size}"
            )
        per_to = int(t_shape[0]) // int(world_size)
        mat = stored.reshape(n_from, per_from)
        # column re-pad first (the per-replica vector is itself world-padded)
        if per_from != per_to:
            cols = np.zeros((n_from, per_to), stored.dtype)
            keep = min(per_from, per_to)
            if per_from > per_to and np.any(mat[:, per_to:]):
                raise TopologyMismatch(
                    f"checkpoint {path}: per-replica leaf {key!r} carries "
                    f"non-zero data past the current per-replica length "
                    f"{per_to} — not world-multiple padding"
                )
            cols[:, :keep] = mat[:, :keep]
            mat = cols
        new_mat, action = redistribute_residual(mat, int(world_size))
        if actions is not None:
            actions.append({
                "leaf": key, "action": action,
                "from_world": n_from, "to_world": int(world_size),
            })
        if action == "reset":
            logger.warning(
                "checkpoint %s: per-replica leaf %r cannot be redistributed "
                "sum-preservingly from world %d to %d (no divisor relation); "
                "residual RESET to zero",
                path, key, n_from, world_size,
            )
        return new_mat.reshape(-1)
    raise TopologyMismatch(
        f"checkpoint {path}: leaf {key!r} has unknown shard tag {info!r}"
    )


def load_with_topology(
    path: str,
    like: Any,
    world_size: Optional[int] = None,
    reshard_actions: Optional[List[dict]] = None,
    model_size: Optional[int] = None,
    reshard_on_mismatch: bool = False,
) -> Tuple[Any, Optional[dict]]:
    """:func:`load` plus the file's parsed topology record (None for v1) —
    one file open for callers that need both (restore_latest, the managed
    load_state). ``model_size`` is the CURRENT tensor-parallel width (None =
    1, every pre-2-D caller); a width mismatch against the file's record is
    a typed :class:`TopologyMismatch` BEFORE any leaf is touched — unless
    ``reshard_on_mismatch`` (the ``training.reshard_on_mismatch`` knob)
    opts into the cross-topology reshaper, which re-shapes the payload
    in-memory onto the current ``(data, model)`` mesh first. Template
    validation still runs on the resharded payload, so genuinely
    incompatible trees (wrong head width, wrong dtype) keep failing loudly."""
    with np.load(path) as data:
        stored = dict(data.items())
    topo = None
    if _TOPO_MARK in stored:
        topo = json.loads(str(np.asarray(stored[_TOPO_MARK]).item()))
    cur_model = 1 if model_size is None else int(model_size)
    file_topo = topo  # the record as WRITTEN — what reshard events report
    if reshard_on_mismatch and topo is not None and world_size:
        saved_model = topology_model_size(topo)
        saved_world = int(topo.get("world_size") or 0)
        # model-width changes always need the reshaper; at a FIXED model>1
        # width a data-width change does too (the in-loader elastic path
        # only redistributes pure-DP residuals). model=1 world changes keep
        # the pre-existing in-loader elastic path — byte-identical behavior
        # for every pure-DP caller.
        if saved_model != cur_model or (
            cur_model > 1 and saved_world and saved_world != int(world_size)
        ):
            from tpuddp.training import reshard as reshard_lib

            stored, topo, racts = reshard_lib.reshard_arrays(
                stored,
                data=int(world_size) // cur_model,
                model=cur_model,
                path=path,
            )
            logger.warning(
                "elastic reshard: checkpoint %s re-shaped in-memory onto "
                "(data=%d, model=%d) before load (%d leaf action(s))",
                path, int(world_size) // cur_model, cur_model, len(racts),
            )
            if reshard_actions is not None:
                reshard_actions.extend(racts)
    _check_model_width(path, topo, model_size)
    flat, treedef = jax.tree_util.tree_flatten_with_path(like)
    leaves = []
    for p, template in flat:
        key = _path_str(p)
        if key in stored:
            leaves.append(_fit_leaf(
                path, key, stored[key], template, topo, world_size,
                reshard_actions,
            ))
        elif _BF16_MARK + key in stored:
            arr = stored[_BF16_MARK + key].view(ml_dtypes.bfloat16)
            leaves.append(_fit_leaf(
                path, key, arr, template, topo, world_size, reshard_actions
            ))
        elif _KEY_MARK + key in stored:
            raw = stored[_KEY_MARK + key]
            if not (
                hasattr(template, "dtype")
                and jax.dtypes.issubdtype(template.dtype, jax.dtypes.prng_key)
            ):
                raise ValueError(
                    f"checkpoint {path}: leaf {key!r} holds a PRNG key but the "
                    "model expects an ordinary array"
                )
            t_raw_shape = tuple(np.shape(jax.random.key_data(template)))
            if tuple(raw.shape) != t_raw_shape:
                raise ValueError(
                    f"checkpoint {path}: PRNG key leaf {key!r} has key-data "
                    f"shape {tuple(raw.shape)} but the model expects "
                    f"{t_raw_shape}"
                )
            leaves.append(jax.random.wrap_key_data(raw))
        elif (
            key == ".comm_state"
            or key.startswith("['comm_state']")
            or key.startswith(".skipped_steps")
            or key.startswith("['skipped_steps']")
        ):
            # Anchored to the TrainState fields / managed state-dict entries —
            # a model parameter whose own name merely contains "comm_state"
            # must still hit the missing-leaf error below.
            # Forward-compat: a checkpoint written before the gradient-comm
            # hook (comm_hook="none" saves no residual leaf) or before the
            # numerical guard (guard off saves no skip counters) loads into
            # the newer template by keeping the template's zero
            # initialization — the exact state a fresh run of that
            # configuration starts from, so resume is correct, just logged.
            # A cross-model-width reshard DROPS the residual deliberately
            # (slices key by model shard); its topology record says so, and
            # the log names the reset instead of claiming the file is old.
            dropped = key in ((topo or {}).get("resharded") or {}).get(
                "dropped", ()
            )
            if dropped:
                logger.warning(
                    "checkpoint %s: leaf %r was reset by a cross-topology "
                    "reshard (model-width change); it restarts at its zero "
                    "initialization",
                    path, key,
                )
            else:
                logger.warning(
                    "checkpoint %s predates %s state: leaf %r starts at "
                    "its zero initialization",
                    path,
                    "guard" if "skipped_steps" in key else "comm_hook",
                    key,
                )
            leaves.append(template)
        else:
            raise KeyError(f"checkpoint {path} is missing leaf {key!r}")
    return jax.tree_util.tree_unflatten(treedef, leaves), file_topo


def load(
    path: str,
    like: Any,
    world_size: Optional[int] = None,
    reshard_actions: Optional[List[dict]] = None,
    model_size: Optional[int] = None,
    reshard_on_mismatch: bool = False,
) -> Any:
    """Restore a pytree saved by :func:`save`, using ``like`` for structure.
    Leaf shapes and dtypes are validated against ``like``; mismatches raise
    with the offending leaf named.

    Elastic resume: when the file carries a v2 topology record and a
    world-size-dependent leaf's shape differs from the template's, the leaf
    is resharded onto the current topology (see the module doc) instead of
    failing. ``world_size`` is the CURRENT world (needed to redistribute
    per-replica leaves); ``model_size`` the current tensor-parallel width
    (cross-width restores refuse typed unless ``reshard_on_mismatch`` opts
    into the cross-topology reshaper); ``reshard_actions`` (a
    caller-supplied list) is appended with one dict per resharded leaf."""
    return load_with_topology(
        path, like, world_size, reshard_actions, model_size=model_size,
        reshard_on_mismatch=reshard_on_mismatch,
    )[0]


def build_reshard_events(
    path: str,
    epoch: int,
    topo: Optional[dict],
    world_size: Optional[int],
    actions: List[dict],
    model_size: Optional[int] = None,
) -> List[dict]:
    """The typed event dicts an elastic restore should land in
    history.jsonl: one ``topology_change`` summary (worlds, model widths,
    resharded leaves, what happened to the residual) plus one
    ``comm_state_reset`` per residual that had to reset. Empty when the
    restore was same-topology. ONE implementation for every driver — the
    native epoch driver, the guard-rollback restore, and the managed
    load_state all record identically."""
    from_world = (topo or {}).get("world_size")
    from_model = topology_model_size(topo) if topo else None
    to_model = None if model_size is None else int(model_size)
    if not (actions or (from_world and world_size and from_world != world_size)):
        return []
    events = [{
        "event": "topology_change",
        "from_world": from_world,
        "to_world": world_size,
        "from_model": from_model,
        "to_model": to_model,
        "checkpoint": os.path.basename(path),
        "checkpoint_epoch": epoch,
        "resharded_leaves": [a["leaf"] for a in actions],
        "residual": next(
            (a["action"] for a in actions if a.get("from_world")), None
        ),
    }]
    for a in actions:
        if a.get("action") == "reset":
            events.append({
                "event": "comm_state_reset",
                "leaf": a["leaf"],
                "from_world": a["from_world"],
                "to_world": a["to_world"],
                "reason": a.get("reason")
                or "no divisor relation between world sizes; "
                "error-feedback residual reset to zero",
            })
    logger.warning(
        "elastic resume: checkpoint %s written on world %s restored onto "
        "world %s (%d leaf/leaves resharded)",
        path, from_world, world_size, len(actions),
    )
    return events


def checkpoint_path(save_dir: str, epoch: int, prefix: str = "ckpt") -> str:
    """``{prefix}_{epoch}.npz`` — default naming parity with the reference's
    ``ckpt_{epoch}.pt`` (multi-GPU-training-torch.py:219-221); the managed
    full-state files use ``prefix="state"``."""
    return os.path.join(save_dir, f"{prefix}_{epoch}.npz")


def step_checkpoint_path(
    save_dir: str, epoch: int, step: int, prefix: str = "ckpt"
) -> str:
    """``{prefix}_{epoch}_s{step}.npz`` — a STEP-granular snapshot taken
    mid-epoch (``step`` real micro-batches of ``epoch`` applied). The suffix
    is invisible to the pre-v4 ``{prefix}_{epoch}.npz`` listing regex, so
    old readers simply never see step files."""
    return os.path.join(save_dir, f"{prefix}_{epoch}_s{step}.npz")


def peer_checkpoint_dirs(save_dir: str) -> List[str]:
    """The peer-redundant spill directories reachable from ``save_dir``:
    every ``ring_*`` subdirectory of ``<heartbeat_dir>/peer_ckpt``. Peer
    redundancy rides the heartbeat channel's directory (the one filesystem
    location every process of a multi-process job can already reach), each
    process spilling its ring neighbor's snapshot bytes there — so the loss
    of any single host's local checkpoint directory still yields a full
    restore. Empty when no peer spills exist."""
    from tpuddp.resilience import watchdog

    hb = watchdog.heartbeat_dir(save_dir)
    if not hb:
        return []
    root = os.path.join(hb, "peer_ckpt")
    if not os.path.isdir(root):
        return []
    return sorted(
        os.path.join(root, d)
        for d in os.listdir(root)
        if d.startswith("ring_") and os.path.isdir(os.path.join(root, d))
    )


def _gather_cross_host_shards(tree: Any) -> Any:
    """Materialize leaves that are sharded ACROSS hosts (weight-update-sharded
    optimizer moments: no single process holds the full vector) as host
    arrays. A collective — every process must call it, which is why it runs
    BEFORE the process-0 gating in :func:`save_on_main`. Replicated
    multi-host arrays are locally complete and need no exchange."""
    def g(leaf):
        if (
            isinstance(leaf, jax.Array)
            and not leaf.is_fully_addressable
            and not leaf.sharding.is_fully_replicated
        ):
            from jax.experimental import multihost_utils

            return multihost_utils.process_allgather(leaf, tiled=True)
        return leaf

    return jax.tree_util.tree_map(g, tree)


def save_on_main(
    save_dir: str,
    epoch: int,
    tree: Any,
    prefix: str = "ckpt",
    completed: bool = True,
    keep_last: Optional[int] = None,
    world_size: Optional[int] = None,
    step: Optional[int] = None,
    cursor: Optional[dict] = None,
    cursor_acc: Optional[Any] = None,
) -> Optional[str]:
    """Process-0-only save + barrier — the reference's writer discipline
    (:217-223), with the cross-host shard gather (a collective) BEFORE the
    process-0 gate. Returns the path on process 0, None elsewhere. The
    managed full-state files use ``prefix="state"``.

    ``completed=False`` marks a preemption-drain emergency save (resume redoes
    ``epoch`` instead of starting at ``epoch + 1``); ``keep_last=K`` prunes all
    but the K newest epochs after a successful save. The v2 topology record
    is derived from the tree's live shardings BEFORE the cross-host gather
    (which flattens sharded leaves to host arrays); ``world_size`` supplies
    the world when no sharding is inspectable.

    ``step`` (with an optional v4 ``cursor``/``cursor_acc``) writes a
    STEP-granular mid-epoch file ``{prefix}_{epoch}_s{step}.npz`` instead —
    a resumable-at-step snapshot (always ``completed=0``); ``restore_latest``
    surfaces its cursor so the driver replays zero batches."""
    topology = derive_topology(tree, world_size)
    if jax.process_count() > 1:
        tree = _gather_cross_host_shards(tree)
    path = None
    if jax.process_index() == 0:
        os.makedirs(save_dir, exist_ok=True)
        if step is None:
            target = checkpoint_path(save_dir, epoch, prefix)
            meta = {"epoch": epoch, "completed": int(completed)}
        else:
            target = step_checkpoint_path(save_dir, epoch, step, prefix)
            meta = {"epoch": epoch, "completed": 0, "step": int(step)}
        path = save(
            target,
            tree,
            meta=meta,
            topology=topology,
            cursor=cursor,
            cursor_acc=cursor_acc,
        )
        # chaos hook: corrupt@ckpt_N garbles the just-published file (stale
        # manifest included), which latest() must then detect and skip
        name = os.path.basename(target)[: -len(".npz")]
        faults.maybe_fire("ckpt", name=name, path=path)
        if keep_last is not None:
            prune_checkpoints(save_dir, keep_last, prefix)
    col.barrier("tpuddp_checkpoint")
    return path


def _family_key(epoch: int, step: Optional[int]) -> Tuple[int, int, int]:
    """Total order over mixed step/epoch checkpoint families: newest first
    by ``(epoch, family, step)``. A FULL-epoch file ``{prefix}_{e}.npz``
    ranks newer than every step snapshot ``{prefix}_{e}_s*.npz`` of the same
    epoch — any epoch-family write of epoch e (end-of-epoch save or a
    preempt drain) happens after the last step snapshot of e."""
    return (int(epoch), 1 if step is None else 0, 0 if step is None else int(step))


def _all_checkpoints(
    save_dir: str, prefix: str = "ckpt"
) -> List[Tuple[str, int, Optional[int]]]:
    """All ``(path, epoch, step)`` matches, newest first (``step`` is None
    for epoch-granular files; ordering per :func:`_family_key`)."""
    if not os.path.isdir(save_dir):
        return []
    pat = re.compile(rf"^{re.escape(prefix)}_(\d+)(?:_s(\d+))?\.npz$")
    found = []
    for name in os.listdir(save_dir):
        m = pat.match(name)
        if m:
            step = int(m.group(2)) if m.group(2) is not None else None
            found.append((os.path.join(save_dir, name), int(m.group(1)), step))
    found.sort(key=lambda t: _family_key(t[1], t[2]), reverse=True)
    return found


def latest(save_dir: str, prefix: str = "ckpt") -> Optional[Tuple[str, int]]:
    """Most recent *intact* ``(path, epoch)`` in ``save_dir``, or None. The
    resume helper the reference lacks (SURVEY.md §3.4). Candidates that fail
    integrity verification (manifest mismatch, truncation, a writer killed
    mid-``save``) are skipped with a warning in favor of the next-newest good
    one — a corrupt newest checkpoint must not take down the resume path.
    Step snapshots participate in the ordering (see :func:`_family_key`);
    use :func:`read_cursor` on the returned path to see whether it is one."""
    for path, epoch, _step in _all_checkpoints(save_dir, prefix):
        if integrity.verify_file(path):
            return path, epoch
        logger.warning(
            "checkpoint %s failed integrity verification (corrupt or "
            "truncated); skipping it and falling back to the next-newest",
            path,
        )
    return None


def _latest_any(
    save_dir: str, prefix: str = "ckpt", include_peers: bool = True
) -> Optional[Tuple[str, int, Optional[int], str]]:
    """The freshest *intact* checkpoint across {local, peer, epoch-family}:
    ``(path, epoch, step, provenance)``. Candidates from ``save_dir`` carry
    provenance ``"local"``; candidates from the peer-redundant spill dirs
    (see :func:`peer_checkpoint_dirs`) carry ``"peer:ring_<i>"``. Equal
    freshness prefers local. Corrupt candidates are skipped with a warning —
    that skip is exactly what the peer copies exist for."""
    candidates = []
    for path, epoch, step in _all_checkpoints(save_dir, prefix):
        candidates.append((_family_key(epoch, step), 0, path, epoch, step, "local"))
    if include_peers:
        for pd in peer_checkpoint_dirs(save_dir):
            prov = f"peer:{os.path.basename(pd)}"
            for path, epoch, step in _all_checkpoints(pd, prefix):
                candidates.append(
                    (_family_key(epoch, step), 1, path, epoch, step, prov)
                )
    candidates.sort(key=lambda c: (c[0], -c[1]), reverse=True)
    for _key, _rank, path, epoch, step, prov in candidates:
        if integrity.verify_file(path):
            return path, epoch, step, prov
        logger.warning(
            "checkpoint %s (%s) failed integrity verification (corrupt or "
            "truncated); skipping it and falling back to the next-newest "
            "intact candidate across {local, peer, epoch-family}",
            path, prov,
        )
    return None


def sweep_stale_tmp(save_dir: str, prefix: str = "ckpt") -> int:
    """Delete orphaned ``{prefix}_*.npz.tmp`` / ``.sha256.tmp`` staging
    files. ``save()`` publishes atomically via ``os.replace``, so a writer
    killed mid-``np.savez`` (preemption, chaos kill) leaks its ``.tmp``
    forever — never a torn checkpoint, but unbounded junk on long chaotic
    runs, and a confusing artifact next to the real files. Swept at the two
    natural janitor points (``restore_latest`` before picking a candidate,
    ``prune_checkpoints`` after a save) and counted by ``tpuddp_inspect
    ckpt``'s directory integrity report. Returns the number removed."""
    if not os.path.isdir(save_dir):
        return 0
    pat = re.compile(
        rf"^{re.escape(prefix)}_\d+(_s\d+)?\.npz(\.sha256)?\.tmp$"
    )
    removed = 0
    for name in os.listdir(save_dir):
        if not pat.match(name):
            continue
        try:
            os.remove(os.path.join(save_dir, name))
            removed += 1
        except FileNotFoundError:
            pass
    if removed:
        logger.warning(
            "swept %d stale checkpoint tmp file(s) from %s (writer killed "
            "mid-save; the atomic publish means no torn checkpoints, only "
            "orphaned staging files)",
            removed, save_dir,
        )
    return removed


def prune_checkpoints(save_dir: str, keep_last: int, prefix: str = "ckpt") -> int:
    """Delete all but the ``keep_last`` newest ``{prefix}_*.npz`` (and their
    manifests), plus any stale ``.tmp`` staging orphans. Returns the number
    of checkpoints removed.

    Ordering is by ``(epoch, step)`` across MIXED step/epoch families (see
    :func:`_family_key`) — a burst of step snapshots must age out by recency,
    not by name family. One hard floor: the newest INTACT full-epoch
    checkpoint is never collected, even when ``keep_last`` step snapshots
    outrank it — it is the only epoch-granular fallback left if every newer
    step snapshot turns out corrupt, and step snapshots of a partially
    applied epoch are useless to pre-v4 tooling."""
    if keep_last < 1:
        raise ValueError(f"keep_last must be >= 1, got {keep_last}")
    sweep_stale_tmp(save_dir, prefix)
    all_ckpts = _all_checkpoints(save_dir, prefix)
    keep = {path for path, _e, _s in all_ckpts[:keep_last]}
    for path, _epoch, step in all_ckpts:
        if step is None and integrity.verify_file(path):
            keep.add(path)  # newest intact full-epoch file: never collected
            break
    removed = 0
    for path, _epoch, _step in all_ckpts:
        if path in keep:
            continue
        for p in (path, integrity.manifest_path(path)):
            try:
                os.remove(p)
            except FileNotFoundError:
                pass
        removed += 1
        logger.info("pruned old checkpoint %s (keep_last=%d)", path, keep_last)
    return removed


def restore_latest(
    save_dir: str,
    like: Any,
    prefix: str = "ckpt",
    world_size: Optional[int] = None,
    reshard_log: Optional[List[dict]] = None,
    model_size: Optional[int] = None,
    reshard_on_mismatch: bool = False,
    cursor_out: Optional[List[dict]] = None,
) -> Tuple[Any, int]:
    """Load the newest intact checkpoint into ``like``'s structure — and,
    for the leaves of ``like`` that live on a mesh, onto the same devices
    with the same shardings. Returns ``(tree, next_epoch)``; ``(like, 0)``
    when none exists. An emergency save
    (``completed=0`` meta, written during a preemption drain) yields its own
    epoch as ``next_epoch`` so the interrupted epoch is redone from the saved
    mid-epoch state; end-of-epoch saves yield ``epoch + 1``.

    Candidate selection spans {local, peer, epoch-family}: step-granular v4
    snapshots and peer-redundant spill copies (see
    :func:`peer_checkpoint_dirs`) compete with local epoch files on
    ``(epoch, step)`` freshness, freshest-INTACT wins, and the provenance of
    the pick is logged. A v4 step snapshot yields its cursor's epoch as
    ``next_epoch`` and appends the cursor (plus ``path``/``provenance``) to
    ``cursor_out`` — the driver then resumes EXACTLY at the recorded step,
    replaying zero batches, instead of redoing the epoch.

    Elastic resume: ``world_size`` is the CURRENT world; a v2 checkpoint
    written on a different world is resharded onto it (see :func:`load`).
    ``model_size`` is the current tensor-parallel width — a checkpoint
    written under a DIFFERENT model width raises the typed
    :class:`TopologyMismatch` unless ``reshard_on_mismatch`` opts into the
    cross-topology reshaper (see :func:`load_with_topology`).
    ``reshard_log`` (a caller-supplied list)
    receives ready-to-write typed event dicts — one ``topology_change``
    summary naming the worlds and the resharded leaves, plus one
    ``comm_state_reset`` per residual that had to reset (M∤N) — so the
    epoch driver can land them as event rows in history.jsonl."""
    sweep_stale_tmp(save_dir, prefix)
    found = _latest_any(save_dir, prefix)
    if found is None:
        return like, 0
    path, epoch, step, provenance = found
    if provenance != "local" or step is not None:
        logger.warning(
            "restore_latest: picked %s (epoch=%d, %s, provenance=%s) as the "
            "freshest intact candidate across {local, peer, epoch-family}",
            path, epoch,
            "full-epoch" if step is None else f"step={step}",
            provenance,
        )
    actions: List[dict] = []
    tree, topo = load_with_topology(
        path, like, world_size=world_size, reshard_actions=actions,
        model_size=model_size, reshard_on_mismatch=reshard_on_mismatch,
    )
    # back onto the mesh where ``like`` lives: the resumed run dispatches the
    # programs the fresh run compiled (and cached), not ones for host inputs
    tree = place_like(like, tree)
    if reshard_log is not None:
        reshard_log.extend(
            build_reshard_events(
                path, epoch, topo, world_size, actions, model_size=model_size
            )
        )
    cursor = read_cursor(path)
    if cursor is not None:
        if actions:
            # a cross-topology reshard changes the data order (the sampler
            # plan keys on the world size), so the step cursor no longer
            # addresses the same batches — surface it, but poison the plan
            # key so the driver falls back to redoing the epoch from the
            # restored mid-epoch state instead of skipping wrong batches
            cursor = dict(cursor)
            cursor["plan_key"] = None
        if cursor_out is not None:
            entry = dict(cursor)
            entry["path"] = path
            entry["provenance"] = provenance
            cursor_out.append(entry)
        logger.warning(
            "resuming from STEP snapshot %s (epoch %d, step %s, "
            "provenance=%s); the interrupted epoch continues at the recorded "
            "step — zero batches replayed",
            path, int(cursor.get("epoch", epoch)), cursor.get("step"),
            provenance,
        )
        return tree, int(cursor.get("epoch", epoch))
    meta = read_meta(path)
    if not meta.get("completed", 1):
        logger.warning(
            "resuming from EMERGENCY checkpoint %s (preempted during epoch "
            "%d); that epoch restarts from the saved mid-epoch state",
            path,
            epoch,
        )
        return tree, epoch
    return tree, epoch + 1
