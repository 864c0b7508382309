"""DistributedDataParallel — the explicit DP wrapper.

Owns the contract of ``torch.nn.parallel.DistributedDataParallel`` (SURVEY.md
§2b #13), reimagined functionally: instead of hooking autograd, it *builds*
the compiled train/eval step in which gradient pmean, buffer broadcast, and
metric partial-sums are explicit. Wrapping = ``ddp = DistributedDataParallel(
model, optimizer, criterion, mesh)`` + ``state = ddp.init_state(key, sample)``;
the construction-time rank-0 parameter broadcast of torch DDP
(multi-GPU-training-torch.py:245) is performed in ``init_state`` via
``broadcast_one_to_all``.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from tpuddp.nn.core import Context
from tpuddp.nn.loss import CrossEntropyLoss
from tpuddp.parallel import collectives as col
from tpuddp.parallel import comm as comm_lib
from tpuddp.parallel.mesh import (
    HOST_AXIS,
    LOCAL_AXIS,
    data_axes,
    data_mesh,
    hierarchical_mesh,
    replicate,
    shard_batch,
)
from tpuddp.parallel.mesh2d import (
    MODEL_AXIS as _MODEL_AXIS,
    data_size as _mesh_data_size,
    model_size as _mesh_model_size,
    squeeze_model as _squeeze_model,
)
from tpuddp.resilience import guard as guard_lib
from tpuddp.training import step as step_lib
from tpuddp.training.train_state import TrainState, create_train_state


class DistributedDataParallel:
    """Builds and caches the compiled DP steps for (model, optimizer, criterion).

    mode="shard_map" is the explicit-DDP analog (visible lax.pmean); mode="auto"
    is the managed analog used by the Accelerator facade. Both run on the same
    mesh/collectives backend.
    """

    def __init__(
        self,
        model,
        optimizer,
        criterion: Optional[Callable] = None,
        mesh=None,
        mode: str = "shard_map",
        sync_buffers: str = "broadcast",
        clip_grad_norm: Optional[float] = None,
        augment: Optional[Callable] = None,
        eval_transform: Optional[Callable] = None,
        remat: bool = False,
        weight_update_sharding: bool = False,
        grad_accumulation: int = 1,
        comm_hook: str = "none",
        bucket_cap_mb: float = comm_lib.DEFAULT_BUCKET_CAP_MB,
        comm_topology: str = "flat",
        topk_density: float = comm_lib.DEFAULT_TOPK_DENSITY,
        guard=None,
    ):
        """``weight_update_sharding``: shard the optimizer update + moments
        across the data axis (reduce-scatter grads, update a 1/N parameter
        shard per replica, all-gather new params — the cross-replica
        weight-update sharding of arxiv.org/abs/2004.13336 / ZeRO-1).
        N-fold less optimizer memory and update HBM traffic per chip; same
        interconnect bytes as the plain allreduce. shard_map mode only.

        ``grad_accumulation=A > 1``: ONE optimizer update per A consecutive
        micro-batches (native effective-batch control, the explicit-API analog
        of ``Accelerator(gradient_accumulation_steps=A)``). Training then runs
        through :meth:`train_step_many` in whole cycles of A — the epoch
        driver pads ragged tails with all-padding micro-batches; the
        per-batch :meth:`train_step` is refused (a full-scale update per
        micro-batch would be a silent A× LR bug).

        ``comm_hook``: the gradient-communication hook (torch DDP's comm-hook
        analog, parallel/comm.py): ``"none"`` keeps today's full-precision
        pmean; ``"bf16"`` runs the bucketed bf16-compressed allreduce (half
        the gradient interconnect bytes); ``"bf16_ef"`` adds the per-replica
        error-feedback residual (carried in ``TrainState.comm_state``,
        checkpointed with the rest of the state) so compression error does
        not bias convergence. In ``mode="shard_map"`` the collective
        genuinely runs in bf16 on the wire; in ``mode="auto"`` the hook
        quantizes the aggregated gradient (same numerics contract, byte
        savings are a shard_map-mode property). Composes with
        ``weight_update_sharding`` (the compressed payload is
        reduce-scattered) and ``grad_accumulation`` (compression happens
        once per cycle, on the averaged gradient).

        ``"int8_ef"`` runs per-bucket max-abs symmetric int8 quantization
        (values + per-bucket f32 scales on the wire, ~75% fewer gradient
        bytes) and ``"topk_ef"`` keeps only the top ``topk_density`` of each
        bucket by magnitude (int8 values + int32 indices + scale, ~87.5%
        fewer bytes at density 0.1); both carry the same persistent
        error-feedback residual as bf16_ef (quantization error AND unsent
        elements re-enter the next send).

        ``bucket_cap_mb``: bucket size cap for the compressed hooks (torch's
        ``bucket_cap_mb`` knob, default 25): small tensors coalesce into one
        collective per bucket; boundaries fall on whole-leaf edges.

        ``comm_topology``: ``"flat"`` (one collective over the whole data
        axis — today's behavior) or ``"hierarchical"`` (parallel/comm.py
        ``reduce_hierarchical``): intra-host f32 reduce-scatter over the
        factored mesh's ``"local"`` axis, compressed inter-host exchange
        over ``"host"``, then all-gather — only the compressed shard crosses
        the slow inter-host link. Needs ``mode="shard_map"`` and a factored
        ``("host", "local")`` mesh (``mesh=None`` builds one via
        :func:`~tpuddp.parallel.mesh.hierarchical_mesh`); mutually exclusive
        with ``weight_update_sharding`` (the scatter already factors the
        exchange). ``grad_comm_bytes_inter_host`` /
        ``grad_comm_bytes_intra_host`` account the two hops separately.

        ``topk_density``: the fraction of each bucket topk_ef keeps
        (default 0.1); ignored by the other hooks.

        ``guard``: the ``training.guard`` block (None/False/True/dict or a
        :class:`~tpuddp.resilience.guard.GuardConfig`). When enabled, the
        compiled step gates every optimizer update behind a non-finite
        gradient firewall (a poisoned step becomes a bitwise no-op counted
        in ``TrainState.skipped_steps``) and :meth:`init_state` runs the
        cross-replica desync auditor — the torch
        ``_verify_params_across_processes`` moment. Off by default; the
        disabled path lowers to the identical step program."""
        self.model = model
        self.optimizer = optimizer
        self.criterion = criterion if criterion is not None else CrossEntropyLoss()
        self.comm_topology = comm_lib.validate_topology(comm_topology)
        if mesh is not None:
            self.mesh = mesh
        elif self.comm_topology == "hierarchical":
            self.mesh = hierarchical_mesh()
        else:
            self.mesh = data_mesh()
        self.mode = mode
        # 2-D ("data", "model") mesh (parallel/mesh2d.py): model=1 collapses
        # to the EXACT flat data mesh, so the legacy DDP construction below
        # runs unchanged and lowers to byte-identical HLO; model>1 arms the
        # tensor-parallel path (parallel/tensor.py) with its own refusal
        # surface — a combo the TP step has no semantics for must fail at
        # wrap time, not mistrain.
        self.model_size = _mesh_model_size(self.mesh)
        if _MODEL_AXIS in self.mesh.axis_names and self.model_size == 1:
            self.mesh = _squeeze_model(self.mesh)
        self.data_size = _mesh_data_size(self.mesh)
        self._tp_specs = None  # P-tree of the TP param shards (model>1 only)
        self._tp_opt_specs = None
        if self.model_size > 1:
            self._validate_tp(
                mode, weight_update_sharding, grad_accumulation,
                clip_grad_norm, augment, eval_transform, remat, optimizer,
            )
        if self.comm_topology == "hierarchical":
            if mode != "shard_map":
                raise ValueError(
                    "comm_topology='hierarchical' needs the explicit "
                    "per-replica step (mode='shard_map'): the multi-hop "
                    "reduction is expressed over the factored mesh's named "
                    "axes (mode='auto' lets XLA place the collective)"
                )
            if weight_update_sharding:
                raise ValueError(
                    "comm_topology='hierarchical' and weight_update_sharding "
                    "are mutually exclusive: the reduce-scatter/all-gather "
                    "exchange already factors the reduction; pick one"
                )
            names = set(self.mesh.axis_names)
            if names != {HOST_AXIS, LOCAL_AXIS}:
                raise ValueError(
                    "comm_topology='hierarchical' needs a factored "
                    f"('{HOST_AXIS}', '{LOCAL_AXIS}') mesh (got axes "
                    f"{tuple(self.mesh.axis_names)}); build one with "
                    "tpuddp.parallel.mesh.hierarchical_mesh"
                )
        # fail at wrap time, not first step (a bad value would silently skip
        # buffer sync and publish divergent buffers as replicated)
        step_lib._validate_sync_buffers(
            model, step_lib.DATA_AXIS if mode == "shard_map" else None, sync_buffers
        )
        if weight_update_sharding and mode != "shard_map":
            raise ValueError(
                "weight_update_sharding requires mode='shard_map' (the "
                "reduce-scatter/all-gather exchange is expressed over the "
                "explicit per-replica step's named axis)"
            )
        self.grad_accumulation = int(grad_accumulation)
        if self.grad_accumulation < 1:
            raise ValueError(
                f"grad_accumulation must be >= 1, got {grad_accumulation!r}"
            )
        self.sync_buffers = sync_buffers
        self.clip_grad_norm = clip_grad_norm
        self.augment = augment
        self.eval_transform = eval_transform
        self.remat = remat
        self.weight_update_sharding = bool(weight_update_sharding)
        self.comm_hook = comm_lib.validate_hook(comm_hook)
        self.bucket_cap_mb = float(bucket_cap_mb)
        if self.bucket_cap_mb <= 0:
            raise ValueError(f"bucket_cap_mb must be > 0, got {bucket_cap_mb!r}")
        self.topk_density = float(topk_density)
        comm_lib.bucket_topk(1, self.topk_density)  # range-validate eagerly
        self.guard = guard_lib.resolve_guard(guard)
        self._comm = None
        self._grad_comm_bytes = None
        self._grad_comm_bytes_f32 = None
        self._grad_comm_breakdown = None
        self._wus_spec = None
        self._state_spec = None
        self._train_step = None
        self._eval_step = None
        self._scan_step = None
        self._eval_scan_step = None

    def _validate_tp(
        self, mode, weight_update_sharding, grad_accumulation,
        clip_grad_norm, augment, eval_transform, remat, optimizer,
    ):
        """Wrap-time refusal surface for the tensor-parallel path: every
        combination the TP step has no semantics for fails HERE, loudly —
        the alternative is a silently different training run."""
        from tpuddp.parallel import tensor as tp_lib

        tp_lib.validate_tp_geometry(self.model, self.model_size)
        if mode != "shard_map":
            raise ValueError(
                "parallel.model > 1 needs the explicit per-replica step "
                "(mode='shard_map'): the model-axis exchanges are written "
                "over named mesh axes"
            )
        if self.comm_topology != "flat":
            raise ValueError(
                "parallel.model > 1 with comm_topology='hierarchical' is "
                "refused: the factored ('host','local') data axis and the "
                "model axis would need a 3-D mesh the comm hooks do not "
                "express yet — pick one"
            )
        if weight_update_sharding:
            raise ValueError(
                "parallel.model > 1 with weight_update_sharding is refused: "
                "the WUS flat layout spans the whole replicated parameter "
                "vector, which a model-sharded state no longer has (the "
                "ZeRO composition is ROADMAP item 2)"
            )
        if int(grad_accumulation) != 1:
            raise ValueError(
                "parallel.model > 1 with grad_accumulation > 1 is deferred; "
                "scale the per-replica batch instead"
            )
        if clip_grad_norm is not None:
            raise ValueError(
                "parallel.model > 1 with clip_grad_norm is deferred: the "
                "global norm of a model-sharded gradient needs a model-axis "
                "reduction the clip path does not express yet"
            )
        if augment is not None or eval_transform is not None:
            raise ValueError(
                "parallel.model > 1 is a token-model path; image "
                "augment/eval_transform hooks do not apply"
            )
        if remat:
            raise ValueError("parallel.model > 1 with remat is deferred")
        if type(optimizer).__name__ in ("LARS", "LAMB"):
            raise ValueError(
                "parallel.model > 1 with LARS/LAMB is deferred: per-layer "
                "trust ratios over model-sharded leaves need model-axis "
                "norm reductions; use adam/sgd/sgdw"
            )
        if jax.process_count() > 1:
            raise ValueError(
                "parallel.model > 1 is single-controller only for now "
                "(every shard must be addressable for placement and "
                "checkpoint gather)"
            )

    # -- world introspection (dist.get_world_size analog) -------------------
    @property
    def world_size(self) -> int:
        return self.mesh.devices.size

    @property
    def tp_rules_hash(self):
        """Short hash of the tensor-parallel rule table this wrap applies
        (run_meta ``mesh.tp_rules_hash``); None on pure-DP wraps."""
        if self.model_size <= 1:
            return None
        from tpuddp.parallel import tensor as tp_lib

        return tp_lib.tp_rules_hash()

    @property
    def tp_param_specs(self):
        """The PartitionSpec tree of the TP parameter shards (None on pure
        DP) — the desync auditor needs it to fingerprint each device's OWN
        shard and compare across data replicas only."""
        return self._tp_specs

    def _init_state_tp(self, key, sample_input, params, model_state) -> TrainState:
        """The tensor-parallel init: full host init + broadcast (the DDP
        construction contract, unchanged), then the QKV layout reshape, the
        rule-table placement of params/moments over the model axis, the
        LOCAL-shard gradient comm plan (data-axis exchange only), and the
        per-(data, model)-device error-feedback residual."""
        import numpy as np
        from jax.sharding import NamedSharding

        from tpuddp.parallel import tensor as tp_lib
        from tpuddp.parallel.mesh import DATA_AXIS
        from tpuddp.parallel.mesh2d import MODEL_AXIS

        if (params is None) != (model_state is None):
            raise ValueError(
                "init_state needs params and model_state together: pretrained "
                "params with freshly-initialized buffers would silently "
                "mis-normalize"
            )
        if params is not None:
            _, run_key = jax.random.split(key)
            state = TrainState(
                params=params,
                model_state=model_state,
                opt_state=None,
                step=jnp.zeros((), jnp.int32),
                rng=run_key,
            )
        else:
            state = create_train_state(self.model, self.optimizer, key, sample_input)
        state = col.broadcast_one_to_all(state)
        host_params = jax.tree_util.tree_map(np.asarray, state.params)
        tp_params = tp_lib.to_tp_tree(host_params)
        self._tp_specs = tp_lib.tp_param_specs(self.model, tp_params)
        # optimizer state over the TP-layout tree: moments inherit each
        # parameter's spec by tree path, so each chip materializes only its
        # shard's moments — the per-chip HBM cut covers m/v too
        opt_state = self.optimizer.init(tp_params)
        self._tp_opt_specs = tp_lib.opt_state_specs(
            opt_state, tp_params, self._tp_specs
        )
        # gradient comm plan over the LOCAL shard template: hooks bucket the
        # shard's flat vector and exchange it across DATA replicas only —
        # the model axis never sees a gradient collective
        local_tpl = tp_lib.local_param_template(
            tp_params, self._tp_specs, self.model_size
        )
        self._comm = comm_lib.make_grad_comm(
            local_tpl, self.data_size, self.comm_hook, self.bucket_cap_mb,
            density=self.topk_density,
        )
        self._grad_comm_bytes = comm_lib.comm_bytes_for_hook(
            local_tpl, self.data_size, self.comm_hook, wire=True,
            bucket_cap_mb=self.bucket_cap_mb, density=self.topk_density,
        )
        self._grad_comm_bytes_f32 = comm_lib.comm_bytes_for_hook(
            local_tpl, self.data_size, "none", wire=True,
        )
        self._grad_comm_breakdown = {
            "total": self._grad_comm_bytes,
            "inter_host": self._grad_comm_bytes,
            "intra_host": 0,
        }
        self._state_spec = tp_lib.tp_state_spec(
            self._tp_specs, self._tp_opt_specs, comm=self._comm
        )
        placed_params = tp_lib.place_tree(self.mesh, tp_params, self._tp_specs)
        placed_opt = tp_lib.place_tree(self.mesh, opt_state, self._tp_opt_specs)
        comm_state = None
        if self._comm is not None and self._comm.needs_residual:
            # one residual slice per (data_index, model_index) device,
            # created device-side already sharded — P(("data", "model"))
            # splits the flat vector data-major, model-minor, exactly the
            # mesh's device order
            n = self._comm.spec.total * self.world_size
            comm_state = jax.jit(
                lambda: jnp.zeros((n,), jnp.float32),
                out_shardings=NamedSharding(
                    self.mesh, step_lib.P((DATA_AXIS, MODEL_AXIS))
                ),
            )()
        skipped = (
            replicate(self.mesh, guard_lib.init_skip_counters())
            if self.guard.enabled
            else None
        )
        return self._audit_at_wrap(TrainState(
            params=placed_params,
            model_state=replicate(self.mesh, state.model_state),
            opt_state=placed_opt,
            step=replicate(self.mesh, state.step),
            rng=replicate(self.mesh, state.rng),
            comm_state=comm_state,
            skipped_steps=skipped,
        ))

    def init_state(self, key, sample_input, params=None, model_state=None) -> TrainState:
        """Create replicated train state. Parameters are broadcast from
        process 0 (multi-host) and placed replicated on every mesh device —
        the DDP construction contract.

        ``params``/``model_state`` override the fresh initialization with
        caller-supplied values (the pretrained fine-tune path,
        data_and_toy_model.py:41-45); optimizer state is re-derived from the
        supplied params."""
        if self.model_size > 1:
            return self._init_state_tp(key, sample_input, params, model_state)
        if (params is None) != (model_state is None):
            raise ValueError(
                "init_state needs params and model_state together: pretrained "
                "params with freshly-initialized buffers (e.g. BatchNorm "
                "running stats) would silently mis-normalize"
            )
        if params is not None:
            # caller already owns the variables; skip the (large) fresh init
            _, run_key = jax.random.split(key)
            state = TrainState(
                params=params,
                model_state=model_state,
                opt_state=self.optimizer.init(params),
                step=jnp.zeros((), jnp.int32),
                rng=run_key,
            )
        else:
            state = create_train_state(self.model, self.optimizer, key, sample_input)
        if self.weight_update_sharding:
            # re-derive the optimizer state over the FLAT padded parameter
            # vector: moments become (total,) arrays laid out sharded over
            # the data axis (each replica materializes only its 1/N slice)
            self._wus_spec = step_lib.make_flat_param_spec(
                state.params, self.world_size
            )
            opt_state = self.optimizer.init(
                jnp.zeros((self._wus_spec.total,), jnp.float32)
            )
            state = TrainState(
                params=state.params,
                model_state=state.model_state,
                opt_state=opt_state,
                step=state.step,
                rng=state.rng,
            )
        # Gradient-comm plan (parallel/comm.py): under weight-update sharding
        # the hook reuses the WUS flat spec so the error-feedback residual
        # aligns with the scattered vector element for element. Hierarchical
        # topology forces a plan even for hook "none" (its multi-hop
        # exchange needs the flat spec regardless of compression).
        self._comm = comm_lib.make_grad_comm(
            state.params, self.world_size, self.comm_hook, self.bucket_cap_mb,
            flat_spec=self._wus_spec, density=self.topk_density,
            force=(self.comm_topology == "hierarchical"),
        )
        wire = self.mode == "shard_map"
        if self.weight_update_sharding:
            # auto mode: XLA inserts the psum over f32 values and the hook
            # only emulates the quantization — account the wire honestly
            self._grad_comm_bytes = comm_lib.comm_bytes_for_hook(
                state.params, self.world_size, self.comm_hook, wus=True,
                wire=wire, bucket_cap_mb=self.bucket_cap_mb,
                density=self.topk_density,
            )
            self._grad_comm_breakdown = {
                "total": self._grad_comm_bytes,
                "inter_host": self._grad_comm_bytes,
                "intra_host": 0,
            }
        else:
            # flat vs hierarchical intra/inter-host split (comm.py
            # accounting model); "total" is the headline counter either way
            local = (
                dict(zip(self.mesh.axis_names, self.mesh.devices.shape)).get(
                    LOCAL_AXIS
                )
                if self.comm_topology == "hierarchical"
                else None
            )
            self._grad_comm_breakdown = comm_lib.comm_bytes_breakdown(
                state.params, self.world_size, self.comm_hook,
                topology=self.comm_topology, local_size=local, wire=wire,
                bucket_cap_mb=self.bucket_cap_mb, density=self.topk_density,
            )
            self._grad_comm_bytes = self._grad_comm_breakdown["total"]
        # the uncompressed reference payload for the same layout: run_meta
        # records both, so a history file alone can state the byte savings
        # a compressed hook achieved (tools/tpuddp_inspect.py)
        self._grad_comm_bytes_f32 = comm_lib.comm_bytes_for_hook(
            state.params, self.world_size, "none",
            wus=self.weight_update_sharding,
            wire=wire,
        )
        sharded_residual = (
            self._comm is not None
            and self._comm.needs_residual
            and self.mode == "shard_map"
        )
        if self._comm is not None and self._comm.needs_residual and not sharded_residual:
            # auto mode: a replicated (total,)-sized residual — O(params),
            # carried through the broadcast like any other leaf. The
            # per-replica shard_map residual is built directly under its
            # target sharding below instead: materializing a
            # (world * total,) host vector of zeros and broadcasting it
            # would cost O(world x params) host memory for nothing.
            state = TrainState(
                params=state.params,
                model_state=state.model_state,
                opt_state=state.opt_state,
                step=state.step,
                rng=state.rng,
                comm_state=jnp.asarray(
                    self._comm.init_residual(per_replica=False)
                ),
            )
        axis = data_axes(self.mesh)
        if self.weight_update_sharding:
            self._state_spec = step_lib.sharded_state_spec(
                state.opt_state, self._wus_spec, comm=self._comm, axis=axis
            )
        elif sharded_residual:
            self._state_spec = step_lib.comm_state_spec(axis=axis)
        if self.guard.enabled:
            # the firewall's skip counters ride in the state (replicated,
            # checkpointed); added after every structural rebuild above so no
            # reconstruction can drop them
            import dataclasses

            state = dataclasses.replace(
                state, skipped_steps=guard_lib.init_skip_counters()
            )
        state = col.broadcast_one_to_all(state)
        if not self.weight_update_sharding and not sharded_residual:
            return self._audit_at_wrap(replicate(self.mesh, state))
        # placement follows the state spec's judgment leaf by leaf (ONE
        # predicate for what shards): optimizer vectors / the per-replica
        # comm residual land sharded over the data axis, everything else
        # replicated
        from jax.sharding import NamedSharding

        def place(leaf, spec):
            if spec == step_lib.P(axis):
                import numpy as np

                host = np.asarray(leaf)
                return jax.make_array_from_callback(
                    host.shape,
                    NamedSharding(self.mesh, spec),
                    lambda idx: host[idx],
                )
            return replicate(self.mesh, leaf)

        comm_state = None
        if sharded_residual:
            # definitionally zeros: create the (world * total,) residual
            # device-side, already sharded over the data axis — no host-size
            # copy, no cross-host broadcast of zeros
            n = self._comm.spec.total * self.world_size
            comm_state = jax.jit(
                lambda: jnp.zeros((n,), jnp.float32),
                out_shardings=NamedSharding(self.mesh, step_lib.P(axis)),
            )()
        return self._audit_at_wrap(TrainState(
            params=replicate(self.mesh, state.params),
            model_state=replicate(self.mesh, state.model_state),
            opt_state=jax.tree_util.tree_map(
                lambda l, s: place(l, s),
                state.opt_state,
                self._state_spec.opt_state,
            )
            if self.weight_update_sharding
            else replicate(self.mesh, state.opt_state),
            step=replicate(self.mesh, state.step),
            rng=replicate(self.mesh, state.rng),
            comm_state=comm_state,
            skipped_steps=replicate(self.mesh, state.skipped_steps),
        ))

    @property
    def comm_overlap_meta(self):
        """What run_meta's ``comm.overlap`` (schema v10) records."""
        return dict(comm_lib.OVERLAP_META)

    def _audit_at_wrap(self, state: TrainState) -> TrainState:
        """torch DDP's ``_verify_params_across_processes`` moment: under
        ``guard``, fingerprint every replica's parameter copy before the
        first step — a construction-time divergence (bad broadcast, corrupt
        host) surfaces as :class:`~tpuddp.resilience.guard.ReplicaDesync`
        (exit 77) instead of a silently forked trajectory. On a 2-D mesh the
        fingerprints cover each device's OWN model shard and compare across
        DATA replicas only — a tensor-parallel shard is *supposed* to differ
        from its model-axis neighbor and must never be convicted for it."""
        if self.guard.enabled:
            guard_lib.audit_or_raise(
                self.mesh, state.params, where="ddp-wrap", specs=self._tp_specs
            )
        return state

    def shard(self, batch):
        """Place a host batch onto the mesh, split over the data axis."""
        return shard_batch(self.mesh, batch)

    def shard_stacked(self, stacked_batch):
        """Place a (K, batch, ...) super-batch for the scan step: axis 1 is the
        data axis, axis 0 the step axis."""
        import numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P

        axis = data_axes(self.mesh)

        def _put(x):
            spec = P(None, axis, *([None] * (x.ndim - 2)))
            sharding = NamedSharding(self.mesh, spec)
            if jax.process_count() > 1:
                return jax.make_array_from_process_local_data(sharding, np.asarray(x))
            return jax.device_put(x, sharding)

        return jax.tree_util.tree_map(_put, stacked_batch)

    def _check_wus_ready(self):
        if self.weight_update_sharding and self._wus_spec is None:
            raise RuntimeError(
                "weight_update_sharding derives its flat layout from the "
                "initialized parameters; call init_state before the first step"
            )
        if self.comm_hook != "none" and self._comm is None:
            raise RuntimeError(
                f"comm_hook={self.comm_hook!r} derives its bucket plan from "
                "the initialized parameters; call init_state before the "
                "first step"
            )

    @property
    def grad_comm_bytes_per_step(self) -> Optional[int]:
        """Per-replica wire bytes of ONE gradient reduction (the comm-bytes
        counter, parallel/comm.py accounting model): known after
        :meth:`init_state`; None before. The epoch driver and bench multiply
        by optimizer updates to report measured comm volume."""
        return self._grad_comm_bytes

    @property
    def grad_comm_bytes_per_step_f32(self) -> Optional[int]:
        """What one gradient reduction WOULD cost uncompressed (hook="none",
        same layout) — the denominator of a compressed hook's byte-savings
        claim, recorded in the run_meta header so the history file is
        self-contained evidence."""
        return self._grad_comm_bytes_f32

    @property
    def grad_comm_bytes_inter_host(self) -> Optional[int]:
        """The inter-host share of one gradient reduction's wire bytes: the
        compressed shard exchange under ``comm_topology="hierarchical"``;
        the whole payload under ``"flat"`` (the conservative reading — a
        flat collective's bytes all cross the slowest link)."""
        bd = self._grad_comm_breakdown
        return None if bd is None else bd["inter_host"]

    @property
    def grad_comm_bytes_intra_host(self) -> Optional[int]:
        """The intra-host (ICI) share: the f32 reduce-scatter + all-gather
        operands under the hierarchical topology, 0 under flat."""
        bd = self._grad_comm_breakdown
        return None if bd is None else bd["intra_host"]

    @property
    def _hier(self):
        """The (inner, outer) axis pair of the hierarchical exchange, or
        None under the flat topology."""
        if self.comm_topology != "hierarchical":
            return None
        return (LOCAL_AXIS, HOST_AXIS)

    def train_step_many(self, state: TrainState, stacked_batch):
        """K fused train steps per dispatch (lax.scan; see
        training.step.build_train_scan_step)."""
        if self._scan_step is None:
            self._check_wus_ready()
            if self.model_size > 1:
                from tpuddp.parallel import tensor as tp_lib

                self._scan_step = tp_lib.build_tp_train_scan_step(
                    self.model, self.criterion, self.optimizer, self.mesh,
                    self._state_spec, comm=self._comm,
                    guard=self.guard.enabled,
                )
                return self._scan_step(state, stacked_batch)
            self._scan_step = step_lib.build_train_scan_step(
                self.model,
                self.criterion,
                self.optimizer,
                self.mesh,
                mode=self.mode,
                sync_buffers=self.sync_buffers,
                clip_grad_norm=self.clip_grad_norm,
                augment=self.augment,
                remat=self.remat,
                wus_spec=self._wus_spec,
                state_spec=self._state_spec,
                grad_accumulation=self.grad_accumulation,
                comm=self._comm,
                guard=self.guard.enabled,
                hier=self._hier,
            )
        return self._scan_step(state, stacked_batch)

    def train_step(self, state: TrainState, batch):
        if self.grad_accumulation > 1:
            raise RuntimeError(
                "per-batch train_step is undefined under grad_accumulation "
                f"(= {self.grad_accumulation}): it would apply one full-scale "
                "update per micro-batch. Use train_step_many with chunks that "
                "are whole multiples of the accumulation cycle (the epoch "
                "driver does this automatically)."
            )
        if self._train_step is None:
            self._check_wus_ready()
            if self.model_size > 1:
                from tpuddp.parallel import tensor as tp_lib

                self._train_step = tp_lib.build_tp_train_step(
                    self.model, self.criterion, self.optimizer, self.mesh,
                    self._state_spec, comm=self._comm,
                    guard=self.guard.enabled,
                )
                return self._train_step(state, batch)
            self._train_step = step_lib.build_train_step(
                self.model,
                self.criterion,
                self.optimizer,
                self.mesh,
                mode=self.mode,
                sync_buffers=self.sync_buffers,
                clip_grad_norm=self.clip_grad_norm,
                augment=self.augment,
                remat=self.remat,
                wus_spec=self._wus_spec,
                state_spec=self._state_spec,
                comm=self._comm,
                guard=self.guard.enabled,
                hier=self._hier,
            )
        return self._train_step(state, batch)

    def eval_step_many(self, state: TrainState, stacked_batch):
        """K fused eval batches per dispatch (lax.scan; see
        training.step.build_eval_scan_step)."""
        if self._eval_scan_step is None:
            self._check_wus_ready()
            if self.model_size > 1:
                from tpuddp.parallel import tensor as tp_lib

                self._eval_scan_step = tp_lib.build_tp_eval_scan_step(
                    self.model, self.criterion, self.mesh, self._state_spec
                )
                return self._eval_scan_step(state, stacked_batch)
            self._eval_scan_step = step_lib.build_eval_scan_step(
                self.model,
                self.criterion,
                self.mesh,
                mode=self.mode,
                transform=self.eval_transform,
                state_spec=self._state_spec,
            )
        return self._eval_scan_step(state, stacked_batch)

    def eval_step(self, state: TrainState, batch):
        if self._eval_step is None:
            self._check_wus_ready()
            if self.model_size > 1:
                from tpuddp.parallel import tensor as tp_lib

                self._eval_step = tp_lib.build_tp_eval_step(
                    self.model, self.criterion, self.mesh, self._state_spec
                )
                return self._eval_step(state, batch)
            self._eval_step = step_lib.build_eval_step(
                self.model,
                self.criterion,
                self.mesh,
                mode=self.mode,
                transform=self.eval_transform,
                state_spec=self._state_spec,
            )
        return self._eval_step(state, batch)

    def forward(self, state: TrainState, x):
        """Inference forward (replicated params, sharded batch). On a
        tensor-parallel wrap the shards are gathered to the canonical host
        layout first — a debugging convenience, not a serving path."""
        params, model_state = state.params, state.model_state
        if self.model_size > 1:
            from tpuddp.parallel import tensor as tp_lib

            params = tp_lib.gather_params(params)
        logits, _ = self.model.apply(
            params, model_state, x, Context(train=False)
        )
        return logits
