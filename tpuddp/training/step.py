"""Compiled data-parallel train/eval steps.

The reference's hot loop (multi-GPU-training-torch.py:109-132) — H2D copy,
zero_grad, forward, loss, backward (NCCL grad allreduce via DDP hooks),
optimizer step, ``loss.item()`` — becomes ONE jitted function here. Two
construction modes, both over the same mesh/collectives backend:

- ``mode="shard_map"`` — the *explicit* analog of native DDP: a per-replica
  function in which the gradient averaging is a visible ``lax.pmean`` over the
  ``"data"`` axis (exactly DDP's bucketed allreduce contract, SURVEY.md §2b
  #13), BatchNorm syncs stats with ``lax.pmean`` when converted (SyncBatchNorm
  contract), and metrics come back as per-replica partial sums — the analog of
  the reference's device-tensor accumulators that get ``dist.all_reduce``-d at
  epoch end (:198-204).

- ``mode="auto"`` — the *managed* analog (what the accelerate entrypoint
  routes through): plain global-batch code under ``jit`` with NamedShardings;
  XLA derives the same psum from the mean-loss data flow. BatchNorm statistics
  are global-batch by construction here.

Batches are ``(x, y, w)`` with a per-sample weight/mask so final partial
batches can be padded to a static shape (TPU-first: no recompiles) while the
sample-weighted metric math of the reference (:129-132) stays exact.

Optional ``augment`` / ``transform`` hooks run *inside* the step on device —
this is where tpuddp's CIFAR pipeline does resize/flip/normalize on-chip,
fused into the forward pass by XLA.
"""

from __future__ import annotations

import math
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from tpuddp import optim as _optim
from tpuddp.nn.core import Context
from tpuddp.observability import profiling as _prof
from tpuddp.parallel import collectives as col
from tpuddp.resilience import guard as guard_lib
from tpuddp.parallel.mesh import DATA_AXIS, data_axes, data_sharded, replicated
from tpuddp.seeding import fold_in_axis_index
from tpuddp.training.train_state import TrainState


class FlatParamSpec(NamedTuple):
    """Static flattening metadata for weight-update sharding: the parameter
    pytree viewed as ONE f32 vector, zero-padded to a ``world``-multiple so
    every replica owns an equal contiguous shard."""

    treedef: Any
    shapes: Tuple[Tuple[int, ...], ...]
    sizes: Tuple[int, ...]
    total: int  # padded length (world * shard size)
    world: int


def make_flat_param_spec(params, world: int) -> FlatParamSpec:
    flat, treedef = jax.tree_util.tree_flatten(params)
    for i, leaf in enumerate(flat):
        if jnp.asarray(leaf).dtype != jnp.float32:
            raise ValueError(
                "weight_update_sharding flattens parameters into one f32 "
                f"vector; leaf {i} has dtype {jnp.asarray(leaf).dtype} "
                "(tpuddp keeps f32 master params — mixed compute dtypes live "
                "in activations, not parameters)"
            )
    shapes = tuple(tuple(int(d) for d in np.shape(l)) for l in flat)
    sizes = tuple(int(np.prod(s)) if s else 1 for s in shapes)
    raw = sum(sizes)
    total = world * math.ceil(raw / world)
    return FlatParamSpec(treedef, shapes, sizes, total, world)


def _tree_to_vec(tree, spec: FlatParamSpec):
    """Concatenate a pytree's leaves (ravel order = tree_flatten order) into
    the spec's padded (total,) f32 vector."""
    leaves = jax.tree_util.tree_leaves(tree)
    flat = jnp.concatenate([jnp.ravel(l) for l in leaves])
    pad = spec.total - flat.shape[0]
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat


def _vec_to_tree(vec, spec: FlatParamSpec):
    leaves, offset = [], 0
    for shape, size in zip(spec.shapes, spec.sizes):
        chunk = jax.lax.slice(vec, (offset,), (offset + size,))
        leaves.append(chunk.reshape(shape))
        offset += size
    return jax.tree_util.tree_unflatten(spec.treedef, leaves)


def sharded_state_spec(opt_state_template, spec: FlatParamSpec, comm=None,
                       axis=DATA_AXIS):
    """The shard_map PartitionSpec pytree for a TrainState whose optimizer
    moment vectors are sharded over the data axis (weight-update sharding):
    every (total,)-sized 1-D leaf of the optimizer state is P(axis),
    everything else replicated. ``comm`` (a GradComm with an error-feedback
    residual) additionally marks ``comm_state`` sharded — the residual is
    per-replica local state, laid out like the moment shards. ``axis`` is
    the data axis name (a tuple on the factored hierarchical mesh)."""
    def leaf_spec(l):
        if getattr(l, "ndim", None) == 1 and l.shape[0] == spec.total:
            return P(axis)
        return P()

    opt_spec = jax.tree_util.tree_map(leaf_spec, opt_state_template)
    return TrainState(
        params=P(), model_state=P(), opt_state=opt_spec, step=P(), rng=P(),
        comm_state=(
            P(axis) if comm is not None and comm.needs_residual else P()
        ),
        skipped_steps=P(),  # guard counters replicate (P() is a safe prefix
        # for the empty subtree when the guard is off)
    )


def comm_state_spec(axis=DATA_AXIS):
    """The shard_map PartitionSpec pytree for a TrainState whose ONLY sharded
    member is the per-replica comm-hook residual (an EF hook without
    weight-update sharding): everything replicated except ``comm_state``."""
    return TrainState(
        params=P(), model_state=P(), opt_state=P(), step=P(), rng=P(),
        comm_state=P(axis), skipped_steps=P(),
    )


def _split_step_rng(state: TrainState, axis_name: Optional[str]):
    """Per-step key; inside shard_map additionally fold in the replica index so
    dropout/augmentation masks differ across replicas (device-level rank fold,
    mirroring the reference's per-rank seeds)."""
    rng = jax.random.fold_in(state.rng, state.step)
    if axis_name is not None:
        rng = fold_in_axis_index(rng, axis_name)
    return jax.random.split(rng)


_SYNC_BUFFER_MODES = ("broadcast", "pmean", "none")


def _validate_sync_buffers(model, axis_name: Optional[str], sync_buffers: str):
    """Build-time honesty check: the shard_map step publishes ``model_state``
    with a replicated out_spec, so any config that would let per-replica
    buffers diverge silently must be refused here, not discovered as a wrong
    checkpoint later."""
    if sync_buffers not in _SYNC_BUFFER_MODES:
        raise ValueError(
            f"unknown sync_buffers {sync_buffers!r}; one of {_SYNC_BUFFER_MODES}"
        )
    if axis_name is not None and sync_buffers == "none":
        from tpuddp.nn.norm import has_divergent_buffers

        if has_divergent_buffers(model):
            raise ValueError(
                'sync_buffers="none" with a module whose buffers diverge '
                "across replicas (an unsynced stateful BatchNorm, or a "
                "custom stateful layer that does not declare "
                "divergent_state()): per-replica state would diverge but be "
                "published as replicated. Use sync_buffers='broadcast' "
                "(torch DDP's broadcast_buffers=True default), 'pmean', "
                "convert_sync_batchnorm(model), or declare "
                "divergent_state() -> False on the module if its state is "
                "replica-invariant."
            )


def _sync_model_state(model_state, axis_name, sync_buffers: str):
    """The ``sync_buffers`` policy over the step's new module buffers."""
    if axis_name is None or sync_buffers == "none":
        return model_state
    with _prof.scope(_prof.BUFFERS):
        if sync_buffers == "broadcast":
            # torch DDP's default broadcast_buffers=True: unsynced BN buffers
            # follow rank 0. Synced BN already produced identical buffers.
            return col.broadcast(model_state, root=0, axis_name=axis_name)
        # pmean: average instead of rank-0-wins, every replica's statistics
        # contribute (identical when BN is already synced)
        return col.pmean(model_state, axis_name)


def _make_grad_core(
    model,
    criterion,
    axis_name: Optional[str],
    sync_buffers: str,
    augment: Optional[Callable],
    remat: bool = False,
):
    """The forward+backward half of the train step: one micro-batch in,
    ``(grads, synced_model_state, loss, n, counters)`` out (``counters``: the
    additive program counters a model's output carries, ``{}`` for most). Gradients are this replica's
    LOCAL batch-mean gradient — cross-replica reduction belongs to the update
    half (:func:`_make_update_fn`), so gradient accumulation can sum local
    grads over K micro-batches and pay for ONE collective per cycle."""
    # Rematerialization: trade FLOPs for HBM by recomputing activations in the
    # backward pass (jax.checkpoint) — how large models/batches fit on-chip.
    apply_fn = model.apply
    if remat:
        def apply_fn(params, mstate, x, ctx):  # noqa: F811
            fn = jax.checkpoint(
                lambda p, s, v: model.apply(p, s, v, ctx),
                static_argnums=(),
            )
            return fn(params, mstate, x)

    def grad_core(state: TrainState, x, y, w):
        aug_rng, dropout_rng = _split_step_rng(state, axis_name)
        if augment is not None:
            with _prof.scope(_prof.AUGMENT):
                x = augment(aug_rng, x)

        def loss_fn(params):
            # sample_weight masks padded rows out of BatchNorm statistics,
            # not just loss/metrics (see nn/norm.py)
            ctx = Context(
                train=True, rng=dropout_rng, axis_name=axis_name, sample_weight=w
            )
            with _prof.scope(_prof.FORWARD):
                logits, model_state = apply_fn(params, state.model_state, x, ctx)
            with _prof.scope(_prof.LOSS):
                loss = criterion(logits, y, w)
            return loss, (model_state, getattr(logits, "counters", {}))

        (loss, (model_state, counters)), grads = jax.value_and_grad(
            loss_fn, has_aux=True
        )(state.params)
        model_state = _sync_model_state(model_state, axis_name, sync_buffers)
        return grads, model_state, loss, jnp.sum(w), counters

    return grad_core


def _firewall_gate(ok, do_update, params, opt_state, comm_state, skipped):
    """The lax.cond firewall gate: ``do_update() -> (params, opt, comm)``
    executes only on a finite aggregated gradient; the skip branch hands
    the inputs back bitwise (the EF residual included — its NaN-poisoned
    candidate is never materialized into the carry) and bumps the
    counters. ``consecutive`` resets on every applied update."""

    def _apply():
        new_params, new_opt_state, new_comm = do_update()
        return (
            new_params, new_opt_state, new_comm,
            guard_lib.reset_consecutive(skipped),
        )

    def _skip():
        return (
            params, opt_state, comm_state,
            guard_lib.bump_skip_counters(skipped),
        )

    return jax.lax.cond(ok, _apply, _skip)


def _make_update_fn(
    optimizer,
    axis_name,
    clip_grad_norm: Optional[float],
    wus_spec: Optional[FlatParamSpec],
    comm=None,
    guard: bool = False,
    hier: Optional[Tuple[str, str]] = None,
):
    """The optimizer half of the train step: replica-local mean gradients in,
    ``(new_params, new_opt_state, new_comm_state, new_skipped)`` out. Owns
    the cross-replica exchange (pmean, a compressed bucketed exchange when a
    comm hook is configured, or reduce-scatter/all-gather under
    weight-update sharding) and the clip-after-aggregate. ``comm`` is a
    :class:`tpuddp.parallel.comm.GradComm` plan (None or hook "none" keeps
    the legacy full-precision path byte-identical); ``comm_state`` threads
    the error-feedback residual through the step. ``hier=(inner, outer)``
    routes the exchange through the hierarchical multi-hop reduction
    (``comm_topology="hierarchical"``: intra-host f32 reduce-scatter over
    ``inner``, compressed inter-host exchange over ``outer``, all-gather —
    requires a ``comm`` plan, which may carry hook "none").

    ``guard=True`` arms the non-finite gradient firewall
    (resilience/guard.py): ONE fused finiteness reduction over the
    aggregated f32 gradient — post-allreduce, so a NaN/Inf on any replica
    propagates through the sum and every replica agrees on the verdict by
    construction; with a comm hook the check runs on the decompressed f32
    payload (auto mode checks before quantization, where the aggregate
    already exists) — gates clip + optimizer.update through ``lax.cond``. A
    bad step is a bitwise no-op on params/opt-state/EF-residual and bumps
    the ``skipped_steps`` counters. ``guard=False`` is the pre-guard code
    path verbatim (identical HLO, ``skipped`` passes through untouched)."""

    def apply_update(params, opt_state, grads, comm_state, skipped):
        if wus_spec is not None:
            # Weight-update sharding (the cross-replica weight-update recipe
            # of arxiv.org/abs/2004.13336, ZeRO-1's TPU-native shape): instead
            # of every replica all-reducing the full gradient and redundantly
            # running the identical optimizer update over ALL parameters,
            # reduce-scatter hands each replica the averaged gradient for its
            # 1/N contiguous shard of the flattened parameter vector; each
            # replica updates only that shard (with its 1/N slice of the
            # optimizer moments — m/v live SHARDED across the mesh, an N-fold
            # optimizer-memory and update-HBM-traffic saving); the new shards
            # are all-gathered back into replicated parameters over ICI.
            # Same bytes on the interconnect as the allreduce (scatter+gather
            # IS an allreduce), 1/N of the optimizer's HBM round trip.
            world = wus_spec.world
            shard_n = wus_spec.total // world
            with _prof.scope(_prof.EXCHANGE):
                g_vec = _tree_to_vec(grads, wus_spec)
                if comm is not None and comm.compressed:
                    # comm-hook composition: scatter the COMPRESSED payload —
                    # half the gradient wire bytes; the bf16_ef residual stays
                    # full-length and replica-local (see comm.reduce_scatter)
                    g_shard, new_comm = comm.reduce_scatter(
                        g_vec, comm_state, axis_name
                    )
                else:
                    g_shard = (
                        jax.lax.psum_scatter(
                            g_vec, axis_name, scatter_dimension=0, tiled=True
                        )
                        / world
                    )
                    new_comm = comm_state

            def wus_update(g_shard=g_shard, new_comm=new_comm):
                g = g_shard
                if clip_grad_norm is not None:
                    # the global norm of a sharded vector is one scalar psum
                    # away; padding zeros contribute nothing
                    with _prof.scope(_prof.CLIP):
                        norm = jnp.sqrt(
                            jax.lax.psum(jnp.sum(jnp.square(g)), axis_name)
                        )
                        g = g * jnp.minimum(1.0, clip_grad_norm / (norm + 1e-6))
                with _prof.scope(_prof.OPTIMIZER):
                    idx = jax.lax.axis_index(axis_name)
                    p_vec = _tree_to_vec(params, wus_spec)
                    p_shard = jax.lax.dynamic_slice(
                        p_vec, (idx * shard_n,), (shard_n,)
                    )
                    update_flat = getattr(optimizer, "update_flat", None)
                    if update_flat is not None:
                        # layer-boundary-aware flat update (LARS/LAMB trust
                        # ratios over the spec's leaf offsets; per-layer norms
                        # psum across the axis since shards straddle layers)
                        new_p_shard, new_opt_state = update_flat(
                            g, opt_state, p_shard, spec=wus_spec,
                            axis_name=axis_name, shard_index=idx,
                        )
                    else:
                        new_p_shard, new_opt_state = optimizer.update(
                            g, opt_state, p_shard
                        )
                with _prof.scope(_prof.EXCHANGE):
                    new_p_vec = jax.lax.all_gather(
                        new_p_shard, axis_name, tiled=True
                    )
                    new_params = _vec_to_tree(new_p_vec, wus_spec)
                return new_params, new_opt_state, new_comm

            if not guard:
                new_params, new_opt_state, new_comm = wus_update()
                return new_params, new_opt_state, new_comm, skipped
            # the scattered shards of the aggregated gradient live on
            # different replicas, so the local shard verdict must be agreed
            # globally: one scalar pmin next to the scatter. Every other
            # collective (clip psum, all-gather) sits inside the cond — all
            # replicas take the same branch, so they still pair up.
            with _prof.scope(_prof.GUARD):
                ok = (
                    col.pmin(
                        guard_lib.tree_all_finite(g_shard).astype(jnp.int32),
                        axis_name,
                    )
                    == 1
                )
                return _firewall_gate(
                    ok, wus_update, params, opt_state, comm_state, skipped
                )

        ok = None
        if guard and axis_name is None:
            # auto/managed mode: XLA's partitioner already aggregated inside
            # backward — `grads` IS the global-batch f32 gradient, checked
            # here BEFORE the hook quantizes it (the f32-payload contract)
            with _prof.scope(_prof.GUARD):
                ok = guard_lib.tree_all_finite(grads)
        with _prof.scope(_prof.EXCHANGE):
            if hier is not None and comm is not None:
                # hierarchical multi-hop reduction over the factored data
                # mesh: intra-host f32 reduce-scatter -> compressed inter-host
                # exchange -> all-gather (comm.reduce_hierarchical)
                agg_grads, new_comm = comm.reduce_hierarchical(
                    grads, comm_state, hier[0], hier[1]
                )
            elif comm is not None and comm.compressed:
                # bucketed compressed allreduce (torch DDP comm-hook analog):
                # flatten -> per-bucket compress -> collective -> f32
                # decompress -> mean. With axis_name=None (auto mode) this is
                # the local quantization emulation — XLA's implicit psum
                # already aggregated.
                agg_grads, new_comm = comm.reduce(grads, comm_state, axis_name)
            elif axis_name is not None:
                # THE DDP step: average gradients across replicas (reference
                # :125's implicit NCCL allreduce). In auto mode XLA inserts
                # this itself.
                agg_grads, new_comm = col.pmean(grads, axis_name), comm_state
            else:
                agg_grads, new_comm = grads, comm_state
        return _update_reduced(
            optimizer, clip_grad_norm, guard, params, opt_state, agg_grads,
            new_comm, comm_state, skipped, ok,
        )

    return apply_update


def _update_reduced(optimizer, clip_grad_norm, guard, params, opt_state,
                    agg_grads, cand_comm, comm_state, skipped, ok=None):
    """Verdict + clip + update over an ALREADY cross-replica-reduced f32
    gradient, behind the ``lax.cond`` firewall when ``guard``: the tail of
    :func:`_make_update_fn`'s replicated path. ``ok`` is a verdict
    taken before the exchange (auto mode); without one the post-allreduce
    gradient is checked — the sum propagated any replica's NaN/Inf
    everywhere, so this replica-local check IS the global verdict, no extra
    collective on the replicated path. (bf16 keeps the f32 exponent range, so
    quantization cannot mask a non-finite f32 payload from the post-reduce
    check.)"""

    def plain_update():
        g = agg_grads
        if clip_grad_norm is not None:
            # clip-before-aggregate caveat (reference README): clip the
            # *averaged* grad, identically on all replicas.
            with _prof.scope(_prof.CLIP):
                g, _ = _optim.clip_grad_norm_(g, clip_grad_norm)
        with _prof.scope(_prof.OPTIMIZER):
            new_params, new_opt_state = optimizer.update(g, opt_state, params)
        return new_params, new_opt_state, cand_comm

    if not guard:
        new_params, new_opt_state, new_comm = plain_update()
        return new_params, new_opt_state, new_comm, skipped
    with _prof.scope(_prof.GUARD):
        if ok is None:
            ok = guard_lib.tree_all_finite(agg_grads)
        return _firewall_gate(
            ok, plain_update, params, opt_state, comm_state, skipped
        )


def _revert_buffers_on_skip(old_state, new_state, skipped, new_skipped):
    """Extend the firewall's no-op to the module buffers: BatchNorm running
    stats computed from the poisoned forward must not outlive the skipped
    update (the counters move only on a skip, so the select is exactly the
    firewall's verdict)."""
    with _prof.scope(_prof.GUARD):
        skipped_now = new_skipped["total"] != skipped["total"]
        return jax.tree_util.tree_map(
            lambda old, new: jnp.where(skipped_now, old, new),
            old_state, new_state,
        )


def _step_metrics(loss, n, counters=None):
    with _prof.scope(_prof.METRICS):
        return {
            "loss_sum": (loss * n)[None],  # sample-weighted, reference :131
            "n": n[None],
            # the model's own additive counters (model.counter_names)
            **{name: value[None] for name, value in (counters or {}).items()},
        }


def _metric_specs(model, spec):
    """The step's metrics as ``shard_map`` returns them: the loss sums and,
    for a model that counts (``counter_names``), its counters."""
    names = ("loss_sum", "n", *getattr(model, "counter_names", ()))
    return {name: spec for name in names}


def _sum_metrics(stacked):
    with _prof.scope(_prof.METRICS):
        return jax.tree_util.tree_map(lambda a: jnp.sum(a, axis=0), stacked)


def _make_train_core(
    model,
    criterion,
    optimizer,
    axis_name,
    sync_buffers: str,
    clip_grad_norm: Optional[float],
    augment: Optional[Callable],
    remat: bool = False,
    wus_spec: Optional[FlatParamSpec] = None,
    comm=None,
    guard: bool = False,
    hier: Optional[Tuple[str, str]] = None,
):
    _validate_sync_buffers(model, axis_name, sync_buffers)
    if wus_spec is not None and axis_name is None:
        raise ValueError(
            "weight_update_sharding needs the explicit per-replica step "
            "(mode='shard_map'): the reduce-scatter/all-gather exchange is "
            "expressed over its named data axis"
        )
    grad_core = _make_grad_core(
        model, criterion, axis_name, sync_buffers, augment, remat
    )
    apply_update = _make_update_fn(
        optimizer, axis_name, clip_grad_norm, wus_spec, comm=comm, guard=guard,
        hier=hier,
    )

    def core(state: TrainState, x, y, w):
        grads, model_state, loss, n, counters = grad_core(state, x, y, w)
        new_params, new_opt_state, new_comm, new_skipped = apply_update(
            state.params, state.opt_state, grads, state.comm_state,
            state.skipped_steps,
        )
        if guard:
            model_state = _revert_buffers_on_skip(
                state.model_state, model_state, state.skipped_steps,
                new_skipped,
            )
        metrics = _step_metrics(loss, n, counters)
        new_state = TrainState(
            params=new_params,
            model_state=model_state,
            opt_state=new_opt_state,
            step=state.step + 1,
            rng=state.rng,
            comm_state=new_comm,
            skipped_steps=new_skipped,
        )
        return new_state, metrics

    return core


def _make_eval_core(model, criterion, axis_name, transform: Optional[Callable]):
    def core(state: TrainState, x, y, w):
        if transform is not None:
            with _prof.scope(_prof.AUGMENT):
                x = transform(x)
        ctx = Context(train=False, rng=None, axis_name=axis_name, sample_weight=w)
        with _prof.scope(_prof.FORWARD):
            logits, _ = model.apply(state.params, state.model_state, x, ctx)
        with _prof.scope(_prof.LOSS):
            loss = criterion(logits, y, w)
        with _prof.scope(_prof.METRICS):
            n = jnp.sum(w)
            predicted = jnp.argmax(logits, axis=-1)
            # per-sequence weights cover their tokens (a no-op for per-sample ones)
            correct = jnp.sum((predicted == y) * w.reshape(w.shape + (1,) * (y.ndim - w.ndim)))
            return {
                "loss_sum": (loss * n)[None],
                "correct": correct[None],
                "n": n[None],
            }

    return core


def build_train_step(
    model,
    criterion,
    optimizer,
    mesh,
    mode: str = "shard_map",
    sync_buffers: str = "broadcast",
    clip_grad_norm: Optional[float] = None,
    augment: Optional[Callable] = None,
    remat: bool = False,
    wus_spec: Optional[FlatParamSpec] = None,
    state_spec=None,
    comm=None,
    guard: bool = False,
    hier: Optional[Tuple[str, str]] = None,
):
    """Compile the DP train step over ``mesh``. Returns
    ``step(state, (x, y, w)) -> (new_state, metrics)`` with donated state.
    ``wus_spec``/``state_spec`` (from :func:`make_flat_param_spec` /
    :func:`sharded_state_spec`) switch on weight-update sharding. ``comm``
    (a :class:`tpuddp.parallel.comm.GradComm`) switches the gradient
    exchange to the bucketed compressed hook pipeline; an error-feedback
    hook needs a ``state_spec`` marking ``comm_state`` sharded
    (:func:`comm_state_spec` or :func:`sharded_state_spec` with ``comm=``).
    ``hier=(inner, outer)`` routes the exchange hierarchically over a
    factored mesh (see :func:`_make_update_fn`). ``guard=True`` arms the
    non-finite gradient firewall (state must carry ``skipped_steps``
    counters; see resilience/guard.py); ``False`` lowers to the identical
    program as before the guard existed."""
    if mode == "shard_map":
        axis = data_axes(mesh)
        st_spec = state_spec if state_spec is not None else P()
        core = _make_train_core(
            model, criterion, optimizer, axis, sync_buffers,
            clip_grad_norm, augment, remat, wus_spec=wus_spec, comm=comm,
            guard=guard, hier=hier,
        )
        fn = shard_map(
            core,
            mesh=mesh,
            in_specs=(st_spec, P(axis), P(axis), P(axis)),
            out_specs=(st_spec, _metric_specs(model, P(axis))),
            check_vma=False,
        )
        jitted = jax.jit(fn, donate_argnums=0)
    elif mode == "auto":
        core = _make_train_core(
            model, criterion, optimizer, None, sync_buffers,
            clip_grad_norm, augment, remat, wus_spec=wus_spec, comm=comm,
            guard=guard,
        )
        jitted = jax.jit(
            core,
            in_shardings=(replicated(mesh), data_sharded(mesh), data_sharded(mesh), data_sharded(mesh)),
            out_shardings=(replicated(mesh), replicated(mesh)),
            donate_argnums=0,
        )
    else:
        raise ValueError(f"unknown mode {mode!r}; one of 'shard_map', 'auto'")

    def step(state, batch):
        x, y, w = batch
        return jitted(state, x, y, w)

    return step


def build_train_scan_step(
    model,
    criterion,
    optimizer,
    mesh,
    mode: str = "shard_map",
    sync_buffers: str = "broadcast",
    clip_grad_norm: Optional[float] = None,
    augment: Optional[Callable] = None,
    remat: bool = False,
    wus_spec: Optional[FlatParamSpec] = None,
    state_spec=None,
    grad_accumulation: int = 1,
    comm=None,
    guard: bool = False,
    hier: Optional[Tuple[str, str]] = None,
):
    """Multi-step variant: runs K train steps per jit call via ``lax.scan``.

    Takes batches stacked on a leading steps axis ``(K, batch, ...)`` and
    returns summed metrics. Semantically identical to K calls of the single
    step (same RNG fold per state.step, same metric totals) but amortizes
    per-dispatch host/runtime latency K-fold — on dispatch-bound runtimes
    this is the difference between dispatch-bound and MXU-bound throughput.
    K is static per compilation (one cache entry per
    distinct K, so group epochs into fixed-size chunks).

    ``grad_accumulation=A > 1`` turns every A consecutive micro-batches into
    ONE optimizer update (effective-batch control, the native analog of the
    managed path's ``gradient_accumulation_steps`` — reference
    multi-GPU-training-torch.py:88's batch size knob): the scan is
    restructured as cycles of A micro-batches whose sample-weighted gradient
    sums accumulate in the carry; the cycle boundary pays ONE cross-replica
    exchange + clip + update on the n-weighted average — exactly the gradient
    of one step over the A micro-batches' concatenation (all-padding
    micro-batches contribute nothing, so tails can be padded to a static
    cycle length). K must be a multiple of A.
    """
    if mode == "shard_map":
        axis_name = data_axes(mesh)
        in_batch = P(None, axis_name)
        metric_spec = P(axis_name)
    elif mode == "auto":
        axis_name, in_batch = None, None
    else:
        raise ValueError(f"unknown mode {mode!r}; one of 'shard_map', 'auto'")

    accum = int(grad_accumulation)
    if accum < 1:
        raise ValueError(f"grad_accumulation must be >= 1, got {grad_accumulation!r}")
    _validate_sync_buffers(model, axis_name, sync_buffers)
    if wus_spec is not None and axis_name is None:
        raise ValueError(
            "weight_update_sharding needs the explicit per-replica step "
            "(mode='shard_map'): the reduce-scatter/all-gather exchange is "
            "expressed over its named data axis"
        )

    if accum == 1:
        core = _make_train_core(
            model, criterion, optimizer, axis_name, sync_buffers,
            clip_grad_norm, augment, remat, wus_spec=wus_spec, comm=comm,
            guard=guard, hier=hier,
        )

        def multi(state: TrainState, xs, ys, ws):
            def body(st, batch):
                x, y, w = batch
                st, m = core(st, x, y, w)
                return st, m

            state, stacked = jax.lax.scan(body, state, (xs, ys, ws))
            return state, _sum_metrics(stacked)
    else:
        grad_core = _make_grad_core(
            model, criterion, axis_name, sync_buffers, augment, remat
        )
        apply_update = _make_update_fn(
            optimizer, axis_name, clip_grad_norm, wus_spec, comm=comm,
            guard=guard, hier=hier,
        )
        def multi(state: TrainState, xs, ys, ws):
            k = xs.shape[0]
            if k % accum != 0:
                raise ValueError(
                    f"scan length {k} is not a multiple of "
                    f"grad_accumulation={accum}; pad the chunk to a whole "
                    "number of accumulation cycles (training/loop.py does "
                    "this with all-padding micro-batches)"
                )
            cyc = (
                xs.reshape(k // accum, accum, *xs.shape[1:]),
                ys.reshape(k // accum, accum, *ys.shape[1:]),
                ws.reshape(k // accum, accum, *ws.shape[1:]),
            )

            def cycle(st, cyc_batch):
                zeros = jax.tree_util.tree_map(jnp.zeros_like, st.params)
                ms0 = st.model_state  # pre-cycle buffers for the guard revert

                def micro(carry, mb):
                    st, gacc, nacc = carry
                    x, y, w = mb
                    grads, model_state, loss, n, counters = grad_core(st, x, y, w)
                    # n-weighted gradient sum: micro-batch i's local grad is
                    # the mean over its n_i live samples, so Σ n_i·g_i / Σ n_i
                    # is EXACTLY the mean gradient of the concatenated batch,
                    # padded/ragged micro-batches included
                    gacc = jax.tree_util.tree_map(
                        lambda a, g: a + n * g, gacc, grads
                    )
                    st = TrainState(
                        params=st.params,
                        model_state=model_state,
                        opt_state=st.opt_state,
                        step=st.step + 1,
                        rng=st.rng,
                        comm_state=st.comm_state,
                        skipped_steps=st.skipped_steps,
                    )
                    m = _step_metrics(loss, n, counters)
                    return (st, gacc, nacc + n), m

                (st, gacc, nacc), stacked = jax.lax.scan(
                    micro, (st, zeros, jnp.zeros((), jnp.float32)), cyc_batch
                )
                # exact weighted mean even for fractional sample weights
                # (guard only the all-padding nacc==0 case, like nn/loss.py)
                denom = jnp.where(nacc == 0, 1.0, nacc)
                g = jax.tree_util.tree_map(lambda a: a / denom, gacc)
                # the firewall (guard=True) checks THIS aggregated
                # cycle-mean gradient: one poisoned micro-batch skips the
                # whole cycle's update, bitwise
                new_params, new_opt_state, new_comm, new_skipped = apply_update(
                    st.params, st.opt_state, g, st.comm_state, st.skipped_steps
                )
                model_state = st.model_state
                if guard:
                    # a skipped cycle also reverts the buffers the cycle's
                    # forwards (poisoned micro-batch included) accumulated —
                    # the cycle is the atomic update unit
                    model_state = _revert_buffers_on_skip(
                        ms0, st.model_state, st.skipped_steps, new_skipped
                    )
                st = TrainState(
                    params=new_params,
                    model_state=model_state,
                    opt_state=new_opt_state,
                    step=st.step,
                    rng=st.rng,
                    comm_state=new_comm,
                    skipped_steps=new_skipped,
                )
                return st, _sum_metrics(stacked)

            state, stacked = jax.lax.scan(cycle, state, cyc)
            return state, _sum_metrics(stacked)

    if mode == "shard_map":
        st_spec = state_spec if state_spec is not None else P()
        fn = shard_map(
            multi,
            mesh=mesh,
            in_specs=(st_spec, in_batch, in_batch, in_batch),
            out_specs=(st_spec, _metric_specs(model, metric_spec)),
            check_vma=False,
        )
        jitted = jax.jit(fn, donate_argnums=0)
    else:
        rep, sh = replicated(mesh), NamedSharding(mesh, P(None, DATA_AXIS))
        jitted = jax.jit(
            multi,
            in_shardings=(rep, sh, sh, sh),
            out_shardings=(rep, rep),
            donate_argnums=0,
        )

    def step(state, stacked_batch):
        xs, ys, ws = stacked_batch
        return jitted(state, xs, ys, ws)

    return step


def stack_batches(batches):
    """Stack K host batches [(x, y, w), ...] into one (K, ...) super-batch for
    the scan step."""
    xs, ys, ws = zip(*batches)
    import numpy as np

    return np.stack(xs), np.stack(ys), np.stack(ws)


def build_eval_step(
    model,
    criterion,
    mesh,
    mode: str = "shard_map",
    transform: Optional[Callable] = None,
    state_spec=None,
):
    """Compile the DP eval step: ``eval_step(state, (x, y, w)) -> metrics``
    (per-replica partial sums in shard_map mode, global sums in auto mode).
    ``state_spec`` describes a weight-update-sharded TrainState (the eval
    core never reads the optimizer state, but the input placement must
    match)."""
    if mode == "shard_map":
        axis = data_axes(mesh)
        core = _make_eval_core(model, criterion, axis, transform)
        fn = shard_map(
            core,
            mesh=mesh,
            in_specs=(
                state_spec if state_spec is not None else P(),
                P(axis), P(axis), P(axis),
            ),
            out_specs={"loss_sum": P(axis), "correct": P(axis), "n": P(axis)},
            check_vma=False,
        )
        jitted = jax.jit(fn)
    elif mode == "auto":
        core = _make_eval_core(model, criterion, None, transform)
        jitted = jax.jit(
            core,
            in_shardings=(replicated(mesh), data_sharded(mesh), data_sharded(mesh), data_sharded(mesh)),
            out_shardings=replicated(mesh),
        )
    else:
        raise ValueError(f"unknown mode {mode!r}")

    def step(state, batch):
        x, y, w = batch
        return jitted(state, x, y, w)

    return step


def build_eval_scan_step(
    model,
    criterion,
    mesh,
    mode: str = "shard_map",
    transform: Optional[Callable] = None,
    state_spec=None,
):
    """Multi-batch eval variant: K eval batches per jit call via ``lax.scan``
    over a ``(K, batch, ...)`` stack, returning summed metrics — the eval-pass
    analog of :func:`build_train_scan_step` (without it the eval epoch is
    per-batch dispatch-bound, reference warm loop
    multi-GPU-training-torch.py:136-153)."""
    if mode == "shard_map":
        axis = data_axes(mesh)
        core = _make_eval_core(model, criterion, axis, transform)
    elif mode == "auto":
        core = _make_eval_core(model, criterion, None, transform)
    else:
        raise ValueError(f"unknown mode {mode!r}; one of 'shard_map', 'auto'")

    def multi(state: TrainState, xs, ys, ws):
        def body(carry, batch):
            return carry, core(state, *batch)

        _, stacked = jax.lax.scan(body, 0, (xs, ys, ws))
        return _sum_metrics(stacked)

    if mode == "shard_map":
        in_batch = P(None, axis)
        fn = shard_map(
            multi,
            mesh=mesh,
            in_specs=(
                state_spec if state_spec is not None else P(),
                in_batch, in_batch, in_batch,
            ),
            out_specs={
                "loss_sum": P(axis),
                "correct": P(axis),
                "n": P(axis),
            },
            check_vma=False,
        )
        jitted = jax.jit(fn)
    else:
        rep, sh = replicated(mesh), NamedSharding(mesh, P(None, DATA_AXIS))
        jitted = jax.jit(multi, in_shardings=(rep, sh, sh, sh), out_shardings=rep)

    def step(state, stacked_batch):
        xs, ys, ws = stacked_batch
        return jitted(state, xs, ys, ws)

    return step


def accumulate_metrics(acc, new):
    """On-device accumulation of per-step metric sums (fixes quirk Q5 — no
    ``loss.item()`` host sync per batch; dispatch stays async)."""
    if acc is None:
        return new
    return jax.tree_util.tree_map(jnp.add, acc, new)


_tree_sum_jit = jax.jit(
    lambda t: jax.tree_util.tree_map(jnp.sum, t)
)


def finalize_metrics(acc):
    """Epoch-end aggregation: ONE jitted cross-device sum over the whole
    metric tree — the analog of the reference's five ``dist.all_reduce`` calls
    (:198-204) — then one host fetch. ``acc`` may be any pytree of metric
    arrays (e.g. ``{"train": ..., "eval": ...}``); None subtrees are allowed
    and come back as empty dicts."""
    if acc is None:
        return {}
    summed = _tree_sum_jit(acc)
    return jax.tree_util.tree_map(float, jax.device_get(summed))
