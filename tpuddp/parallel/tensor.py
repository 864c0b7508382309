"""Tensor-parallel execution over the 2-D ``("data", "model")`` mesh.

This module turns the transformer family's *declared* partition metadata
(``tpuddp.models.transformer.param_logical_axes`` / ``partition_spec`` —
SNIPPETS.md [2]'s rule table, unconsumed since the family landed) into a
running training step:

- **column-split** ``wqkv`` / ``mlp w1`` (each model shard owns ``H/M`` heads
  / ``F/M`` hidden units; the input activation is replicated, no exchange on
  the way in);
- **row-split** ``attn wo`` / ``mlp w2`` (each shard contracts its own slice
  and the partial outputs ``psum`` over ``"model"`` — one activation psum per
  row-split projection, two per block, Megatron's f/g pattern);
- **vocab-split** embedding + tied LM head: the lookup is a masked local
  gather whose cross-shard ``psum`` is *exact* (every token's row lives on
  exactly one shard; the others contribute literal zeros), and the logit
  **gather** concatenates local vocab columns over ``"model"`` — exact by
  construction, no reduction touches a logit value.

The model-axis exchanges are expressed through ``jax.custom_vjp`` collectives
(:func:`copy_to_tp` / :func:`reduce_from_tp` / :func:`gather_from_tp`) so the
backward pass is *explicit* — the conjugate psum of a column-split input and
the cotangent slice of the gather are written here, not left to shard_map's
transpose machinery (which is exactly the part ``check_vma=False`` opts out
of validating).

Everything data-parallel composes unchanged and reduces over the **data**
axis only: the batch splits ``P("data")``, gradient comm hooks
(none/bf16_ef/int8_ef/topk_ef) bucket the *local shard* gradient and
exchange it across data replicas (each ``(data_index, model_index)`` device
keeps its own error-feedback residual — the comm_state lays out
``P(("data", "model"))``), and the guard firewall agrees its verdict with one
scalar pmin over ``"model"`` (shards hold different gradient slices, so their
local verdicts can legitimately differ).

Layout note (the one reshape): the canonical joined-QKV weight packs its
columns ``[3, H, Dh]`` with the q/k/v factor OUTERMOST, so a contiguous
column split is not head-aligned. The TP state stores it as ``(E, 3, H*Dh)``
(and ``bqkv`` as ``(3, H*Dh)``) — sharding the last axis is then exactly a
head split, and flattening the gathered ``(E, 3, H*Dh)`` back to
``(E, 3*H*Dh)`` reproduces the canonical layout bit for bit
(:func:`to_tp_tree` / :func:`from_tp_tree`).
"""

from __future__ import annotations

import functools
import hashlib
import json
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from tpuddp.parallel import collectives as col
from tpuddp.parallel.mesh import DATA_AXIS
from tpuddp.parallel.mesh2d import MODEL_AXIS
from tpuddp.resilience import guard as guard_lib
from tpuddp.training.train_state import TrainState

# The tensor-parallel rule set: SNIPPETS.md [2]'s table (heads/mlp/joined_kv
# -> "model") EXTENDED with the vocab split — the embedding and the tied LM
# head shard their vocabulary rows so the largest single matrix also cuts
# 1/M per chip. The base table keeps vocab unsharded because generic rules
# cannot promise an exact lookup; this layer can (masked gather + zero psum),
# so the TP rule set claims it. run_meta records tp_rules_hash so a history
# states exactly which rule set trained it.
def tp_rules() -> dict:
    from tpuddp.models import transformer as tf_lib

    rules = dict(tf_lib.PARTITION_RULES)
    rules["vocab"] = MODEL_AXIS
    return rules


def tp_rules_hash(rules: Optional[dict] = None) -> str:
    """Stable short hash of the TP rule table (the run_meta ``mesh`` block's
    ``tp_rules_hash`` field): two histories sharded under different rule sets
    must not read as the same configuration."""
    rules = tp_rules() if rules is None else rules
    canon = json.dumps({k: rules[k] for k in sorted(rules)}, sort_keys=True)
    return hashlib.sha256(canon.encode()).hexdigest()[:16]


def supports_tp(model) -> bool:
    """Does this model declare the partition metadata the TP layer consumes?
    (The transformer family does; CNNs don't — their TP story is deferred.)"""
    from tpuddp.models.transformer import TransformerLM

    return isinstance(model, TransformerLM)


def validate_tp_geometry(model, model_width: int) -> None:
    """Refuse a TP width the model cannot tile: heads, MLP hidden units, and
    vocabulary rows all split evenly or the shard shapes would be ragged."""
    if not supports_tp(model):
        raise ValueError(
            f"model {type(model).__name__} declares no partition metadata "
            "(param_logical_axes); tensor parallelism supports the "
            "transformer family — run other models at parallel.model=1"
        )
    for name, dim in (
        ("n_heads", model.n_heads),
        ("d_mlp", model.d_mlp),
        ("vocab_size", model.vocab_size),
    ):
        if dim % model_width:
            raise ValueError(
                f"parallel.model={model_width} does not tile the model's "
                f"{name}={dim}; every sharded dimension must split evenly"
            )


# ------------------------------------------------------ layout conversion --


def to_tp_tree(params):
    """Canonical param tree -> the TP layout: ``wqkv (E, 3HD) -> (E, 3, HD)``
    and ``bqkv (3HD,) -> (3, HD)`` so a last-axis shard is head-aligned.
    Every other leaf passes through untouched."""

    def conv(block):
        attn = dict(block["attn"])
        w = attn["wqkv"]
        attn["wqkv"] = w.reshape(w.shape[0], 3, w.shape[1] // 3)
        attn["bqkv"] = attn["bqkv"].reshape(3, -1)
        out = dict(block)
        out["attn"] = attn
        return out

    out = dict(params)
    out["blocks"] = tuple(conv(b) for b in params["blocks"])
    return out


def from_tp_tree(tp_params):
    """Inverse of :func:`to_tp_tree`: the gathered ``(E, 3, H*Dh)`` flattens
    back to the canonical ``(E, 3*H*Dh)`` packing exactly."""

    def conv(block):
        attn = dict(block["attn"])
        w = attn["wqkv"]
        attn["wqkv"] = w.reshape(w.shape[0], w.shape[1] * w.shape[2])
        attn["bqkv"] = attn["bqkv"].reshape(-1)
        out = dict(block)
        out["attn"] = attn
        return out

    out = dict(tp_params)
    out["blocks"] = tuple(conv(b) for b in tp_params["blocks"])
    return out


def tp_param_specs(model, tp_params) -> dict:
    """PartitionSpec pytree (congruent with the TP-layout tree) applying the
    TP rule set: the model's declared ``partition_spec`` mapped leaf-by-leaf,
    with the two reshaped QKV leaves re-spelled for their 3-D/2-D layout."""
    from tpuddp.models import transformer as tf_lib

    mesh_axes = tf_lib.partition_spec(model, tp_params, rules=tp_rules())

    def to_P(t):
        return P(*t)

    spec = jax.tree_util.tree_map(
        to_P, mesh_axes,
        is_leaf=lambda leaf: isinstance(leaf, tuple) and not isinstance(leaf, P)
        and all(n is None or isinstance(n, str) for n in leaf),
    )
    blocks = []
    for b in spec["blocks"]:
        attn = dict(b["attn"])
        attn["wqkv"] = P(None, None, MODEL_AXIS)  # (E, 3, H*Dh): head split
        attn["bqkv"] = P(None, MODEL_AXIS)
        nb = dict(b)
        nb["attn"] = attn
        blocks.append(nb)
    out = dict(spec)
    out["blocks"] = tuple(blocks)
    return out


def _local_shape(shape, spec, model_width: int):
    out = list(shape)
    for d, axis in enumerate(tuple(spec)):
        if axis == MODEL_AXIS:
            out[d] = out[d] // model_width
    return tuple(out)


def local_param_template(tp_params, specs, model_width: int):
    """One model shard's view of the TP tree as host zeros — the template the
    gradient comm plan (bucket layout, byte accounting) is built from: comm
    hooks exchange the LOCAL shard gradient over the data axis only."""
    return jax.tree_util.tree_map(
        lambda leaf, spec: np.zeros(
            _local_shape(np.shape(leaf), spec, model_width), np.float32
        ),
        tp_params, specs,
    )


def per_chip_param_bytes(tp_params, specs, model_width: int) -> int:
    """Parameter bytes ONE chip holds under this sharding — to be read
    against the replicated (model=1) footprint."""
    total = 0
    for leaf, spec in zip(
        jax.tree_util.tree_leaves(tp_params),
        jax.tree_util.tree_leaves(specs),
    ):
        shape = _local_shape(np.shape(leaf), spec, model_width)
        itemsize = np.dtype(getattr(leaf, "dtype", np.float32)).itemsize
        total += int(np.prod(shape, dtype=np.int64)) * itemsize
    return total


def opt_state_specs(opt_state, tp_params, param_specs):
    """PartitionSpec pytree for an optimizer state over TP params: every
    state leaf congruent with a parameter (Adam m/v, SGD momentum — their
    tree paths end with the parameter's path) inherits that parameter's
    spec; scalars and anything unrecognized replicate. Shape matching would
    be ambiguous (``embed`` and ``pos`` can share a shape with different
    specs), so the PATH is the key."""
    param_spec_by_path = {
        jax.tree_util.keystr(p): s
        for p, s in jax.tree_util.tree_flatten_with_path(param_specs)[0]
    }
    flat, treedef = jax.tree_util.tree_flatten_with_path(opt_state)
    leaves = []
    for path, _leaf in flat:
        key = jax.tree_util.keystr(path)
        spec = P()
        for ppath, pspec in param_spec_by_path.items():
            if key.endswith(ppath):
                spec = pspec
                break
        leaves.append(spec)
    return jax.tree_util.tree_unflatten(treedef, leaves)


def place_tree(mesh, host_tree, specs):
    """Place a host pytree onto the mesh leaf by leaf under ``specs``
    (single-process: every device is addressable, a plain device_put
    shards/replicates as the spec says)."""
    return jax.tree_util.tree_map(
        lambda leaf, spec: jax.device_put(leaf, NamedSharding(mesh, spec)),
        host_tree, specs,
    )


def tp_state_spec(param_specs, opt_specs, comm=None) -> TrainState:
    """The shard_map PartitionSpec TrainState for the TP step: params and
    optimizer moments carry their model-axis shards, the per-device
    error-feedback residual (when an EF comm hook is armed) lays out
    ``P(("data", "model"))`` — one slice per ``(data_index, model_index)``
    device — and everything else replicates."""
    return TrainState(
        params=param_specs,
        model_state=P(),
        opt_state=opt_specs,
        step=P(),
        rng=P(),
        comm_state=(
            P((DATA_AXIS, MODEL_AXIS))
            if comm is not None and comm.needs_residual
            else P()
        ),
        skipped_steps=P(),
    )


# ------------------------------------- model-axis collectives (explicit AD) --


@jax.custom_vjp
def copy_to_tp(x):
    """Megatron's ``f``: identity forward at a column-split layer's input,
    psum over ``"model"`` backward — each shard backpropagates only its own
    branch, so the input's true cotangent is the cross-shard sum."""
    return x


copy_to_tp.defvjp(
    lambda x: (x, None),
    lambda _, ct: (lax.psum(ct, MODEL_AXIS),),
)


@jax.custom_vjp
def reduce_from_tp(x):
    """Megatron's ``g``: psum over ``"model"`` forward at a row-split layer's
    output (the partial contractions sum to the full one), identity backward
    (the summed output's cotangent already is every shard's cotangent)."""
    return lax.psum(x, MODEL_AXIS)


reduce_from_tp.defvjp(
    lambda x: (lax.psum(x, MODEL_AXIS), None),
    lambda _, ct: (ct,),
)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _gather_last(width: int, x):
    return lax.all_gather(x, MODEL_AXIS, axis=x.ndim - 1, tiled=True)


def _gather_last_fwd(width, x):
    return _gather_last(width, x), None


def _gather_last_bwd(width, _, ct):
    idx = lax.axis_index(MODEL_AXIS)
    return (lax.dynamic_slice_in_dim(ct, idx * width, width, axis=ct.ndim - 1),)


_gather_last.defvjp(_gather_last_fwd, _gather_last_bwd)


def gather_from_tp(x):
    """Exact last-axis concatenation over ``"model"`` (the vocab-split logit
    gather): forward is a pure all-gather — no value is reduced, so every
    logit column equals its unsharded self — and backward slices this
    shard's own columns out of the cotangent."""
    return _gather_last(int(x.shape[-1]), x)


# ----------------------------------------------------------- TP forward --


def tp_forward(model, p, tokens):
    """The tensor-parallel causal forward, per-device view inside shard_map:
    ``p`` is this shard's slice of the TP-layout tree, ``tokens`` this data
    replica's ``(B, T)`` int batch (replicated across the model axis).
    Returns full ``(B, T, V)`` logits (vocab columns gathered exactly).
    Matches ``TransformerLM.apply`` up to the row-split contractions'
    summation order (each is one psum of M partials)."""
    import math

    from tpuddp.models.transformer import _NEG_INF

    tokens = jnp.asarray(tokens).astype(jnp.int32)
    B, T = tokens.shape
    embed = p["embed"]["weight"]  # (V/M, E) — this shard's vocab rows
    v_local = embed.shape[0]
    offset = lax.axis_index(MODEL_AXIS) * v_local
    local_ids = tokens - offset
    mine = (local_ids >= 0) & (local_ids < v_local)
    safe = jnp.clip(local_ids, 0, v_local - 1)
    # masked local lookup + zero psum: exactly one shard contributes each
    # token's row, the rest add literal 0.0 — the lookup stays bitwise-exact
    partial_emb = jnp.where(mine[..., None], jnp.take(embed, safe, axis=0), 0.0)
    h = reduce_from_tp(partial_emb) + p["pos"]["weight"][:T]
    mask = jnp.tril(jnp.ones((T, T), bool))
    scale = 1.0 / math.sqrt(model.head_dim)
    for bp in p["blocks"]:
        # -- attention: column-split QKV (local heads), row-split output
        a = copy_to_tp(model._norm(bp["ln1"], h))
        qkv = jnp.einsum("bte,eck->btck", a, bp["attn"]["wqkv"]) + bp["attn"]["bqkv"]
        qkv = qkv.reshape(B, T, 3, -1, model.head_dim)  # (B, T, 3, H/M, Dh)
        q, k, v = qkv[..., 0, :, :], qkv[..., 1, :, :], qkv[..., 2, :, :]
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        scores = jnp.where(mask[None, None, :, :], scores, _NEG_INF)
        attn = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(h.dtype)
        o = jnp.einsum("bhqk,bkhd->bqhd", attn, v)
        part = o.reshape(B, T, -1) @ bp["attn"]["wo"]  # local head rows
        h = h + reduce_from_tp(part) + bp["attn"]["bo"]
        # -- MLP: column-split in, row-split out
        b = copy_to_tp(model._norm(bp["ln2"], h))
        m = jax.nn.gelu(
            b @ bp["mlp"]["w1"] + bp["mlp"]["b1"], approximate=False
        ) @ bp["mlp"]["w2"]
        h = h + reduce_from_tp(m) + bp["mlp"]["b2"]
    h = copy_to_tp(model._norm(p["ln_f"], h))
    return gather_from_tp(h @ embed.T)  # tied head: local vocab columns


# ------------------------------------------------------------ step builders --


def _make_tp_train_core(model, criterion, optimizer, comm, guard: bool):
    def core(state: TrainState, x, y, w):
        def loss_fn(params):
            logits = tp_forward(model, params, x)
            return criterion(logits, y, w)

        loss, grads = jax.value_and_grad(loss_fn)(state.params)
        n = jnp.sum(w)
        # THE data-parallel exchange: gradients (local-shard trees) reduce
        # over the DATA axis only — a model shard's gradient belongs to that
        # shard alone. Comm hooks bucket the local flat vector; each
        # (data, model) device carries its own EF residual slice.
        if comm is not None and comm.compressed:
            agg, new_comm = comm.reduce(grads, state.comm_state, DATA_AXIS)
        else:
            agg, new_comm = col.pmean(grads, DATA_AXIS), state.comm_state
        skipped = state.skipped_steps
        if guard:
            # model shards hold DIFFERENT gradient slices, so the local
            # finiteness verdicts can differ — one scalar pmin over "model"
            # makes every device take the same lax.cond branch (the data
            # axis already agrees: the psum propagated any replica's NaN)
            ok = (
                col.pmin(
                    guard_lib.tree_all_finite(agg).astype(jnp.int32),
                    MODEL_AXIS,
                )
                == 1
            )

            def _apply():
                new_p, new_o = optimizer.update(agg, state.opt_state, state.params)
                return new_p, new_o, new_comm, guard_lib.reset_consecutive(skipped)

            def _skip():
                return (
                    state.params, state.opt_state, state.comm_state,
                    guard_lib.bump_skip_counters(skipped),
                )

            new_params, new_opt_state, out_comm, new_skipped = jax.lax.cond(
                ok, _apply, _skip
            )
        else:
            new_params, new_opt_state = optimizer.update(
                agg, state.opt_state, state.params
            )
            out_comm, new_skipped = new_comm, skipped
        metrics = {"loss_sum": (loss * n)[None], "n": n[None]}
        new_state = TrainState(
            params=new_params,
            model_state=state.model_state,
            opt_state=new_opt_state,
            step=state.step + 1,
            rng=state.rng,
            comm_state=out_comm,
            skipped_steps=new_skipped,
        )
        return new_state, metrics

    return core


def _make_tp_eval_core(model, criterion):
    def core(state: TrainState, x, y, w):
        logits = tp_forward(model, state.params, x)
        loss = criterion(logits, y, w)
        n = jnp.sum(w)
        predicted = jnp.argmax(logits, axis=-1)
        correct = jnp.sum((predicted == y) * w)
        return {
            "loss_sum": (loss * n)[None],
            "correct": correct[None],
            "n": n[None],
        }

    return core


def build_tp_train_step(model, criterion, optimizer, mesh, state_spec,
                        comm=None, guard: bool = False):
    """Compile the TP train step over the 2-D mesh. Same calling contract as
    :func:`tpuddp.training.step.build_train_step`: ``step(state, (x, y, w))
    -> (new_state, metrics)`` with donated state; metrics are per-data-
    replica partial sums (identical across the model axis by construction)."""
    core = _make_tp_train_core(model, criterion, optimizer, comm, guard)
    metric_spec = {"loss_sum": P(DATA_AXIS), "n": P(DATA_AXIS)}
    fn = shard_map(
        core,
        mesh=mesh,
        in_specs=(state_spec, P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS)),
        out_specs=(state_spec, metric_spec),
        check_vma=False,
    )
    jitted = jax.jit(fn, donate_argnums=0)

    def step(state, batch):
        x, y, w = batch
        return jitted(state, x, y, w)

    return step


def build_tp_train_scan_step(model, criterion, optimizer, mesh, state_spec,
                             comm=None, guard: bool = False):
    """K fused TP train steps per dispatch (lax.scan over the single-step
    core, the ``train_step_many`` contract)."""
    core = _make_tp_train_core(model, criterion, optimizer, comm, guard)

    def multi(state: TrainState, xs, ys, ws):
        def body(st, batch):
            return core(st, *batch)

        state, stacked = jax.lax.scan(body, state, (xs, ys, ws))
        return state, jax.tree_util.tree_map(lambda a: jnp.sum(a, axis=0), stacked)

    in_batch = P(None, DATA_AXIS)
    metric_spec = {"loss_sum": P(DATA_AXIS), "n": P(DATA_AXIS)}
    fn = shard_map(
        multi,
        mesh=mesh,
        in_specs=(state_spec, in_batch, in_batch, in_batch),
        out_specs=(state_spec, metric_spec),
        check_vma=False,
    )
    jitted = jax.jit(fn, donate_argnums=0)

    def step(state, stacked_batch):
        xs, ys, ws = stacked_batch
        return jitted(state, xs, ys, ws)

    return step


def build_tp_eval_step(model, criterion, mesh, state_spec):
    core = _make_tp_eval_core(model, criterion)
    metric_spec = {
        "loss_sum": P(DATA_AXIS), "correct": P(DATA_AXIS), "n": P(DATA_AXIS),
    }
    fn = shard_map(
        core,
        mesh=mesh,
        in_specs=(state_spec, P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS)),
        out_specs=metric_spec,
        check_vma=False,
    )
    jitted = jax.jit(fn)

    def step(state, batch):
        x, y, w = batch
        return jitted(state, x, y, w)

    return step


def build_tp_eval_scan_step(model, criterion, mesh, state_spec):
    core = _make_tp_eval_core(model, criterion)

    def multi(state: TrainState, xs, ys, ws):
        def body(carry, batch):
            return carry, core(state, *batch)

        _, stacked = jax.lax.scan(body, 0, (xs, ys, ws))
        return jax.tree_util.tree_map(lambda a: jnp.sum(a, axis=0), stacked)

    in_batch = P(None, DATA_AXIS)
    metric_spec = {
        "loss_sum": P(DATA_AXIS), "correct": P(DATA_AXIS), "n": P(DATA_AXIS),
    }
    fn = shard_map(
        multi,
        mesh=mesh,
        in_specs=(state_spec, in_batch, in_batch, in_batch),
        out_specs=metric_spec,
        check_vma=False,
    )
    jitted = jax.jit(fn)

    def step(state, stacked_batch):
        xs, ys, ws = stacked_batch
        return jitted(state, xs, ys, ws)

    return step


def gather_params(state_or_params):
    """Host canonical-layout parameter tree from a TP state (or TP param
    tree): fetch the (fully addressable) global arrays and undo the QKV
    layout reshape — the reference view parity tests compare against."""
    params = getattr(state_or_params, "params", state_or_params)
    host = jax.tree_util.tree_map(np.asarray, params)
    return from_tp_tree(host)
