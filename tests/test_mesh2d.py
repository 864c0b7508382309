"""2-D ``("data", "model")`` mesh + tensor parallelism (ISSUE 14).

Covers the tentpole contracts:

- spec application over EVERY ``param_logical_axes`` entry (the rule-table
  matrix);
- TP=2 forward/backward against a single-device reference (params gathered,
  logits compared — the row-split contractions change only each matmul's
  summation order, so the comparison is tight-tolerance; the vocab-split
  embedding lookup and logit gather are exact by construction);
- ``model=1`` lowering to HLO byte-identical with today's flat DDP path;
- comm-hook byte accounting on the data axis only, with the error-feedback
  residual keyed by ``(data_index, model_index)``;
- guard: no false positive on TP shards (they legitimately differ across
  the model axis), a genuine data-axis divergence still convicts, and the
  non-finite firewall skip stays a bitwise no-op;
- checkpoint round trip at TP=2 + the typed cross-``model``-width refusal
  (including the v2-record regression: a pre-v3 file written on a 2-D mesh
  must refuse, not mis-slice);
- the config surface: ``parallel`` block unknown-key refusal, ``mesh_from``
  tiling/hierarchical refusals.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from tpuddp import config as cfg_lib
from tpuddp import nn, optim
from tpuddp.models import load_model
from tpuddp.models import transformer as tf_lib
from tpuddp.nn.core import Context
from tpuddp.parallel import comm as comm_lib
from tpuddp.parallel import tensor as tp_lib
from tpuddp.parallel.ddp import DistributedDataParallel
from tpuddp.parallel.mesh import DATA_AXIS, data_mesh
from tpuddp.parallel.mesh2d import (
    AXIS_ROLES,
    MODEL_AXIS,
    data_size,
    describe,
    mesh2d,
    model_size,
    squeeze_model,
)
from tpuddp.resilience import guard as guard_lib
from tpuddp.training import checkpoint as ckpt

KEY = jax.random.PRNGKey(0)
V, T, B = 64, 16, 8


def make_tp(devices, data=2, model=2, **kw):
    m = load_model("transformer_tiny", num_classes=V, max_seq_len=32)
    ddp = DistributedDataParallel(
        m, optim.Adam(lr=1e-2), nn.CrossEntropyLoss(),
        mesh=mesh2d(data, model, devices=devices[: data * model]), **kw,
    )
    return ddp, m


def token_batch(seed=0, b=B):
    rng = np.random.default_rng(seed)
    return (
        rng.integers(0, V, (b, T)).astype(np.int32),
        rng.integers(0, V, (b, T)).astype(np.int32),
        np.ones((b, T), np.float32),
    )


def leaves_equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb)
    )


# ------------------------------------------------------------- mesh factory --


def test_mesh2d_axes_and_order(cpu_devices):
    mesh = mesh2d(2, 2, devices=cpu_devices[:4])
    assert mesh.axis_names == (DATA_AXIS, MODEL_AXIS)
    assert mesh.devices.shape == (2, 2)
    # model minor: one TP group = adjacent devices
    assert list(mesh.devices[0]) == list(cpu_devices[:2])
    assert model_size(mesh) == 2 and data_size(mesh) == 2
    assert describe(mesh) == {"data": 2, "model": 2}


def test_mesh2d_device_count_must_tile(cpu_devices):
    with pytest.raises(ValueError, match="exactly"):
        mesh2d(3, 2, devices=cpu_devices[:4])


def test_axis_registry_closed():
    assert set(AXIS_ROLES) == {"data", "model", "host", "local"}
    from tpuddp.parallel.mesh2d import validate_axis

    with pytest.raises(ValueError, match="unknown mesh axis"):
        validate_axis("pipeline")


def test_squeeze_model(cpu_devices):
    m1 = mesh2d(4, 1, devices=cpu_devices[:4])
    flat = squeeze_model(m1)
    assert flat.axis_names == (DATA_AXIS,)
    assert list(flat.devices.flat) == list(m1.devices.flat)
    with pytest.raises(ValueError, match="cannot squeeze"):
        squeeze_model(mesh2d(2, 2, devices=cpu_devices[:4]))
    # a mesh without the model axis passes through untouched
    dm = data_mesh(4)
    assert squeeze_model(dm) is dm


def test_model_size_of_1d_meshes(cpu_devices):
    assert model_size(data_mesh(4)) == 1
    assert model_size(None) == 1
    assert describe(None) is None


# ------------------------------------------------------------ config surface --


def test_parallel_block_unknown_key_refused():
    with pytest.raises(ValueError, match="unknown parallel key"):
        cfg_lib.resolve_parallel({"data": 2, "modle": 2})
    assert cfg_lib.resolve_parallel(None) == {"data": "auto", "model": 1}
    assert cfg_lib.parallel_config({"parallel": {"model": 2}})["model"] == 2


def test_mesh_from_refuses_bad_tiling(cpu_devices):
    with pytest.raises(ValueError, match="!= device count|does not tile"):
        cfg_lib.mesh_from({"data": 3, "model": 2}, world_size=4)
    with pytest.raises(ValueError, match="does not tile"):
        cfg_lib.mesh_from({"model": 3}, world_size=4)


def test_mesh_from_refuses_hierarchical_model_parallel():
    with pytest.raises(ValueError, match="hierarchical"):
        cfg_lib.mesh_from(
            {"model": 2}, world_size=4, comm_topology="hierarchical"
        )


def test_mesh_from_model1_is_flat_mesh(cpu_devices):
    mesh = cfg_lib.mesh_from(None, world_size=4)
    assert mesh.axis_names == (DATA_AXIS,)
    mesh2 = cfg_lib.mesh_from({"data": 2, "model": 2}, world_size=4)
    assert mesh2.axis_names == (DATA_AXIS, MODEL_AXIS)


# ------------------------------------------------- spec application matrix --


def test_spec_matrix_covers_every_logical_axes_entry():
    """Every ``param_logical_axes`` entry maps through the TP rule table to
    the expected mesh-axis spec — column-split QKV/mlp-in, row-split
    attn-out/mlp-out, vocab-split embedding, everything else replicated."""
    model = load_model("transformer_tiny", num_classes=V, max_seq_len=32)
    params, _ = model.init(KEY, jnp.zeros((1, T), jnp.int32))
    tp_params = tp_lib.to_tp_tree(params)
    specs = tp_lib.tp_param_specs(model, tp_params)
    expected_block = {
        "ln1": {"scale": P(None), "bias": P(None)},
        "attn": {
            "wqkv": P(None, None, MODEL_AXIS),  # (E, 3, H*Dh) head split
            "bqkv": P(None, MODEL_AXIS),
            "wo": P(MODEL_AXIS, None),          # row split by heads
            "bo": P(None),
        },
        "ln2": {"scale": P(None), "bias": P(None)},
        "mlp": {
            "w1": P(None, MODEL_AXIS),          # column split (mlp)
            "b1": P(MODEL_AXIS),
            "w2": P(MODEL_AXIS, None),          # row split (mlp)
            "b2": P(None),
        },
    }
    assert specs["embed"]["weight"] == P(MODEL_AXIS, None)  # vocab split
    assert specs["pos"]["weight"] == P(None, None)
    assert specs["ln_f"] == {"scale": P(None), "bias": P(None)}
    for blk in specs["blocks"]:
        assert blk == expected_block
    # the matrix covers EVERY logical-axes entry: same leaf count
    axes = tf_lib.param_logical_axes(model, params)
    n_axes = len(jax.tree_util.tree_leaves(
        axes,
        is_leaf=lambda l: isinstance(l, tuple) and bool(l)
        and all(isinstance(n, str) for n in l),
    ))
    assert n_axes == len(jax.tree_util.tree_leaves(specs))


def test_tp_rules_extend_snippet_table_with_vocab():
    rules = tp_lib.tp_rules()
    base = tf_lib.PARTITION_RULES
    assert base["vocab"] is None and rules["vocab"] == MODEL_AXIS
    for k in ("heads", "mlp", "joined_kv"):
        assert rules[k] == base[k] == MODEL_AXIS
    assert len(tp_lib.tp_rules_hash()) == 16
    assert tp_lib.tp_rules_hash() != tp_lib.tp_rules_hash(base)


def test_qkv_layout_roundtrip():
    model = load_model("transformer_tiny", num_classes=V, max_seq_len=32)
    params, _ = model.init(KEY, jnp.zeros((1, T), jnp.int32))
    back = tp_lib.from_tp_tree(tp_lib.to_tp_tree(params))
    assert leaves_equal(params, back)


def test_geometry_refusals(cpu_devices):
    with pytest.raises(ValueError, match="n_heads"):
        tp_lib.validate_tp_geometry(
            load_model("transformer_tiny", num_classes=V), 3
        )
    with pytest.raises(ValueError, match="partition metadata"):
        tp_lib.validate_tp_geometry(load_model("toy_mlp"), 2)


# ------------------------------------------------ forward/backward parity --


def test_tp2_forward_matches_single_device_reference(cpu_devices):
    """TP=2 logits vs the unsharded ``model.apply`` on the gathered params:
    the column-split attention and the vocab-split head/lookup are exact;
    the two row-split projections psum M partials, changing only the
    contraction's summation order — asserted tight."""
    from jax import shard_map

    ddp, model = make_tp(cpu_devices)
    st = ddp.init_state(KEY, jnp.zeros((1, T), jnp.int32))
    x, _, _ = token_batch()
    ref_params = tp_lib.gather_params(st)
    ref_logits, _ = model.apply(ref_params, (), x, Context(train=False))
    fn = shard_map(
        lambda p, t: tp_lib.tp_forward(model, p, t),
        mesh=ddp.mesh,
        in_specs=(ddp.tp_param_specs, P(DATA_AXIS)),
        out_specs=P(DATA_AXIS),
        check_vma=False,
    )
    tp_logits = jax.jit(fn)(st.params, ddp.shard((x,))[0])
    np.testing.assert_allclose(
        np.asarray(tp_logits), np.asarray(ref_logits), rtol=0, atol=2e-5
    )


def test_tp2_backward_matches_single_device_reference(cpu_devices):
    """One Adam step at TP=2xDP=2 lands the same parameters as one
    full-batch step on a single unsharded copy (the DP pmean over the data
    axis + the TP psums reproduce the full-batch gradient)."""
    ddp, model = make_tp(cpu_devices)
    st = ddp.init_state(KEY, jnp.zeros((1, T), jnp.int32))
    x, y, w = token_batch()
    ref_params = jax.tree_util.tree_map(jnp.asarray, tp_lib.gather_params(st))
    crit = nn.CrossEntropyLoss()

    def ref_loss(p):
        logits, _ = model.apply(p, (), x, Context(train=True))
        return crit(logits, y, w)

    ref_grads = jax.grad(ref_loss)(ref_params)
    opt = optim.Adam(lr=1e-2)
    ref_new, _ = opt.update(ref_grads, opt.init(ref_params), ref_params)

    st2, _ = ddp.train_step(st, ddp.shard((x, y, w)))
    tp_new = tp_lib.gather_params(st2)
    for (pa, a), (_, b) in zip(
        jax.tree_util.tree_flatten_with_path(ref_new)[0],
        jax.tree_util.tree_flatten_with_path(tp_new)[0],
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=0, atol=5e-4,
            err_msg=jax.tree_util.keystr(pa),
        )


def test_tp2xdp2_loss_trajectory_matches_dp4(cpu_devices):
    """Matched global batch: TP=2xDP=2 and pure DP=4 track the same loss
    trajectory step for step (float-reduction tolerance)."""
    tp, _ = make_tp(cpu_devices)
    dp, _ = make_tp(cpu_devices, data=4, model=1)
    st_tp = tp.init_state(KEY, jnp.zeros((1, T), jnp.int32))
    st_dp = dp.init_state(KEY, jnp.zeros((1, T), jnp.int32))
    for i in range(6):
        x, y, w = token_batch(seed=10 + i)
        st_tp, m_tp = tp.train_step(st_tp, tp.shard((x, y, w)))
        st_dp, m_dp = dp.train_step(st_dp, dp.shard((x, y, w)))
        l_tp = float(np.asarray(m_tp["loss_sum"]).sum() / np.asarray(m_tp["n"]).sum())
        l_dp = float(np.asarray(m_dp["loss_sum"]).sum() / np.asarray(m_dp["n"]).sum())
        assert abs(l_tp - l_dp) < 1e-4, (i, l_tp, l_dp)


def test_tp_scan_step_matches_repeated_single_steps(cpu_devices):
    ddp, _ = make_tp(cpu_devices)
    st_a = ddp.init_state(KEY, jnp.zeros((1, T), jnp.int32))
    st_b = ddp.init_state(KEY, jnp.zeros((1, T), jnp.int32))
    b0, b1 = token_batch(seed=3), token_batch(seed=4)
    for b in (b0, b1):
        st_a, _ = ddp.train_step(st_a, ddp.shard(b))
    stacked = tuple(np.stack([p, q]) for p, q in zip(b0, b1))
    st_b, _ = ddp.train_step_many(st_b, ddp.shard_stacked(stacked))
    assert leaves_equal(st_a.params, st_b.params)


def test_tp_eval_step_counts_tokens(cpu_devices):
    ddp, _ = make_tp(cpu_devices)
    st = ddp.init_state(KEY, jnp.zeros((1, T), jnp.int32))
    m = ddp.eval_step(st, ddp.shard(token_batch()))
    assert float(np.asarray(m["n"]).sum()) == B * T
    assert np.isfinite(np.asarray(m["loss_sum"])).all()


# ------------------------------------------------------ model=1 HLO identity --


def test_model1_hlo_identity_with_flat_ddp(cpu_devices):
    """``mesh2d(4, 1)`` routes through the EXISTING DDP path unchanged: the
    lowered train-step HLO is byte-identical to a flat ``data_mesh(4)``
    wrap's."""
    m1, _ = make_tp(cpu_devices, data=4, model=1)
    assert m1.mesh.axis_names == (DATA_AXIS,)  # squeezed to the flat mesh
    flat2 = DistributedDataParallel(
        load_model("transformer_tiny", num_classes=V, max_seq_len=32),
        optim.Adam(lr=1e-2), nn.CrossEntropyLoss(), mesh=data_mesh(4),
    )

    def lowered(d):
        st = d.init_state(KEY, jnp.zeros((1, T), jnp.int32))
        struct = jax.tree_util.tree_map(
            lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), st
        )
        b = (
            jax.ShapeDtypeStruct((B, T), jnp.int32),
            jax.ShapeDtypeStruct((B, T), jnp.int32),
            jax.ShapeDtypeStruct((B, T), jnp.float32),
        )
        return jax.jit(lambda s, bb: d.train_step(s, bb)).lower(struct, b).as_text()

    assert lowered(m1) == lowered(flat2)


# ------------------------------------------------------- comm-hook composition --


def test_comm_bytes_account_data_axis_only(cpu_devices):
    """The wire counter reports the LOCAL shard payload exchanged across
    data replicas: TP=2 halves the flat gradient vector, so bf16_ef bytes
    are half the model=1 bf16_ef bytes of the same model, and the bf16 cut
    vs the TP run's own f32 baseline stays exactly 50%."""
    tp, model = make_tp(cpu_devices, comm_hook="bf16_ef")
    st = tp.init_state(KEY, jnp.zeros((1, T), jnp.int32))
    assert tp.grad_comm_bytes_per_step == tp.grad_comm_bytes_per_step_f32 // 2
    # the local template is the sharded tree: its padded flat length is the
    # comm plan's residual length
    tp_params = jax.tree_util.tree_map(np.asarray, st.params)
    local = tp_lib.local_param_template(tp_params, tp.tp_param_specs, 2)
    expect = comm_lib.comm_bytes_for_hook(local, 2, "bf16_ef")
    assert tp.grad_comm_bytes_per_step == expect
    assert tp._grad_comm_breakdown["intra_host"] == 0


def test_ef_residual_keyed_by_data_model_index(cpu_devices):
    """The error-feedback residual lays out one slice per
    ``(data_index, model_index)`` device — P(("data", "model")) over the
    flat vector — and becomes non-zero once compression error accrues."""
    tp, _ = make_tp(cpu_devices, comm_hook="bf16_ef")
    st = tp.init_state(KEY, jnp.zeros((1, T), jnp.int32))
    assert st.comm_state.shape == (tp._comm.spec.total * 4,)
    assert st.comm_state.sharding.spec == P((DATA_AXIS, MODEL_AXIS))
    assert len(st.comm_state.addressable_shards) == 4
    st, _ = tp.train_step(st, tp.shard(token_batch()))
    st, _ = tp.train_step(st, tp.shard(token_batch(seed=1)))
    res = np.asarray(st.comm_state)
    assert np.abs(res).max() > 0


def test_tp_bf16ef_tracks_uncompressed_trajectory(cpu_devices):
    base, _ = make_tp(cpu_devices)
    comp, _ = make_tp(cpu_devices, comm_hook="bf16_ef")
    st_b = base.init_state(KEY, jnp.zeros((1, T), jnp.int32))
    st_c = comp.init_state(KEY, jnp.zeros((1, T), jnp.int32))
    for i in range(4):
        b = token_batch(seed=20 + i)
        st_b, m_b = base.train_step(st_b, base.shard(b))
        st_c, m_c = comp.train_step(st_c, comp.shard(b))
    l_b = float(np.asarray(m_b["loss_sum"]).sum() / np.asarray(m_b["n"]).sum())
    l_c = float(np.asarray(m_c["loss_sum"]).sum() / np.asarray(m_c["n"]).sum())
    assert abs(l_b - l_c) <= comm_lib.loss_parity_tol("bf16_ef", l_b)


# --------------------------------------------------------------- guard --


def _perturb_data_replica(ddp, params, leaf_index, device_index):
    """Return params with ONE device's copy of leaf ``leaf_index`` bumped —
    a data-axis divergence the auditor must convict."""
    flat, treedef = jax.tree_util.tree_flatten(params)
    specs = jax.tree_util.tree_leaves(ddp.tp_param_specs)
    leaf, spec = flat[leaf_index], specs[leaf_index]
    pieces = []
    for d_idx, dev in enumerate(ddp.mesh.devices.flat):
        arr = np.asarray(
            [s for s in leaf.addressable_shards if s.device == dev][0].data
        ).copy()
        if d_idx == device_index:
            arr = arr + 1.0
        pieces.append(jax.device_put(arr, dev))
    bad = jax.make_array_from_single_device_arrays(
        leaf.shape, NamedSharding(ddp.mesh, spec), pieces
    )
    return jax.tree_util.tree_unflatten(
        treedef, flat[:leaf_index] + [bad] + flat[leaf_index + 1:]
    )


def test_guard_no_false_positive_on_tp_shards(cpu_devices):
    """A TP state's shards differ across the model axis BY DESIGN; the
    auditor (fingerprint within a model-shard group, compare across data
    replicas) must not convict them — at wrap time or on explicit audit."""
    ddp, _ = make_tp(cpu_devices, guard=True)
    st = ddp.init_state(KEY, jnp.zeros((1, T), jnp.int32))  # audits at wrap
    assert guard_lib.audit_params(
        ddp.mesh, st.params, specs=ddp.tp_param_specs
    ) is None


def test_guard_convicts_data_axis_divergence(cpu_devices):
    ddp, _ = make_tp(cpu_devices)
    st = ddp.init_state(KEY, jnp.zeros((1, T), jnp.int32))
    paths = [
        jax.tree_util.keystr(p)
        for p, _ in jax.tree_util.tree_flatten_with_path(st.params)[0]
    ]
    specs = jax.tree_util.tree_leaves(ddp.tp_param_specs)
    # one replicated leaf and one model-SHARDED leaf: both must convict
    # when a data replica's copy diverges (device 2 = (data=1, model=0))
    sharded_i = next(i for i, s in enumerate(specs) if MODEL_AXIS in str(s))
    replicated_i = next(i for i, s in enumerate(specs) if s == P(None))
    for i in (replicated_i, sharded_i):
        bad = _perturb_data_replica(ddp, st.params, i, device_index=2)
        assert guard_lib.audit_params(
            ddp.mesh, bad, specs=ddp.tp_param_specs
        ) == paths[i]


def test_guard_firewall_skip_is_bitwise_noop_on_tp(cpu_devices):
    ddp, _ = make_tp(cpu_devices, guard=True)
    st = ddp.init_state(KEY, jnp.zeros((1, T), jnp.int32))
    before = [np.asarray(l).copy() for l in jax.tree_util.tree_leaves(st.params)]
    x, y, w = token_batch()
    w = w.copy()
    w[0, 0] = np.nan  # poisons the loss -> non-finite gradient everywhere
    st2, _ = ddp.train_step(st, ddp.shard((x, y, w)))
    assert int(np.asarray(st2.skipped_steps["total"])) == 1
    assert all(
        np.array_equal(a, np.asarray(b))
        for a, b in zip(before, jax.tree_util.tree_leaves(st2.params))
    )
    # a clean batch afterwards applies and resets the consecutive counter
    st3, _ = ddp.train_step(st2, ddp.shard(token_batch(seed=9)))
    assert int(np.asarray(st3.skipped_steps["consecutive"])) == 0
    assert not leaves_equal(
        jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(st3.params), before
        ),
        st3.params,
    )


# ----------------------------------------------------------- checkpointing --


def test_checkpoint_roundtrip_tp2(cpu_devices, tmp_path):
    tp, _ = make_tp(cpu_devices, comm_hook="bf16_ef")
    st = tp.init_state(KEY, jnp.zeros((1, T), jnp.int32))
    st, _ = tp.train_step(st, tp.shard(token_batch()))
    host = [np.asarray(l).copy() for l in jax.tree_util.tree_leaves(st)]
    ckpt.save_on_main(str(tmp_path), 0, st, world_size=4)
    topo = ckpt.read_topology(str(tmp_path / "ckpt_0.npz"))
    assert topo["format"] == ckpt.FORMAT_VERSION
    assert topo["model_size"] == 2
    assert ckpt.topology_model_size(topo) == 2
    # v3 placement tags: every model-sharded leaf names its mesh axes
    # (trailing replicated dims may be elided from the recorded spec)
    assert topo["placement"][".params['embed']['weight']"][0] == "model"
    assert topo["leaves"][".comm_state"]["model"] == 2
    restored, nxt = ckpt.restore_latest(
        str(tmp_path), st, world_size=4, model_size=2
    )
    assert nxt == 1
    assert all(
        np.array_equal(a, np.asarray(b))
        for a, b in zip(host, jax.tree_util.tree_leaves(restored))
    )


def test_checkpoint_cross_model_width_refused_typed(cpu_devices, tmp_path):
    tp, _ = make_tp(cpu_devices)
    st = tp.init_state(KEY, jnp.zeros((1, T), jnp.int32))
    ckpt.save_on_main(str(tmp_path), 0, st, world_size=4)
    for width in (1, 4, None):
        with pytest.raises(ckpt.TopologyMismatch, match="model"):
            ckpt.restore_latest(
                str(tmp_path), st, world_size=4, model_size=width
            )


def test_v2_record_on_2d_mesh_refuses_not_misslices(cpu_devices, tmp_path):
    """The elastic-resume hardening satellite: a format-v2 topology record
    (no explicit model_size) written on a 2-D mesh still names its mesh
    axes — loading it under a DIFFERENT model width must raise the typed
    refusal, never re-pad/mis-slice the flat leaves."""
    tp, _ = make_tp(cpu_devices, comm_hook="bf16_ef")
    st = tp.init_state(KEY, jnp.zeros((1, T), jnp.int32))
    ckpt.save_on_main(str(tmp_path), 0, st, world_size=4)
    topo = ckpt.read_topology(str(tmp_path / "ckpt_0.npz"))
    # strip the v3 fields -> exactly what a v2 writer on this mesh recorded
    v2 = {k: v for k, v in topo.items() if k not in ("model_size", "placement")}
    v2["format"] = 2
    # the v2 per-replica tag had no model field either
    v2["leaves"] = {
        k: {kk: vv for kk, vv in info.items() if kk != "model"}
        for k, info in topo["leaves"].items()
    }
    assert ckpt.topology_model_size(v2) == 2  # derived from mesh_axes
    host = jax.tree_util.tree_map(np.asarray, st)
    path = str(tmp_path / "ckpt_7.npz")
    ckpt.save(path, host, meta={"epoch": 7, "completed": 1}, topology=v2)
    with pytest.raises(ckpt.TopologyMismatch, match="model=2"):
        ckpt.load(path, st, world_size=4, model_size=1)
    # same width still loads
    assert ckpt.load(path, st, world_size=4, model_size=2) is not None


def test_dp_checkpoint_refused_on_tp_mesh(cpu_devices, tmp_path):
    """A pure-DP (model=1) checkpoint restored onto a TP run refuses typed
    — and a v1 file (no topology record at all) refuses too."""
    dp, _ = make_tp(cpu_devices, data=4, model=1)
    st = dp.init_state(KEY, jnp.zeros((1, T), jnp.int32))
    ckpt.save_on_main(str(tmp_path), 0, st, world_size=4)
    with pytest.raises(ckpt.TopologyMismatch, match="model"):
        ckpt.load(
            str(tmp_path / "ckpt_0.npz"), st, world_size=4, model_size=2
        )
    # v1: no topology record
    host = jax.tree_util.tree_map(np.asarray, st)
    v1 = str(tmp_path / "ckpt_3.npz")
    ckpt.save(v1, host, meta={"epoch": 3, "completed": 1}, topology=None)
    with pytest.raises(ckpt.TopologyMismatch, match="format v1"):
        ckpt.load(v1, st, world_size=4, model_size=2)


def test_tp_residual_data_resharding_requires_opt_in(cpu_devices, tmp_path):
    """Changing the DATA width under TP with an EF residual armed refuses by
    default — the (data, model)-keyed slices need the per-model-column
    redistribution in tpuddp.training.reshard, and the refusal names BOTH
    opt-in spellings (reshard_on_mismatch, the offline tool) so the operator
    is pointed at the fix, not just the wall (ISSUE 16 satellite)."""
    tp, _ = make_tp(cpu_devices, comm_hook="bf16_ef")
    st = tp.init_state(KEY, jnp.zeros((1, T), jnp.int32))
    ckpt.save_on_main(str(tmp_path), 0, st, world_size=4)
    # a template whose residual is half as long (data=1 x model=2)
    import dataclasses

    smaller = dataclasses.replace(
        st, comm_state=jnp.zeros((st.comm_state.shape[0] // 2,), jnp.float32)
    )
    with pytest.raises(
        ckpt.TopologyMismatch, match="reshard_on_mismatch"
    ) as err:
        ckpt.load(
            str(tmp_path / "ckpt_0.npz"), smaller, world_size=2, model_size=2
        )
    assert "tpuddp_inspect reshard" in str(err.value)


# ----------------------------------------------------------- wrap refusals --


def test_tp_wrap_refusal_surface(cpu_devices):
    model = load_model("transformer_tiny", num_classes=V, max_seq_len=32)
    mesh = mesh2d(2, 2, devices=cpu_devices[:4])

    def build(**kw):
        kwargs = dict(mesh=mesh)
        kwargs.update(kw)
        return DistributedDataParallel(
            model, optim.Adam(lr=1e-2), nn.CrossEntropyLoss(), **kwargs
        )

    with pytest.raises(ValueError, match="shard_map"):
        build(mode="auto")
    with pytest.raises(ValueError, match="weight_update_sharding"):
        build(weight_update_sharding=True)
    with pytest.raises(ValueError, match="hierarchical"):
        build(comm_topology="hierarchical")
    with pytest.raises(ValueError, match="grad_accumulation"):
        build(grad_accumulation=2)
    with pytest.raises(ValueError, match="clip_grad_norm"):
        build(clip_grad_norm=1.0)
    with pytest.raises(ValueError, match="LARS/LAMB"):
        DistributedDataParallel(
            model, optim.LAMB(1e-3), nn.CrossEntropyLoss(), mesh=mesh
        )
    with pytest.raises(ValueError, match="partition metadata"):
        DistributedDataParallel(
            load_model("toy_mlp"), optim.Adam(lr=1e-2),
            nn.CrossEntropyLoss(), mesh=mesh,
        )
    with pytest.raises(ValueError, match="n_heads"):
        # transformer_tiny has 4 heads: a model axis of 8 cannot tile it
        DistributedDataParallel(
            load_model("transformer_tiny", num_classes=V),
            optim.Adam(lr=1e-2), nn.CrossEntropyLoss(),
            mesh=mesh2d(1, 8, devices=cpu_devices[:8]),
        )


# --------------------------------------------------------------- data path --


def test_sharded_loader_samples_per_data_group(cpu_devices):
    """On a 2-D mesh the loader builds one sampler per DATA index: the
    global batch is data_size x batch rows, and placement replicates each
    row group across the model axis."""
    from tpuddp.data.loader import ShardedDataLoader

    class Toy:
        def __len__(self):
            return 64

        def __getitem__(self, i):
            return np.full((4,), i, np.float32), i % 10

    mesh = mesh2d(2, 2, devices=cpu_devices[:4])
    loader = ShardedDataLoader(Toy(), 4, mesh, shuffle=False)
    assert loader.world_size == 2  # data groups, not devices
    x, y, w = next(iter(loader))
    assert x.shape == (8, 4)  # 2 data groups x batch 4
    from tpuddp.parallel.mesh import shard_batch

    placed = shard_batch(mesh, x)
    assert placed.sharding.spec == P(DATA_AXIS, None)
    # model-axis neighbors hold the SAME rows
    shards = {s.device: np.asarray(s.data) for s in placed.addressable_shards}
    d = mesh.devices
    np.testing.assert_array_equal(shards[d[0, 0]], shards[d[0, 1]])
    np.testing.assert_array_equal(shards[d[1, 0]], shards[d[1, 1]])
    assert not np.array_equal(shards[d[0, 0]], shards[d[1, 0]])


# ------------------------------------------------------------ run_meta block --


def test_run_meta_mesh_block_v8():
    from tpuddp.observability import schema

    assert schema.SCHEMA_VERSION >= 8  # the mesh block is required since v8
    meta = schema.make_run_meta(
        mesh=mesh2d(2, 2, devices=jax.devices("cpu")[:4]),
        comm_hook="none", tp_rules_hash="abc123",
    )
    assert meta["mesh"] == {"data": 2, "model": 2, "tp_rules_hash": "abc123"}
    assert not schema.validate_record(meta)
    # a v8 header MISSING the mesh key is drift
    bad = {k: v for k, v in meta.items() if k != "mesh"}
    errors = schema.validate_record(bad)
    assert any("mesh" in e for e in errors)
    # older versions validate at their own version
    old = dict(bad)
    old["schema_version"] = 7
    old["survivability"] = None
    assert not schema.validate_record(old)
    # no-mesh writers carry the key as null
    serving_meta = schema.make_run_meta(world_size=2, comm_hook=None)
    assert serving_meta["mesh"] is None
    assert not schema.validate_record(serving_meta)
