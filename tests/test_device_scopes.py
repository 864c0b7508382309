"""Device operations carry the program's own names (observability/
profiling.py: ``scope``, ``PHASE_SCOPES``): every step program's lowered
module holds, as ``op_name``s, the phase scope of each part of the step and
one layer path per parametrised layer; and the names are metadata only, the
program without them is byte for byte the same.

Lowered, never compiled or run: tracing is where the scopes act.
"""

import contextlib
import re

import jax
import jax.numpy as jnp
import pytest

from tpuddp import nn, optim
from tpuddp.data.transforms import make_eval_transform, make_train_augment
from tpuddp.models import load_model
from tpuddp.observability import profiling
from tpuddp.parallel import make_mesh
from tpuddp.parallel.ddp import DistributedDataParallel

MB = 1024 * 1024
WORLD = 2

# name -> (registry name, model kwargs, uint8 input shape, augment's resize)
MODELS = {
    "alexnet": ("alexnet", {}, (32, 32, 3), 64),
    "resnet18_small": ("resnet18_small", {}, (16, 16, 3), None),
    "resnet50": ("resnet50", {}, (32, 32, 3), None),
    "toy_mlp": ("toy_mlp", {"hidden": (16,)}, (8, 8, 3), None),  # no BatchNorm
}

# path -> (DDP kwargs, the method lowered, whether it takes a stacked batch)
_COMPRESSED = {"bucket_cap_mb": 600 * 4 / MB, "comm_hook": "bf16_ef"}  # several buckets
PATHS = {
    "train_step": ({"clip_grad_norm": 1.0, "guard": True}, "train_step", False),
    "train_step_many": ({}, "train_step_many", True),
    "accumulate": ({"grad_accumulation": 2, "guard": True}, "train_step_many", True),
    "compressed_hook": (_COMPRESSED, "train_step", False),
    "weight_update_sharded": (
        {"weight_update_sharding": True, "clip_grad_norm": 1.0}, "train_step", False,
    ),
    "eval_step": ({}, "eval_step", False),
}

# the phase scopes each path must carry (buffers: where the model has any)
_TRAIN = {profiling.AUGMENT, profiling.FORWARD, profiling.LOSS, profiling.EXCHANGE,
          profiling.OPTIMIZER, profiling.METRICS}
PHASES = {
    "train_step": _TRAIN | {profiling.CLIP, profiling.GUARD},
    "train_step_many": _TRAIN,
    "accumulate": _TRAIN | {profiling.GUARD},
    "compressed_hook": _TRAIN,
    "weight_update_sharded": _TRAIN | {profiling.CLIP},
    "eval_step": {profiling.AUGMENT, profiling.FORWARD, profiling.LOSS, profiling.METRICS},
}


def test_the_vocabulary_is_fixed():
    """The benchmark's reduction keeps its own copy of these names
    (benchmark/scope_reduce.py): a change here is a change of the yardstick,
    and of ``NAMES_VERSION``, which keeps a compile cache from serving
    programs under the older names (utils/compile_cache.py)."""
    assert profiling.NAMES_VERSION == "names-v1"
    assert profiling.SCOPE_PREFIX == "tpuddp."
    assert profiling.PHASE_SCOPES == (
        "tpuddp.augment", "tpuddp.forward", "tpuddp.loss", "tpuddp.buffers",
        "tpuddp.exchange", "tpuddp.clip", "tpuddp.guard", "tpuddp.optimizer",
        "tpuddp.metrics",
    )
    assert profiling.layer_scope(3, nn.Conv2d(8, 3)) == "3_Conv2d"
    for name in profiling.PHASE_SCOPES:
        assert re.fullmatch(r"[A-Za-z0-9_.]+", name)


def _lower(cpu_devices, model_name, path):
    """``(ddp, state, lowered)``: the path's step program, traced and
    lowered through the method a caller would dispatch (nothing compiles,
    nothing runs)."""
    registry, model_kwargs, shape, resize = MODELS[model_name]
    ddp_kwargs, method, stacked = PATHS[path]
    ddp = DistributedDataParallel(
        load_model(registry, 10, **model_kwargs), optim.Adam(1e-3),
        nn.CrossEntropyLoss(), mesh=make_mesh(cpu_devices[:WORLD]),
        augment=make_train_augment(size=resize),
        eval_transform=make_eval_transform(size=resize), **ddp_kwargs,
    )
    hw = resize or shape[0]
    state = ddp.init_state(jax.random.key(0), jnp.zeros((2, hw, hw, shape[2])))
    n = 2 * WORLD
    batch = (
        jnp.zeros((n, *shape), jnp.uint8), jnp.zeros((n,), jnp.int32),
        jnp.ones((n,), jnp.float32),
    )
    if stacked:
        batch = tuple(jnp.stack([a, a]) for a in batch)
    return ddp, state, jax.jit(getattr(ddp, method)).lower(state, batch)


def _parametrised_layer_paths(params):
    """``3_Conv2d`` for a layer of ``Sequential`` that owns parameters,
    ``4_Bottleneck/conv1`` ... for a residual block's children."""
    paths = []
    for i, p in enumerate(params):
        if not jax.tree_util.tree_leaves(p):
            continue
        nested = [k for k, v in p.items() if isinstance(v, dict)]
        paths.append((i, nested))
    return paths


@pytest.mark.parametrize("path", list(PATHS))
@pytest.mark.parametrize("model_name", list(MODELS))
def test_every_step_program_carries_its_scopes(cpu_devices, model_name, path):
    ddp, state, lowered = _lower(cpu_devices, model_name, path)
    text = lowered.as_text(debug_info=True)
    want = set(PHASES[path])
    if jax.tree_util.tree_leaves(state.model_state) and path != "eval_step":
        want.add(profiling.BUFFERS)
    for name in profiling.PHASE_SCOPES:
        assert (name in text) == (name in want), (name, sorted(want))

    training = path != "eval_step"
    forward = f"jvp({profiling.FORWARD})/" if training else f"{profiling.FORWARD}/"
    backward = f"transpose(jvp({profiling.FORWARD}))/"
    model = ddp.model
    for i, children in _parametrised_layer_paths(state.params):
        layer = profiling.layer_scope(i, model[i])
        for layer_path in [f"{layer}/{c}" for c in children] or [layer]:
            assert f'"{forward}{layer_path}/' in text, layer_path
            if training:
                assert f'"{backward}{layer_path}/' in text, layer_path
    if not training:
        assert "transpose(" not in text


@pytest.mark.parametrize("path", ["train_step", "train_step_many"])
@pytest.mark.parametrize("model_name", ["alexnet", "resnet18_small"])
def test_the_names_are_metadata_only(cpu_devices, monkeypatch, model_name, path):
    """With the one helper patched to a null context the lowered program,
    debug info stripped, is byte-equal to the scoped one: same instructions,
    same order, same constants."""
    scoped = _lower(cpu_devices, model_name, path)[2]
    assert profiling.SCOPE_PREFIX in scoped.as_text(debug_info=True)
    monkeypatch.setattr(profiling, "scope", lambda name: contextlib.nullcontext())
    bare = _lower(cpu_devices, model_name, path)[2]
    assert profiling.SCOPE_PREFIX not in bare.as_text(debug_info=True)
    assert scoped.as_text() == bare.as_text()


def test_remat_names_the_recompute(cpu_devices):
    """Under ``remat`` the backward's recomputed forward reads
    ``checkpoint/rematted_computation/<layer>``: what the benchmark's
    reduction counts as recompute."""
    ddp = DistributedDataParallel(
        load_model("toy_mlp", 10, hidden=(16,)), optim.Adam(1e-3),
        nn.CrossEntropyLoss(), mesh=make_mesh(cpu_devices[:WORLD]), remat=True,
    )
    state = ddp.init_state(jax.random.key(0), jnp.zeros((2, 8, 8, 3)))
    batch = (jnp.zeros((4, 8, 8, 3)), jnp.zeros((4,), jnp.int32), jnp.ones((4,)))
    text = jax.jit(ddp.train_step).lower(state, batch).as_text(debug_info=True)
    assert (
        f"transpose(jvp({profiling.FORWARD}))/jvp({profiling.FORWARD})"
        "/checkpoint/rematted_computation/1_Linear/" in text
    )
