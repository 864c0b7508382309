"""Model zoo structure checks — param counts must equal the reference stack's
torchvision architectures (same topology, NHWC layout)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpuddp import nn
from tpuddp.models import AlexNet, ResNet18, ToyCNN, ToyMLP, load_model
from tpuddp.models.alexnet import replace_head
from tpuddp.nn.core import Context

KEY = jax.random.key(0)


def n_params(tree):
    return sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(tree))


def test_registry():
    assert isinstance(load_model("toy_mlp"), nn.Sequential)
    with pytest.raises(ValueError):
        load_model("vgg")


def test_toy_models_forward():
    x = jnp.zeros((2, 32, 32, 3))
    for model in (ToyMLP(), ToyCNN()):
        params, state = model.init(KEY, x)
        y, _ = model.apply(params, state, x, Context())
        assert y.shape == (2, 10)


# torchvision isn't in this image, so the oracles are the published
# architecture parameter counts: AlexNet(1000) = 61,100,840 and
# ResNet-18(1000) = 11,689,512, adjusted for the 10-way head swap the
# reference performs (data_and_toy_model.py:43-44).
ALEXNET_10_PARAMS = 61_100_840 - (4096 * 1000 + 1000) + (4096 * 10 + 10)
RESNET18_10_PARAMS = 11_689_512 - (512 * 1000 + 1000) + (512 * 10 + 10)


@pytest.mark.slow
def test_alexnet_matches_torchvision_param_count():
    """Same topology as the reference's load_model() output
    (data_and_toy_model.py:41-45): torchvision AlexNet with a 10-way head."""
    model = AlexNet(num_classes=10)
    params, state = model.init(KEY, jnp.zeros((1, 224, 224, 3)))
    assert n_params(params) == ALEXNET_10_PARAMS

    y, _ = model.apply(params, state, jnp.zeros((2, 224, 224, 3)), Context())
    assert y.shape == (2, 10)


@pytest.mark.slow
def test_resnet18_matches_torchvision_param_count():
    # BN running stats are buffers (model_state), not params — like torch.
    model = ResNet18(num_classes=10)
    params, state = model.init(KEY, jnp.zeros((1, 64, 64, 3)))
    assert n_params(params) == RESNET18_10_PARAMS

    y, _ = model.apply(params, state, jnp.zeros((2, 64, 64, 3)), Context())
    assert y.shape == (2, 10)


def test_resnet18_small_input_stem():
    model = ResNet18(num_classes=10, small_input=True)
    params, state = model.init(KEY, jnp.zeros((1, 32, 32, 3)))
    y, new_state = model.apply(
        params, state, jnp.ones((2, 32, 32, 3)), Context(train=True)
    )
    assert y.shape == (2, 10)
    # BN buffers update in train mode somewhere in the tree
    leaves_before = jax.tree_util.tree_leaves(state)
    leaves_after = jax.tree_util.tree_leaves(new_state)
    assert any(
        not np.allclose(np.asarray(a), np.asarray(b))
        for a, b in zip(leaves_before, leaves_after)
    )


def test_resnet_sync_bn_conversion():
    model = ResNet18(num_classes=10)
    nn.convert_sync_batchnorm(model)
    # stem BN + every block's BNs flipped
    assert model[1].sync is True
    block = model[4]
    assert block.bn1.sync and block.bn2.sync and block.down_bn.sync


def test_alexnet_replace_head():
    model = AlexNet(num_classes=10)
    params, state = model.init(KEY, jnp.zeros((1, 63, 63, 3)))
    params = list(params)
    new_params = replace_head(model, params, jax.random.key(1), num_classes=7)
    y, _ = model.apply(new_params, state, jnp.zeros((1, 63, 63, 3)), Context())
    assert y.shape == (1, 7)


def test_alexnet_dropout_only_in_train():
    model = AlexNet(num_classes=10, dropout=0.9)
    params, state = model.init(KEY, jnp.zeros((1, 63, 63, 3)))
    x = jnp.ones((1, 63, 63, 3))
    y1, _ = model.apply(params, state, x, Context(train=False))
    y2, _ = model.apply(params, state, x, Context(train=False))
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))  # deterministic eval
    t1, _ = model.apply(params, state, x, Context(train=True, rng=jax.random.key(1)))
    t2, _ = model.apply(params, state, x, Context(train=True, rng=jax.random.key(2)))
    assert not np.allclose(np.asarray(t1), np.asarray(t2))  # stochastic train


def test_resnet34_shapes_and_param_count():
    """ResNet-34: [3,4,6,3] BasicBlocks; torchvision resnet34 has 21.28M
    params at 1000 classes — ours at 10 classes should land at the same
    count minus the head difference."""
    import jax
    import jax.numpy as jnp

    from tpuddp.models import ResNet34
    from tpuddp.nn.core import Context

    model = ResNet34(num_classes=10, small_input=True)
    params, state = model.init(jax.random.key(0), jnp.zeros((1, 32, 32, 3)))
    n_params = sum(
        int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(params)
    )
    # torchvision resnet34: 21,797,672 at 1000 classes; minus its head
    # (512*1000+1000), minus the small-input stem delta (7x7x3x64 ->
    # 3x3x3x64 = -7,680), plus our 10-class head (512*10+10)
    assert n_params == 21797672 - 513000 - 7680 + 5130, n_params
    y, _ = model.apply(params, state, jnp.zeros((2, 32, 32, 3)), Context(train=False))
    assert y.shape == (2, 10)


def test_resnet34_registry_and_sync_bn():
    from tpuddp.models import load_model
    from tpuddp.nn.norm import has_divergent_buffers

    m = load_model("resnet34_small", 10, sync_bn=True)
    assert not has_divergent_buffers(m)  # every BN is synced


def test_space_to_depth_stem_is_exact():
    """nn.SpaceToDepthConv2d == nn.Conv2d bit-for-reassociation: same params,
    same forward output and same parameter gradients on the AlexNet stem
    shape (11x11/s4/p2 on 3 channels), plus a non-square odd-size case."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpuddp import nn
    from tpuddp.nn.core import Context

    for (h, w), k, s, p in [((224, 224), 11, 4, 2), ((67, 93), 7, 2, 3)]:
        ref = nn.Conv2d(16, kernel_size=k, strides=s, padding=p)
        s2d = nn.SpaceToDepthConv2d(16, kernel_size=k, strides=s, padding=p)
        x = jnp.asarray(
            np.random.RandomState(0).randn(2, h, w, 3).astype(np.float32)
        )
        params, _ = ref.init(jax.random.key(0), x)

        y_ref, _ = ref.apply(params, (), x, Context())
        y_s2d, _ = s2d.apply(params, (), x, Context())
        assert y_ref.shape == y_s2d.shape
        np.testing.assert_allclose(
            np.asarray(y_ref), np.asarray(y_s2d), rtol=1e-5, atol=1e-5
        )

        def loss(mod):
            def f(p):
                y, _ = mod.apply(p, (), x, Context())
                return jnp.sum(y * y)
            return jax.grad(f)(params)

        g_ref, g_s2d = loss(ref), loss(s2d)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4
            ),
            g_ref, g_s2d,
        )


def test_alexnet_s2d_same_logits_and_registry():
    """AlexNet(space_to_depth=True) shares parameter trees with the vanilla
    model (checkpoints/imports interchangeable) and produces the same
    logits; the registry exposes it as alexnet_s2d."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpuddp.models import AlexNet, load_model
    from tpuddp.nn.core import Context

    vanilla = AlexNet(num_classes=10)
    s2d = load_model("alexnet_s2d", 10)
    x = jnp.asarray(
        np.random.RandomState(1).randn(2, 224, 224, 3).astype(np.float32)
    )
    params, state = vanilla.init(jax.random.key(0), x)
    p2, _ = s2d.init(jax.random.key(0), x)
    jax.tree_util.tree_map(  # identical tree structure AND shapes
        lambda a, b: (np.testing.assert_array_equal(np.shape(a), np.shape(b))),
        params, p2,
    )
    y1, _ = vanilla.apply(params, state, x, Context(train=False))
    y2, _ = s2d.apply(params, state, x, Context(train=False))
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-4, atol=1e-4)


def test_resnet_s2d_stem_same_logits():
    """ResNet's 7x7/s2 full stem under space_to_depth: same params, same
    eval-mode logits as the plain stem (exactness at the model level)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpuddp.models import ResNet18, load_model
    from tpuddp.nn.core import Context

    plain = ResNet18(num_classes=10)
    s2d = load_model("resnet18_s2d", 10)
    x = jnp.asarray(
        np.random.RandomState(2).randn(2, 96, 96, 3).astype(np.float32)
    )
    params, state = plain.init(jax.random.key(0), x)
    y1, _ = plain.apply(params, state, x, Context(train=False))
    y2, _ = s2d.apply(params, state, x, Context(train=False))
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-4, atol=1e-4)


def test_small_input_rejects_space_to_depth():
    import pytest

    from tpuddp.models import ResNet18

    with pytest.raises(ValueError, match="small_input"):
        ResNet18(small_input=True, space_to_depth=True)


@pytest.mark.slow
def test_space_to_depth_fuzz_matches_conv2d():
    """Property check over random geometries: SpaceToDepthConv2d == Conv2d
    for any (k, s, p, h, w) it accepts — the padding/blocking arithmetic must
    hold everywhere, not just the stems we ship."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpuddp import nn
    from tpuddp.nn.core import Context

    rs = np.random.RandomState(42)
    for trial in range(12):
        s = int(rs.randint(2, 5))
        k = int(rs.randint(1, 12))
        p = int(rs.randint(0, k + 2))
        # bounds guarantee at least one output window per dim
        h = int(rs.randint(max(k - p, s), 40))
        w = int(rs.randint(max(k - p, s), 40))
        c = int(rs.choice([1, 3, 5]))
        ref = nn.Conv2d(8, kernel_size=k, strides=s, padding=p)
        s2d = nn.SpaceToDepthConv2d(8, kernel_size=k, strides=s, padding=p)
        x = jnp.asarray(rs.randn(2, h, w, c).astype(np.float32))
        params, _ = ref.init(jax.random.key(trial), x)
        y_ref, _ = ref.apply(params, (), x, Context())
        y_s2d, _ = s2d.apply(params, (), x, Context())
        assert y_ref.shape == y_s2d.shape, (trial, k, s, p, h, w, c)
        np.testing.assert_allclose(
            np.asarray(y_ref), np.asarray(y_s2d), rtol=1e-4, atol=1e-4,
            err_msg=f"trial {trial}: k={k} s={s} p={p} h={h} w={w} c={c}",
        )


# --- nn.Conv2d picks the thin-channel strided stem's lowering itself --------

_DN = ("NHWC", "HWIO", "NHWC")


def _direct_conv(layer, params, x):
    """The direct lowering, written out: what ``Conv2d.apply`` was before it
    chose, and still is wherever the rule says no."""
    y = jax.lax.conv_general_dilated(
        x, params["weight"].astype(x.dtype), window_strides=layer.strides,
        padding=layer._pad_arg(), dimension_numbers=_DN,
    )
    if layer.use_bias:
        y = y + params["bias"].astype(y.dtype)
    return y


def _lowered_text(fn, *args):
    def f(*a):
        return fn(*a)

    return jax.jit(f).lower(*args).as_text()


# (in_channels, kernel, strides, padding) -> block size, or None for direct
_RULE_CASES = {
    "alexnet_stem": ((3, 11, 4, 2), 2),  # blocks of 2 span 12 rows, of 4 span 16
    "rgba_7x7_s4": ((4, 7, 4, 3), 4),  # 8 rows either way: the larger block
    "grey_7x7_s5": ((1, 7, 5, 0), 5),
    "resnet_stem": ((3, 7, 2, 3), None),  # measured slower blocked (PERF.md, PR 25)
    "5x5_s3": ((3, 5, 3, 2), None),
    "8ch_11x11_s4": ((8, 11, 4, 2), None),
    "3x3_s1": ((3, 3, 1, 1), None),
    "5x5_s1": ((3, 5, 1, 2), None),
    "1x1_s2_256ch": ((256, 1, 2, 0), None),
    "3x3_s2_64ch": ((64, 3, 2, 1), None),
    "kernel_not_over_stride": ((3, 2, 2, 0), None),
    "string_padding": ((3, 7, 2, "SAME"), None),
    "asymmetric_padding": ((3, 7, 2, ((3, 2), (3, 2))), None),
    "non_square_stride": ((3, 7, (2, 1), 3), None),
}


@pytest.mark.parametrize("case", sorted(_RULE_CASES))
def test_conv_lowering_rule(case):
    """The one rule (nn.layers.space_to_depth_block) on static shapes: it
    takes the thin-channel, widely strided stems and nothing else, ``conv_lowering``
    states the choice, and a convolution it rejects lowers to the direct
    form's HLO, character for character."""
    from tpuddp.nn.layers import space_to_depth_block

    (c, k, s, p), block = _RULE_CASES[case]
    layer = nn.Conv2d(8, kernel_size=k, strides=s, padding=p)
    assert space_to_depth_block(c, layer.kernel_size, layer.strides, p) == block
    want = "direct" if block is None else f"space-to-depth, block {block}"
    assert nn.conv_lowering(layer, c) == want
    x = jnp.zeros((2, 24, 24, c), jnp.bfloat16)
    params, _ = layer.init(KEY, x)
    auto = _lowered_text(lambda pr, v: layer.apply(pr, (), v, Context())[0], params, x)
    direct = _lowered_text(lambda pr, v: _direct_conv(layer, pr, v), params, x)
    assert (auto == direct) == (block is None)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize(
    "hw,k,s,p",
    [((224, 224), 11, 4, 2), ((63, 63), 11, 4, 2), ((67, 93), 7, 4, 3), ((225, 226), 9, 5, 1)],
    ids=["alexnet224", "alexnet63", "odd67x93", "odd225x226"],
)
def test_conv_auto_lowering_is_exact(hw, k, s, p, dtype):
    """``Conv2d`` with the rule engaged against a direct
    ``lax.conv_general_dilated`` on the same parameters: forward, weight and
    bias gradients, at the AlexNet stem's real shape, its 63x63 minimum and
    sizes that are no multiple of the stride."""
    dtype = jnp.dtype(dtype)
    layer = nn.Conv2d(16, kernel_size=k, strides=s, padding=p)
    assert nn.conv_lowering(layer, 3).startswith("space-to-depth, block ")
    x = jnp.asarray(
        np.random.RandomState(0).randn(2, *hw, 3).astype(np.float32)
    ).astype(dtype)
    params, _ = layer.init(KEY, x)

    def run(forward):
        def loss(pr):
            y = forward(pr)
            return jnp.sum(jnp.square(y.astype(jnp.float32))), y

        (_, y), g = jax.value_and_grad(loss, has_aux=True)(params)
        return y, g

    y_auto, g_auto = run(lambda pr: layer.apply(pr, (), x, Context())[0])
    y_ref, g_ref = run(lambda pr: _direct_conv(layer, pr, x))
    assert y_auto.shape == y_ref.shape and y_auto.dtype == y_ref.dtype
    # bf16: the two forms round a different order of the same f32 sum once
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    scale = float(jnp.abs(y_ref.astype(jnp.float32)).max())
    np.testing.assert_allclose(
        np.asarray(y_auto, np.float32), np.asarray(y_ref, np.float32),
        rtol=tol, atol=tol * scale,
    )
    for name in ("weight", "bias"):
        a, b = np.asarray(g_auto[name]), np.asarray(g_ref[name])
        np.testing.assert_allclose(a, b, rtol=tol, atol=tol * np.abs(b).max())


@pytest.mark.parametrize(
    "plain,alias,hw", [("alexnet", "alexnet_s2d", 64), ("resnet18", "resnet18_s2d", 32),
                       ("resnet50", "resnet50_s2d", 32)],
)
def test_s2d_names_build_the_plain_program(plain, alias, hw):
    """The ``*_s2d`` registry names and ``space_to_depth=`` are aliases: one
    jitted forward of either lowers to the same HLO text."""
    x = jnp.zeros((2, hw, hw, 3), jnp.bfloat16)
    texts = []
    for name in (plain, alias):
        model = load_model(name, 10)
        params, state = jax.eval_shape(lambda: model.init(KEY, x))
        texts.append(_lowered_text(
            lambda pr, st, v: model.apply(pr, st, v, Context(train=False))[0],
            params, state, x,
        ))
    assert texts[0] == texts[1]


def test_stem_keeps_its_device_scope():
    """The stem is a ``Conv2d`` whatever it lowers to, so its device
    operations stay under ``0_Conv2d`` and the benchmark's layer table
    (benchmark/scope_reduce.py, benchmark/flops/alexnet_cifar224.py) keeps
    joining on that name."""
    model = load_model("alexnet_s2d", 10)
    x = jnp.zeros((2, 64, 64, 3), jnp.bfloat16)
    params, state = jax.eval_shape(lambda: model.init(KEY, x))
    assert nn.conv_lowering(model[0], 3) == "space-to-depth, block 2"
    text = jax.jit(
        lambda pr, st, v: model.apply(pr, st, v, Context(train=False))[0]
    ).lower(params, state, x).as_text(debug_info=True)
    assert "0_Conv2d/conv_general_dilated" in text
    assert "SpaceToDepthConv2d" not in text
