"""ResNets (NHWC) — ResNet-18/34 (BasicBlock; -18 is the multi-host CIFAR
BASELINE config, BASELINE.json configs[4]) and ResNet-50/101/152 (Bottleneck).
BatchNorm layers honor convert_sync_batchnorm / ``sync_bn=True`` so
cross-replica statistic sync works under DP."""

from __future__ import annotations

import jax

from tpuddp import nn
from tpuddp.nn.core import Context, Module
from tpuddp.observability import profiling as _prof


def _conv(block, key: str, params, x, ctx: Context):
    """Child ``key`` of a residual block (a bufferless convolution), its
    device operations named for the key."""
    with _prof.scope(key):
        y, _ = getattr(block, key).apply(params[key], (), x, ctx)
    return y


def _norm(block, key: str, params, state, new_state, x, ctx: Context):
    """Child ``key`` of a residual block (a BatchNorm), its device operations
    named for the key; its new buffers go to ``new_state[key]``."""
    with _prof.scope(key):
        y, new_state[key] = getattr(block, key).apply(
            params[key], state[key], x, ctx
        )
    return y


class BasicBlock(Module):
    """Two 3x3 convs with identity (or 1x1-projected) shortcut."""

    def __init__(self, features: int, stride: int = 1, sync_bn: bool = False):
        self.features = features
        self.stride = stride
        self.conv1 = nn.Conv2d(features, 3, strides=stride, padding=1, use_bias=False)
        self.bn1 = nn.BatchNorm(sync=sync_bn)
        self.conv2 = nn.Conv2d(features, 3, padding=1, use_bias=False)
        self.bn2 = nn.BatchNorm(sync=sync_bn)
        self.down_conv = nn.Conv2d(features, 1, strides=stride, use_bias=False)
        self.down_bn = nn.BatchNorm(sync=sync_bn)

    def children(self):
        return (self.conv1, self.bn1, self.conv2, self.bn2, self.down_conv, self.down_bn)

    def divergent_state(self) -> bool:
        return False  # aggregates child state only; owns no buffers of its own

    def init(self, key, x):
        keys = jax.random.split(key, 6)
        in_ch = x.shape[-1]
        p, s = {}, {}
        p["conv1"], _, h = self.conv1.init_with_output_shape(keys[0], x)
        p["bn1"], s["bn1"], h = self.bn1.init_with_output_shape(keys[1], h)
        p["conv2"], _, h = self.conv2.init_with_output_shape(keys[2], h)
        p["bn2"], s["bn2"], _ = self.bn2.init_with_output_shape(keys[3], h)
        if self.stride != 1 or in_ch != self.features:
            p["down_conv"], _, d = self.down_conv.init_with_output_shape(keys[4], x)
            p["down_bn"], s["down_bn"], _ = self.down_bn.init_with_output_shape(keys[5], d)
        return p, s

    def apply(self, params, state, x, ctx: Context):
        new_state = dict(state)
        h = _conv(self, "conv1", params, x, ctx)
        h = _norm(self, "bn1", params, state, new_state, h, ctx)
        h, _ = nn.ReLU().apply((), (), h, ctx)
        h = _conv(self, "conv2", params, h, ctx)
        h = _norm(self, "bn2", params, state, new_state, h, ctx)
        if "down_conv" in params:
            sc = _conv(self, "down_conv", params, x, ctx)
            sc = _norm(self, "down_bn", params, state, new_state, sc, ctx)
        else:
            sc = x
        return jax.nn.relu(h + sc), new_state


class Bottleneck(Module):
    """1x1 reduce -> 3x3 (strided, torchvision v1.5 placement) -> 1x1 expand
    (x4), with identity (or 1x1-projected) shortcut — the ResNet-50/101/152
    block (torchvision-layout state_dict keys: conv1/bn1, conv2/bn2,
    conv3/bn3, downsample.{0,1})."""

    expansion = 4

    def __init__(self, features: int, stride: int = 1, sync_bn: bool = False):
        self.features = features  # the bottleneck width; output is 4x
        self.stride = stride
        self.conv1 = nn.Conv2d(features, 1, use_bias=False)
        self.bn1 = nn.BatchNorm(sync=sync_bn)
        self.conv2 = nn.Conv2d(features, 3, strides=stride, padding=1, use_bias=False)
        self.bn2 = nn.BatchNorm(sync=sync_bn)
        self.conv3 = nn.Conv2d(features * self.expansion, 1, use_bias=False)
        self.bn3 = nn.BatchNorm(sync=sync_bn)
        self.down_conv = nn.Conv2d(
            features * self.expansion, 1, strides=stride, use_bias=False
        )
        self.down_bn = nn.BatchNorm(sync=sync_bn)

    def children(self):
        return (
            self.conv1, self.bn1, self.conv2, self.bn2, self.conv3, self.bn3,
            self.down_conv, self.down_bn,
        )

    def divergent_state(self) -> bool:
        return False  # aggregates child state only; owns no buffers of its own

    def init(self, key, x):
        keys = jax.random.split(key, 8)
        in_ch = x.shape[-1]
        p, s = {}, {}
        p["conv1"], _, h = self.conv1.init_with_output_shape(keys[0], x)
        p["bn1"], s["bn1"], h = self.bn1.init_with_output_shape(keys[1], h)
        p["conv2"], _, h = self.conv2.init_with_output_shape(keys[2], h)
        p["bn2"], s["bn2"], h = self.bn2.init_with_output_shape(keys[3], h)
        p["conv3"], _, h = self.conv3.init_with_output_shape(keys[4], h)
        p["bn3"], s["bn3"], _ = self.bn3.init_with_output_shape(keys[5], h)
        if self.stride != 1 or in_ch != self.features * self.expansion:
            p["down_conv"], _, d = self.down_conv.init_with_output_shape(keys[6], x)
            p["down_bn"], s["down_bn"], _ = self.down_bn.init_with_output_shape(keys[7], d)
        return p, s

    def apply(self, params, state, x, ctx: Context):
        new_state = dict(state)
        h = _conv(self, "conv1", params, x, ctx)
        h = _norm(self, "bn1", params, state, new_state, h, ctx)
        h = jax.nn.relu(h)
        h = _conv(self, "conv2", params, h, ctx)
        h = _norm(self, "bn2", params, state, new_state, h, ctx)
        h = jax.nn.relu(h)
        h = _conv(self, "conv3", params, h, ctx)
        h = _norm(self, "bn3", params, state, new_state, h, ctx)
        if "down_conv" in params:
            sc = _conv(self, "down_conv", params, x, ctx)
            sc = _norm(self, "down_bn", params, state, new_state, sc, ctx)
        else:
            sc = x
        return jax.nn.relu(h + sc), new_state


class GlobalAvgPool(Module):
    def apply(self, params, state, x, ctx: Context):
        return x.mean(axis=(1, 2)), state


def _resnet(
    depths,
    num_classes: int,
    sync_bn: bool,
    small_input: bool,
    space_to_depth: bool = False,
    block=BasicBlock,
) -> nn.Sequential:
    """stem + ``block`` stages at widths [64,128,256,512] + GAP head.
    ``small_input=True`` uses the CIFAR stem (3x3/1 conv, no maxpool) for
    native 32x32 training — the TPU-friendly alternative to the reference's
    resize-everything-to-224. The full stem's 7x7/s2 3-channel conv is a
    plain ``nn.Conv2d``, which picks its own lowering from its shapes
    (``nn.conv_lowering``: direct at stride 2, where the blocked form was
    measured to lose, PERF.md PR 25); ``space_to_depth`` is accepted for the
    callers and ``*_s2d`` names that used to ask for the blocked stem, and
    builds the same program either way. ``block`` is BasicBlock (ResNet-18/34) or
    Bottleneck (ResNet-50)."""
    if small_input:
        if space_to_depth:
            raise ValueError(
                "space_to_depth applies to the full 7x7/s2 stem; the "
                "small_input CIFAR stem (3x3/s1) has no stride to block"
            )
        stem = [
            nn.Conv2d(64, 3, strides=1, padding=1, use_bias=False),
            nn.BatchNorm(sync=sync_bn),
            nn.ReLU(),
        ]
    else:
        stem = [
            nn.Conv2d(64, 7, strides=2, padding=3, use_bias=False),
            nn.BatchNorm(sync=sync_bn),
            nn.ReLU(),
            nn.MaxPool2d(3, strides=2, padding=1),
        ]
    blocks = []
    for n_blocks, (width, stride) in zip(
        depths, [(64, 1), (128, 2), (256, 2), (512, 2)]
    ):
        blocks.append(block(width, stride=stride, sync_bn=sync_bn))
        blocks.extend(
            block(width, stride=1, sync_bn=sync_bn)
            for _ in range(n_blocks - 1)
        )
    head = [GlobalAvgPool(), nn.Linear(num_classes)]
    return nn.Sequential(*stem, *blocks, *head)


def ResNet18(
    num_classes: int = 10, sync_bn: bool = False, small_input: bool = False,
    space_to_depth: bool = False,
) -> nn.Sequential:
    """Standard ResNet-18: [2,2,2,2] BasicBlocks."""
    return _resnet((2, 2, 2, 2), num_classes, sync_bn, small_input, space_to_depth)


def ResNet34(
    num_classes: int = 10, sync_bn: bool = False, small_input: bool = False,
    space_to_depth: bool = False,
) -> nn.Sequential:
    """Standard ResNet-34: [3,4,6,3] BasicBlocks."""
    return _resnet((3, 4, 6, 3), num_classes, sync_bn, small_input, space_to_depth)


def ResNet50(
    num_classes: int = 10, sync_bn: bool = False, small_input: bool = False,
    space_to_depth: bool = False,
) -> nn.Sequential:
    """Standard ResNet-50: [3,4,6,3] Bottleneck blocks (torchvision v1.5
    stride placement: the 3x3 conv strides)."""
    return _resnet(
        (3, 4, 6, 3), num_classes, sync_bn, small_input, space_to_depth,
        block=Bottleneck,
    )


def ResNet101(
    num_classes: int = 10, sync_bn: bool = False, small_input: bool = False,
    space_to_depth: bool = False,
) -> nn.Sequential:
    """Standard ResNet-101: [3,4,23,3] Bottleneck blocks."""
    return _resnet(
        (3, 4, 23, 3), num_classes, sync_bn, small_input, space_to_depth,
        block=Bottleneck,
    )


def ResNet152(
    num_classes: int = 10, sync_bn: bool = False, small_input: bool = False,
    space_to_depth: bool = False,
) -> nn.Sequential:
    """Standard ResNet-152: [3,8,36,3] Bottleneck blocks."""
    return _resnet(
        (3, 8, 36, 3), num_classes, sync_bn, small_input, space_to_depth,
        block=Bottleneck,
    )
