"""AlexNet-class CNN — the reference's flagship model.

The reference loads torchvision's pretrained AlexNet and swaps the last
classifier layer for CIFAR-10 (data_and_toy_model.py:41-45). This is the same
architecture in NHWC (TPU-native layout), trained from scratch: pretrained
ImageNet weights are a torchvision download and this build runs zero-egress.
``classifier_head_only=False`` + :func:`replace_head` reproduce the
swap-the-head workflow for any weights loaded from disk.
"""

from __future__ import annotations

import jax

from tpuddp import nn


def AlexNet(
    num_classes: int = 10, dropout: float = 0.5, space_to_depth: bool = False
) -> nn.Sequential:
    """torchvision AlexNet topology: 5 conv blocks -> adaptive 6x6 avg pool ->
    3-layer classifier. Input is NHWC, any spatial size >= 63 (reference feeds
    224x224 CIFAR upsamples).

    The 11x11/s4 3-channel stem lowers through space-to-depth by itself
    (``nn.Conv2d`` chooses from its shapes: same math, same parameter
    shapes). ``space_to_depth`` is accepted for the callers that used to ask
    for that lowering and changes nothing: ``alexnet_s2d`` is ``alexnet``."""
    del space_to_depth
    features = [
        nn.Conv2d(64, kernel_size=11, strides=4, padding=2),
        nn.ReLU(),
        nn.MaxPool2d(3, strides=2),
        nn.Conv2d(192, kernel_size=5, padding=2),
        nn.ReLU(),
        nn.MaxPool2d(3, strides=2),
        nn.Conv2d(384, kernel_size=3, padding=1),
        nn.ReLU(),
        nn.Conv2d(256, kernel_size=3, padding=1),
        nn.ReLU(),
        nn.Conv2d(256, kernel_size=3, padding=1),
        nn.ReLU(),
        nn.MaxPool2d(3, strides=2),
    ]
    classifier = [
        nn.AdaptiveAvgPool2d((6, 6)),
        nn.Flatten(),
        nn.Dropout(dropout),
        nn.Linear(4096),
        nn.ReLU(),
        nn.Dropout(dropout),
        nn.Linear(4096),
        nn.ReLU(),
        nn.Linear(num_classes),
    ]
    return nn.Sequential(*features, *classifier)


def replace_head(model: nn.Sequential, params, key, num_classes: int):
    """Swap the final Linear's parameters for a fresh ``num_classes`` head —
    the reference's ``model.classifier[6] = nn.Linear(4096, 10)`` move
    (data_and_toy_model.py:43-44). Returns updated params."""
    head: nn.Linear = model[-1]
    in_features = params[-1]["weight"].shape[0]
    new_head = nn.Linear(num_classes, use_bias=head.use_bias)
    new_p, _ = new_head.init(key, jax.ShapeDtypeStruct((1, in_features), params[-1]["weight"].dtype))
    model.layers = model.layers[:-1] + (new_head,)
    return tuple(params[:-1]) + (new_p,)
