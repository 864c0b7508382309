"""tpuddp.nn — a compact functional neural-net layer library.

Pure init/apply modules over explicit parameter pytrees (no framework
dependency): the compute path is jax.numpy + lax so everything fuses under jit
and tiles onto the TPU MXU. Layout is NHWC (TPU-native), vs the reference
stack's NCHW.
"""

from tpuddp.nn.core import Context, Module, Sequential  # noqa: F401
from tpuddp.nn.layers import (  # noqa: F401
    AdaptiveAvgPool2d,
    AvgPool2d,
    Conv2d,
    SpaceToDepthConv2d,
    conv_lowering,
    Dropout,
    Embedding,
    Flatten,
    Linear,
    MaxPool2d,
    ReLU,
)
from tpuddp.nn.norm import (  # noqa: F401
    BatchNorm,
    LayerNorm,
    convert_sync_batchnorm,
)
from tpuddp.nn.loss import CrossEntropyLoss, cross_entropy  # noqa: F401
from tpuddp.nn.sequence import (  # noqa: F401
    DeferredExits,
    DeferredLogits,
    causal_attention,
    causal_conv1d,
    linear_cross_entropy,
    rms_norm,
    rotary,
    swiglu,
)
from tpuddp.nn.deltanet import chunk_gated_delta_rule  # noqa: F401
from tpuddp.nn.moe import expert_share_moe  # noqa: F401

__all__ = [
    "Context",
    "Module",
    "Sequential",
    "Linear",
    "Conv2d",
    "SpaceToDepthConv2d",
    "conv_lowering",
    "MaxPool2d",
    "AvgPool2d",
    "AdaptiveAvgPool2d",
    "ReLU",
    "Dropout",
    "Embedding",
    "Flatten",
    "BatchNorm",
    "LayerNorm",
    "convert_sync_batchnorm",
    "CrossEntropyLoss",
    "cross_entropy",
    "DeferredExits",
    "DeferredLogits",
    "causal_attention",
    "causal_conv1d",
    "linear_cross_entropy",
    "rms_norm",
    "rotary",
    "swiglu",
    "chunk_gated_delta_rule",
    "expert_share_moe",
]
