"""Model zoo. The reference's zoo is ``load_model`` = pretrained AlexNet with
its classifier head swapped for CIFAR-10 (data_and_toy_model.py:41-45); tpuddp
adds genuinely small toy models for fast CI (per SURVEY.md scale calibration),
ResNet-18/34 (BasicBlock) + ResNet-50/101/152 (Bottleneck), VGG-11/13/16/19, and
CIFAR-stem variants (the ``*_s2d`` names are aliases of the plain ones); all
torch-importable. Token models: a small GPT-2-style ``TransformerLM`` and
``HybridMoELM``, expert decoders whose layers are listed by type (seven mixer
types: Gated DeltaNet, gated attention, sliding-window and full attention, a
gated short convolution, latent attention, attention over the keys a learned
indexer chose), with a dense or a sparse
feed-forward a layer, a tied or an untied head and, where the router chooses
by a bias, that bias as the trunk's state; built at published widths as one
chip's share of an expert-parallel job. The same trunk walks a dense stack
several times over the same weights, every pass an exit that a learned gate
weighs, or carries a module after the stack whose head predicts the token
after next."""

from tpuddp.models.toy import ToyCNN, ToyMLP  # noqa: F401
from tpuddp.models.alexnet import AlexNet  # noqa: F401
from tpuddp.models.transformer import TransformerLM  # noqa: F401
from tpuddp.models.resnet import (  # noqa: F401
    ResNet18, ResNet34, ResNet50, ResNet101, ResNet152,
)
from tpuddp.models.vgg import VGG11, VGG13, VGG16, VGG19  # noqa: F401
from tpuddp.models.hybrid_moe import (  # noqa: F401
    GLM_4_7_FLASH_EP8, GLM_4_7_FLASH_TINY, KEYE_VL_2_0_EP8, KEYE_VL_2_0_TINY, LFM2_EP4, LFM2_TINY, MELLUM2_EP4, MELLUM2_TINY, OURO_2_6B_L6, OURO_TINY, QWEN3_NEXT_EP16, QWEN3_NEXT_TINY,
    HybridMoELM,
)

from functools import partial as _partial

_REGISTRY = {
    "toy_mlp": ToyMLP,
    "toy_cnn": ToyCNN,
    "alexnet": AlexNet,
    "resnet18": ResNet18,
    "resnet34": ResNet34,
    "resnet50": ResNet50,
    "resnet101": ResNet101,
    "resnet152": ResNet152,
    "vgg11": VGG11,
    "vgg13": VGG13,
    "vgg16": VGG16,
    "vgg19": VGG19,
    # CIFAR-style stem (3x3 conv, no maxpool) for small native resolutions
    "resnet18_small": _partial(ResNet18, small_input=True),
    "resnet34_small": _partial(ResNet34, small_input=True),
    "resnet50_small": _partial(ResNet50, small_input=True),
    "resnet101_small": _partial(ResNet101, small_input=True),
    "resnet152_small": _partial(ResNet152, small_input=True),
    # decoder-only transformer family (tpuddp/models/transformer.py):
    # num_classes aliases vocab_size; partition rules follow SNIPPETS.md
    # [2]'s table so these drop into the future ("data","model") mesh
    "transformer_tiny": _partial(
        TransformerLM, d_model=64, n_heads=4, n_layers=2, max_seq_len=128,
    ),
    "transformer_small": _partial(
        TransformerLM, d_model=128, n_heads=8, n_layers=4, max_seq_len=256,
    ),
    # hybrid mixture-of-experts decoders on one trunk (models/hybrid_moe.py):
    # num_classes is the slice of the vocabulary held. Qwen3-Next (Gated
    # DeltaNet 3:1 with gated attention, a shared expert) at the published
    # widths as one chip of a 16-way expert-parallel job holds them, Mellum 2
    # (sliding-window 3:1 with YaRN-scaled full attention, no shared expert)
    # as one chip of a 4-way job, LFM2 (gated short convolutions 3:1 with
    # full attention over heads of 64, a dense leading layer, a sigmoid router
    # balanced by a bias, a tied head) as one chip of a 4-way job, and a
    # CPU-test size of each
    "qwen3_next_ep16": _partial(HybridMoELM, **QWEN3_NEXT_EP16),
    "qwen3_next_tiny": _partial(HybridMoELM, **QWEN3_NEXT_TINY),
    "mellum2_ep4": _partial(HybridMoELM, **MELLUM2_EP4),
    "mellum2_tiny": _partial(HybridMoELM, **MELLUM2_TINY),
    "lfm2_ep4": _partial(HybridMoELM, **LFM2_EP4),
    "lfm2_tiny": _partial(HybridMoELM, **LFM2_TINY),
    # the same trunk as a looped dense decoder: Ouro-2.6B (full attention over
    # 16 heads of 128 with no per-head norm, a norm before and after each half
    # of a layer, a 5,632-wide SwiGLU, an untied head) with six of its layers
    # whole on the chip, walked four times over the same leaves, every pass an
    # exit weighted by a learned gate; and a CPU-test size of it
    "ouro_2_6b_l6": _partial(HybridMoELM, **OURO_2_6B_L6),
    "ouro_tiny": _partial(HybridMoELM, **OURO_TINY),
    # the same trunk with latent attention (GLM-4.7-Flash: 20 heads whose keys
    # and values come through a 512-wide latent, queries through a 768-wide
    # one, a head 192 without position + 64 rotary, the rotary key shared by
    # the heads; a dense leading layer, 64 routed experts chosen by sigmoid
    # score plus bias and scaled by 1.8, a shared expert added ungated) and a
    # module after the stack whose head predicts the token after next, as one
    # chip of an 8-way expert-parallel job holds them; and a CPU-test size
    "glm_4_7_flash_ep8": _partial(HybridMoELM, **GLM_4_7_FLASH_EP8),
    "glm_4_7_flash_tiny": _partial(HybridMoELM, **GLM_4_7_FLASH_TINY),
    # the same trunk with learned sparse attention (Keye-VL-2.0-30B-A3B's
    # language model: 32 query heads over 4 key/value heads of 128 with the
    # per-head norm; in every layer an indexer of 16 heads of 64 over one key
    # head scores every earlier key and a query attends only its 2,048 best;
    # the indexer learns from an objective of its own inside the layer; 128
    # routed experts, 8 a token, none shared) as one chip of an 8-way
    # expert-parallel job holds it; and a CPU-test size
    "keye_vl_2_0_ep8": _partial(HybridMoELM, **KEYE_VL_2_0_EP8),
    "keye_vl_2_0_tiny": _partial(HybridMoELM, **KEYE_VL_2_0_TINY),
    # aliases of the plain names: nn.Conv2d picks the space-to-depth lowering
    # of a thin-channel strided stem from its own shapes, so these build the
    # same program (kept for settings files and checkpoints that name them)
    "alexnet_s2d": _partial(AlexNet, space_to_depth=True),
    "resnet18_s2d": _partial(ResNet18, space_to_depth=True),
    "resnet34_s2d": _partial(ResNet34, space_to_depth=True),
    "resnet50_s2d": _partial(ResNet50, space_to_depth=True),
    "resnet101_s2d": _partial(ResNet101, space_to_depth=True),
    "resnet152_s2d": _partial(ResNet152, space_to_depth=True),
}


def load_model(name: str = "alexnet", num_classes: int = 10, **kwargs):
    """Registry-based analog of the reference's ``load_model()``."""
    try:
        cls = _REGISTRY[name]
    except KeyError:
        raise ValueError(f"unknown model {name!r}; one of {sorted(_REGISTRY)}")
    return cls(num_classes=num_classes, **kwargs)


__all__ = [
    "ToyMLP", "ToyCNN", "AlexNet", "ResNet18", "ResNet34", "ResNet50",
    "ResNet101", "ResNet152",
    "TransformerLM",
    "HybridMoELM",
    "VGG11", "VGG13", "VGG16", "VGG19",
    "load_model",
]
