"""Import torch/torchvision checkpoints into tpuddp models (AlexNet,
VGG-11/13/16/19, ResNet-18/34/50/101/152).

The reference starts from *pretrained* torchvision AlexNet weights
(data_and_toy_model.py:41-43). This build runs zero-egress, so pretrained
weights can't be downloaded — but when a torchvision ``state_dict`` exists on
disk (or any torch AlexNet checkpoint), this converter maps it into tpuddp's
NHWC parameter tree:

- conv weights:   OIHW -> HWIO transpose;
- first classifier Linear: torch flattens NCHW (c, h, w) while tpuddp flattens
  NHWC (h, w, c), so the 9216-dim input axis is re-ordered accordingly;
- other Linears:  (out, in) -> (in, out) transpose.

The conversion is validated end-to-end in tests: a torch AlexNet and the
imported tpuddp AlexNet produce matching logits — the strongest available
proof that the architectures are identical.
"""

from __future__ import annotations

from functools import partial as _pt
from typing import Dict, Mapping

import jax.numpy as jnp
import numpy as np

# torchvision AlexNet state_dict key -> index of the layer in tpuddp's
# Sequential (tpuddp/models/alexnet.py). Conveniently torchvision's
# features.N indices coincide with ours because the layer order is identical.
_CONV_KEYS = {
    "features.0": 0,
    "features.3": 3,
    "features.6": 6,
    "features.8": 8,
    "features.10": 10,
}
_LINEAR_KEYS = {
    # layer indices in tpuddp's 22-layer Sequential: features occupy 0-12
    # (last MaxPool at 12), then AdaptiveAvgPool@13, Flatten@14, Dropout@15,
    # Linear@16, ReLU@17, Dropout@18, Linear@19, ReLU@20, Linear@21
    "classifier.1": 16,
    "classifier.4": 19,
    "classifier.6": 21,
}
_POOL_GRID = 6  # AdaptiveAvgPool2d((6, 6))
_POOL_CH = 256


def _to_np(t) -> np.ndarray:
    if hasattr(t, "detach"):
        t = t.detach().cpu().numpy()
    return np.asarray(t, dtype=np.float32)


def _convert_seq_cnn(
    state_dict: Mapping[str, object],
    params,
    conv_keys: Mapping[str, int],
    linear_keys: Mapping[str, int],
    first_linear: str,
    pool_grid: int,
    pool_ch: int,
):
    """Shared torchvision-Sequential-CNN converter (AlexNet, VGG): conv OIHW
    -> HWIO; the FIRST classifier Linear's flattened input axis is re-ordered
    from torch's NCHW flatten (c, h, w) to NHWC (h, w, c); other Linears are
    plain transposes. Every tensor's shape is validated with the torch key
    named on mismatch."""
    new_params = list(params)

    for key, idx in conv_keys.items():
        w = _to_np(state_dict[f"{key}.weight"])  # OIHW
        b = _to_np(state_dict[f"{key}.bias"])
        hwio = np.transpose(w, (2, 3, 1, 0))
        expect = new_params[idx]["weight"].shape
        if hwio.shape != tuple(expect):
            raise ValueError(f"{key}: shape {hwio.shape} != expected {expect}")
        new_params[idx] = {"weight": jnp.asarray(hwio), "bias": jnp.asarray(b)}

    for key, idx in linear_keys.items():
        w = _to_np(state_dict[f"{key}.weight"])  # (out, in)
        b = _to_np(state_dict[f"{key}.bias"])
        if key == first_linear:
            # re-order the flattened input axis: torch (c, h, w) -> ours (h, w, c)
            out_f = w.shape[0]
            w = (
                w.reshape(out_f, pool_ch, pool_grid, pool_grid)
                .transpose(2, 3, 1, 0)  # -> (h, w, c, out)
                .reshape(pool_grid * pool_grid * pool_ch, out_f)
            )
        else:
            w = w.T
        expect = new_params[idx]["weight"].shape
        if w.shape != tuple(expect):
            raise ValueError(f"{key}: shape {w.shape} != expected {expect}")
        new_params[idx] = {"weight": jnp.asarray(w), "bias": jnp.asarray(b)}

    return tuple(new_params)


def convert_alexnet_state_dict(state_dict: Mapping[str, object], params):
    """Return a copy of tpuddp AlexNet ``params`` (tuple pytree from
    ``AlexNet().init``) with weights replaced by the torch ``state_dict``."""
    return _convert_seq_cnn(
        state_dict, params, _CONV_KEYS, _LINEAR_KEYS,
        first_linear="classifier.1", pool_grid=_POOL_GRID, pool_ch=_POOL_CH,
    )


def convert_vgg_state_dict(name: str, state_dict: Mapping[str, object], params):
    """torchvision-layout VGG ``state_dict`` -> tpuddp VGG params. The
    ``features.N`` conv index map and the classifier Linear positions are
    GENERATED from the same plan that builds the tpuddp model
    (tpuddp/models/vgg.py), so the correspondence can't drift."""
    from tpuddp.models.vgg import vgg_classifier_linear_indices, vgg_conv_indices

    conv_keys = {f"features.{i}": i for i in vgg_conv_indices(name)}
    l0, l1, l2 = vgg_classifier_linear_indices(name)
    linear_keys = {"classifier.0": l0, "classifier.3": l1, "classifier.6": l2}
    return _convert_seq_cnn(
        state_dict, params, conv_keys, linear_keys,
        first_linear="classifier.0", pool_grid=7, pool_ch=512,
    )


def load_torch_alexnet(params, path: str):
    """Load a torch ``.pt``/``.pth`` AlexNet state_dict from ``path`` and
    convert. Requires torch at call time (it is a dev/test dependency only)."""
    import torch

    state_dict = torch.load(path, map_location="cpu", weights_only=True)
    if hasattr(state_dict, "state_dict"):
        state_dict = state_dict.state_dict()
    return convert_alexnet_state_dict(state_dict, params)


def load_pretrained_alexnet(
    path: str, key, num_classes: int = 10, image_size: int = 224,
    space_to_depth: bool = False,
):
    """The reference's fine-tune-from-pretrained workflow
    (data_and_toy_model.py:41-45), from a torch checkpoint on disk: build an
    AlexNet sized to the checkpoint's own head (e.g. 1000-class ImageNet),
    import the weights, then swap in a fresh ``num_classes`` head when the
    widths differ. Returns ``(model, params, model_state)`` ready for
    ``DistributedDataParallel.init_state`` / ``Accelerator.prepare``.
    ``space_to_depth`` is accepted and changes nothing: the stem picks its
    own lowering (``nn.conv_lowering``) and the parameter layout is the
    direct form's, so the same checkpoint loads either way.
    """
    from tpuddp.models.alexnet import AlexNet

    return _load_pretrained(
        path, key, num_classes, image_size,
        build=lambda n: AlexNet(num_classes=n, space_to_depth=space_to_depth),
        head_weight_key="classifier.6.weight",
        convert=lambda sd, p, s: (convert_alexnet_state_dict(sd, p), s),
        salt=0x9e7,
    )


def _load_pretrained(
    path, key, num_classes, image_size, build, head_weight_key, convert, salt
):
    """Shared fine-tune loader: torch.load + module unwrap + build the model
    sized to the checkpoint's own head + convert + swap the head when widths
    differ. One implementation for every architecture-specific converter."""
    import jax
    import torch

    from tpuddp.models.alexnet import replace_head

    state_dict = torch.load(path, map_location="cpu", weights_only=True)
    if hasattr(state_dict, "state_dict"):
        state_dict = state_dict.state_dict()
    head_out = int(_to_np(state_dict[head_weight_key]).shape[0])

    model = build(head_out)
    init_key, head_key = jax.random.split(jax.random.fold_in(key, salt))
    params, model_state = model.init(
        init_key, jnp.zeros((1, image_size, image_size, 3))
    )
    params, model_state = convert(state_dict, params, model_state)
    if head_out != num_classes:
        params = replace_head(model, params, head_key, num_classes)
    return model, params, model_state


def _conv_w(sd, key):
    return jnp.asarray(np.transpose(_to_np(sd[f"{key}.weight"]), (2, 3, 1, 0)))


def _bn(sd, key):
    params = {
        "scale": jnp.asarray(_to_np(sd[f"{key}.weight"])),
        "bias": jnp.asarray(_to_np(sd[f"{key}.bias"])),
    }
    state = {
        "mean": jnp.asarray(_to_np(sd[f"{key}.running_mean"])),
        "var": jnp.asarray(_to_np(sd[f"{key}.running_var"])),
    }
    return params, state


def _checked(tag: str, new: Dict, expect) -> Dict:
    """Validate EVERY imported tensor's shape against the initialized tree
    before assignment — a width-variant or truncated checkpoint must fail
    here with a named tensor, not deep inside XLA at first apply."""
    for k, arr in new.items():
        if isinstance(arr, dict):
            exp_sub = expect.get(k) if isinstance(expect, dict) else None
            if exp_sub is None:
                raise ValueError(f"{tag}.{k}: unexpected parameter group")
            _checked(f"{tag}.{k}", arr, exp_sub)
            continue
        exp = expect.get(k) if isinstance(expect, dict) else None
        if exp is None or tuple(arr.shape) != tuple(exp.shape):
            raise ValueError(
                f"{tag}.{k}: shape {tuple(arr.shape)} != expected "
                f"{None if exp is None else tuple(exp.shape)}"
            )
    return new


def _recording(state_dict: Mapping[str, object]):
    """Wrap a ``state_dict`` so every key READ is recorded; returns
    ``(mapping, consumed_set)``. Together with :func:`_check_leftover` this
    enforces strictness in the checkpoint->model direction: a converter
    must touch every checkpoint tensor or the import is refused."""
    consumed: set = set()

    class _Recording(dict):
        def __getitem__(self, k):
            consumed.add(k)
            return dict.__getitem__(self, k)

    return _Recording(state_dict), consumed


def _check_leftover(state_dict, consumed, layout: str) -> None:
    leftover = sorted(
        k for k in state_dict
        if k not in consumed and not k.endswith("num_batches_tracked")
    )
    if leftover:
        raise ValueError(
            f"checkpoint has {len(leftover)} tensors this {layout} layout "
            f"does not consume (e.g. {leftover[:3]}); wrong architecture?"
        )


def _convert_resnet_state_dict(
    state_dict: Mapping[str, object], params, model_state, depths, n_convs: int
):
    """Shared torchvision-layout ResNet converter (conv1/bn1 stem,
    layer{1-4}.{block}.conv{1..n_convs}/bn{1..n_convs} (+downsample), fc)
    onto tpuddp's full-stem ResNet Sequential (tpuddp/models/resnet.py).
    ``n_convs=2`` is the BasicBlock family (ResNet-18/34), ``n_convs=3`` the
    Bottleneck family (ResNet-50). Returns ``(params, model_state)`` — unlike
    AlexNet, ResNet carries BatchNorm running statistics in the model state,
    which must ride along for eval-mode parity. Strictness both ways: every
    tensor the model expects must be in the checkpoint, and every checkpoint
    tensor must be consumed."""
    state_dict, consumed = _recording(state_dict)
    new_p, new_s = list(params), list(model_state)
    # stem: Sequential[0]=Conv2d(64,7,s2), [1]=BatchNorm ([2] ReLU, [3] MaxPool)
    new_p[0] = _checked("conv1", {"weight": _conv_w(state_dict, "conv1")}, new_p[0])
    bn_p, bn_s = _bn(state_dict, "bn1")
    new_p[1] = _checked("bn1", bn_p, new_p[1])
    new_s[1] = _checked("bn1(state)", bn_s, new_s[1])
    idx = 4  # first block index in the full-stem Sequential
    for stage, n_blocks in zip((1, 2, 3, 4), depths):
        for block in range(n_blocks):
            t = f"layer{stage}.{block}"
            p, s = {}, {}
            for i in range(1, n_convs + 1):
                p[f"conv{i}"] = {"weight": _conv_w(state_dict, f"{t}.conv{i}")}
                p[f"bn{i}"], s[f"bn{i}"] = _bn(state_dict, f"{t}.bn{i}")
            if f"{t}.downsample.0.weight" in state_dict:
                p["down_conv"] = {"weight": _conv_w(state_dict, f"{t}.downsample.0")}
                p["down_bn"], s["down_bn"] = _bn(state_dict, f"{t}.downsample.1")
            missing = (set(new_p[idx]) - set(p)) | (set(new_s[idx]) - set(s))
            if missing:
                raise ValueError(
                    f"{t}: checkpoint lacks expected tensors {sorted(missing)} "
                    "(truncated file or a different shortcut variant)"
                )
            new_p[idx] = _checked(t, p, new_p[idx])
            new_s[idx] = _checked(f"{t}(state)", s, new_s[idx])
            idx += 1
    # head: GAP at -2 (no params), Linear at -1
    w = _to_np(state_dict["fc.weight"]).T
    b = _to_np(state_dict["fc.bias"])
    if w.shape != tuple(new_p[-1]["weight"].shape):
        raise ValueError(f"fc: shape {w.shape} != {new_p[-1]['weight'].shape}")
    new_p[-1] = {"weight": jnp.asarray(w), "bias": jnp.asarray(b)}
    # Unconsumed tensors mean the checkpoint is a DIFFERENT architecture
    # whose early blocks happen to be shape-compatible (e.g. a ResNet-34
    # imported as ResNet-18 would silently drop half its blocks).
    _check_leftover(
        state_dict, consumed, f"ResNet{depths} ({n_convs}-conv block)"
    )
    return tuple(new_p), tuple(new_s)


def convert_resnet_basic_state_dict(
    state_dict: Mapping[str, object], params, model_state, depths=(2, 2, 2, 2)
):
    """BasicBlock-family converter — (2,2,2,2) is ResNet-18, (3,4,6,3) is
    ResNet-34."""
    return _convert_resnet_state_dict(state_dict, params, model_state, depths, 2)


def convert_resnet_bottleneck_state_dict(
    state_dict: Mapping[str, object], params, model_state, depths=(3, 4, 6, 3)
):
    """Bottleneck-family converter — (3,4,6,3) is ResNet-50, (3,4,23,3)
    ResNet-101, (3,8,36,3) ResNet-152."""
    return _convert_resnet_state_dict(state_dict, params, model_state, depths, 3)


def convert_resnet18_state_dict(state_dict: Mapping[str, object], params, model_state):
    """ResNet-18 ([2,2,2,2]) instantiation of the BasicBlock converter."""
    return convert_resnet_basic_state_dict(
        state_dict, params, model_state, depths=(2, 2, 2, 2)
    )


def convert_resnet34_state_dict(state_dict: Mapping[str, object], params, model_state):
    """ResNet-34 ([3,4,6,3]) instantiation of the BasicBlock converter."""
    return convert_resnet_basic_state_dict(
        state_dict, params, model_state, depths=(3, 4, 6, 3)
    )


def load_pretrained_resnet18(
    path: str, key, num_classes: int = 10, image_size: int = 224,
    space_to_depth: bool = False,
):
    """ResNet-18 analog of :func:`load_pretrained_alexnet`: build the model
    sized to the checkpoint's own head, import weights + BN statistics, swap
    in a fresh ``num_classes`` head when the widths differ."""
    from tpuddp.models.resnet import ResNet18

    return _load_pretrained(
        path, key, num_classes, image_size,
        build=lambda n: ResNet18(num_classes=n, space_to_depth=space_to_depth),
        head_weight_key="fc.weight",
        convert=convert_resnet18_state_dict,
        salt=0x9e8,
    )


def load_pretrained_resnet34(
    path: str, key, num_classes: int = 10, image_size: int = 224,
    space_to_depth: bool = False,
):
    """ResNet-34 analog of :func:`load_pretrained_resnet18` — the [3,4,6,3]
    BasicBlock depths; wrong-depth checkpoints are rejected by the block
    consumption check (missing tensors) or leftover-tensor check."""
    from tpuddp.models.resnet import ResNet34

    return _load_pretrained(
        path, key, num_classes, image_size,
        build=lambda n: ResNet34(num_classes=n, space_to_depth=space_to_depth),
        head_weight_key="fc.weight",
        convert=convert_resnet34_state_dict,
        salt=0x9e9,
    )


def _load_pretrained_bottleneck(name, cls_name, depths, salt):
    """Build the fine-tune loader for one Bottleneck family member (the
    ResNet-50/101/152 analog of :func:`load_pretrained_resnet18`)."""

    def loader(path, key, num_classes=10, image_size=224, space_to_depth=False):
        from tpuddp.models import resnet as resnet_lib

        cls = getattr(resnet_lib, cls_name)
        return _load_pretrained(
            path, key, num_classes, image_size,
            build=lambda n: cls(num_classes=n, space_to_depth=space_to_depth),
            head_weight_key="fc.weight",
            convert=_pt(convert_resnet_bottleneck_state_dict, depths=depths),
            salt=salt,
        )

    loader.__name__ = loader.__qualname__ = f"load_pretrained_{name}"
    loader.__doc__ = (
        f"{cls_name} fine-tune loader — {list(depths)} Bottleneck blocks "
        "(2048-wide head); torchvision-layout checkpoints, head swapped to "
        "``num_classes`` when the widths differ."
    )
    return loader


load_pretrained_resnet50 = _load_pretrained_bottleneck(
    "resnet50", "ResNet50", (3, 4, 6, 3), 0x9eb
)
load_pretrained_resnet101 = _load_pretrained_bottleneck(
    "resnet101", "ResNet101", (3, 4, 23, 3), 0x9ec
)
load_pretrained_resnet152 = _load_pretrained_bottleneck(
    "resnet152", "ResNet152", (3, 8, 36, 3), 0x9ed
)


def load_pretrained_vgg(
    name: str, path: str, key, num_classes: int = 10, image_size: int = 224
):
    """VGG analog of :func:`load_pretrained_alexnet`: build the model sized
    to the checkpoint's own head, import, swap in a fresh ``num_classes``
    head when the widths differ."""
    from tpuddp.models import vgg as vgg_lib

    build_cls = {
        "vgg11": vgg_lib.VGG11, "vgg13": vgg_lib.VGG13,
        "vgg16": vgg_lib.VGG16, "vgg19": vgg_lib.VGG19,
    }[name]
    return _load_pretrained(
        path, key, num_classes, image_size,
        build=lambda n: build_cls(num_classes=n),
        head_weight_key="classifier.6.weight",
        convert=lambda sd, p, s: (convert_vgg_state_dict(name, sd, p), s),
        salt=0x9ea,
    )


def convert_transformer_state_dict(state_dict: Mapping[str, object], params):
    """torch decoder-only transformer ``state_dict`` -> tpuddp
    :class:`~tpuddp.models.transformer.TransformerLM` params.

    Expected torch naming (the layout the parity test's reference module
    uses — plain Linears, not ``nn.MultiheadAttention``, so the math is
    explicit): ``embed.weight``, ``pos.weight``, per block ``blocks.{i}.
    {ln1,ln2}.{weight,bias}``, ``blocks.{i}.attn.{in_proj,out_proj}.
    {weight,bias}``, ``blocks.{i}.mlp.{fc1,fc2}.{weight,bias}``, and
    ``ln_f.{weight,bias}``. Linear weights transpose ``(out, in) -> (in,
    out)``; the joined ``in_proj`` packs ``[q; k; v]`` row blocks exactly as
    tpuddp's ``wqkv`` packs them column-wise, so the transpose alone aligns
    the ``joined_kv`` axis. The LM head is TIED to ``embed.weight`` on both
    sides — a checkpoint with a separate ``head.weight`` is a different
    architecture and is rejected by the leftover check."""
    state_dict, consumed = _recording(state_dict)

    def _lin(key):
        return {
            "weight": jnp.asarray(_to_np(state_dict[f"{key}.weight"]).T),
            "bias": jnp.asarray(_to_np(state_dict[f"{key}.bias"])),
        }

    def _ln(key):
        return {
            "scale": jnp.asarray(_to_np(state_dict[f"{key}.weight"])),
            "bias": jnp.asarray(_to_np(state_dict[f"{key}.bias"])),
        }

    new = dict(params)
    new["embed"] = _checked(
        "embed",
        {"weight": jnp.asarray(_to_np(state_dict["embed.weight"]))},
        params["embed"],
    )
    new["pos"] = _checked(
        "pos",
        {"weight": jnp.asarray(_to_np(state_dict["pos.weight"]))},
        params["pos"],
    )
    blocks = []
    for i, expect in enumerate(params["blocks"]):
        t = f"blocks.{i}"
        in_proj = _lin(f"{t}.attn.in_proj")
        out_proj = _lin(f"{t}.attn.out_proj")
        fc1, fc2 = _lin(f"{t}.mlp.fc1"), _lin(f"{t}.mlp.fc2")
        block = {
            "ln1": _ln(f"{t}.ln1"),
            "attn": {
                "wqkv": in_proj["weight"],
                "bqkv": in_proj["bias"],
                "wo": out_proj["weight"],
                "bo": out_proj["bias"],
            },
            "ln2": _ln(f"{t}.ln2"),
            "mlp": {
                "w1": fc1["weight"],
                "b1": fc1["bias"],
                "w2": fc2["weight"],
                "b2": fc2["bias"],
            },
        }
        blocks.append(_checked(t, block, expect))
    new["blocks"] = tuple(blocks)
    new["ln_f"] = _checked("ln_f", _ln("ln_f"), params["ln_f"])
    _check_leftover(
        state_dict, consumed,
        f"{len(params['blocks'])}-block TransformerLM",
    )
    return new


_PRETRAINED_LOADERS = {
    "alexnet": load_pretrained_alexnet,
    "resnet18": load_pretrained_resnet18,
    "resnet34": load_pretrained_resnet34,
    "resnet50": load_pretrained_resnet50,
    "resnet101": load_pretrained_resnet101,
    "resnet152": load_pretrained_resnet152,
    "vgg11": _pt(load_pretrained_vgg, "vgg11"),
    "vgg13": _pt(load_pretrained_vgg, "vgg13"),
    "vgg16": _pt(load_pretrained_vgg, "vgg16"),
    "vgg19": _pt(load_pretrained_vgg, "vgg19"),
    # aliases of the plain names (models/__init__.py): same model, same
    # parameter layout, same torch checkpoints
    "alexnet_s2d": _pt(load_pretrained_alexnet, space_to_depth=True),
    "resnet18_s2d": _pt(load_pretrained_resnet18, space_to_depth=True),
    "resnet34_s2d": _pt(load_pretrained_resnet34, space_to_depth=True),
    "resnet50_s2d": _pt(load_pretrained_resnet50, space_to_depth=True),
    "resnet101_s2d": _pt(load_pretrained_resnet101, space_to_depth=True),
    "resnet152_s2d": _pt(load_pretrained_resnet152, space_to_depth=True),
}


def pretrained_from_config(training: Mapping[str, object], key=None):
    """Entrypoint-shared ``training.pretrained_path`` handling: validate the
    model name, derive the head-init key from ``training.seed`` when the
    caller has no rank-seeded stream, and load. Returns
    ``(model, params, model_state)``."""
    import jax

    loader = _PRETRAINED_LOADERS.get(str(training["model"]))
    if loader is None:
        raise ValueError(
            "training.pretrained_path supports models "
            f"{sorted(_PRETRAINED_LOADERS)} (got {training['model']!r})"
        )
    if key is None:
        key = jax.random.key(int(training.get("seed") or 0))
    from tpuddp.config import num_classes_from

    return loader(
        str(training["pretrained_path"]),
        key,
        num_classes=num_classes_from(training),
        image_size=int(training.get("image_size") or 224),
    )
