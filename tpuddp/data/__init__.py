"""Data layer: datasets, host loaders, and device-side transforms."""

from typing import Any, Dict, Sequence, Tuple

from tpuddp.data.loader import (  # noqa: F401
    DataLoader,
    PrefetchLoader,
    ShardedDataLoader,
)
from tpuddp.data.synthetic import SyntheticClassification  # noqa: F401


def load_datasets_for(training: Dict[str, Any], synthetic_fallback: bool = True):
    """(train, test) datasets for ``training.dataset`` — the dataset-dispatch
    layer both entrypoints share (the reference hardcodes CIFAR-10,
    data_and_toy_model.py:8-38; tpuddp adds ``digits`` — real offline data —
    ``synthetic`` for CI/benchmarks, and ``markov_tokens``, a seeded token
    stream for the language models, data/tokens.py)."""
    name = str(training.get("dataset") or "cifar10")
    if name == "cifar10":
        from tpuddp.data import cifar10

        kwargs = {}
        if training.get("synthetic_n"):
            kwargs["synthetic_n"] = tuple(training["synthetic_n"])
        return cifar10.load_datasets(
            training.get("data_root", "./data"),
            synthetic_fallback=synthetic_fallback,
            **kwargs,
        )
    if name == "digits":
        from tpuddp.data import digits

        return digits.load_datasets()
    if name == "synthetic":
        from tpuddp.data.synthetic import synthetic_uint8_datasets

        n = tuple(training.get("synthetic_n") or (2048, 512))
        return synthetic_uint8_datasets(n[0], n[1])
    if name == "markov_tokens":
        from tpuddp.data.tokens import markov_token_datasets

        n = tuple(training.get("synthetic_n") or (256, 64))
        return markov_token_datasets(
            n[0], n[1], int(training.get("seq_len") or 64), int(training["num_classes"]),
            seed=int(training.get("seed") or 0),
        )
    raise ValueError(
        f"unknown training.dataset {name!r}; one of cifar10, digits, synthetic, markov_tokens"
    )


def flip_for(training: Dict[str, Any]) -> bool:
    """Horizontal-flip augmentation setting: explicit ``training.flip`` wins;
    the default follows the dataset (CIFAR photos are flip-invariant,
    data_and_toy_model.py:15; handwritten digits are not)."""
    f = training.get("flip")
    if f is not None:
        return bool(f)
    return str(training.get("dataset") or "cifar10") != "digits"


def compute_dtype_for(training: Dict[str, Any]):
    """Activation dtype for the device-side transforms: ``bfloat16`` is the
    TPU mixed-precision mode (f32 master params, bf16 activations on the
    MXU; see BASELINE.md's bf16-vs-f32 analysis)."""
    import jax.numpy as jnp

    name = str(training.get("compute_dtype") or "float32")
    table = {"float32": jnp.float32, "bfloat16": jnp.bfloat16, "bf16": jnp.bfloat16}
    if name not in table:
        raise ValueError(
            f"unknown training.compute_dtype {name!r}; one of float32, bfloat16"
        )
    return table[name]


def norm_stats_for(training: Dict[str, Any]) -> Tuple[Sequence[float], Sequence[float]]:
    """Per-dataset normalization (mean, std) for the device-side transforms
    (the reference bakes CIFAR constants into its torchvision pipeline,
    data_and_toy_model.py:17,25)."""
    name = str(training.get("dataset") or "cifar10")
    if name == "digits":
        from tpuddp.data.digits import DIGITS_MEAN, DIGITS_STD

        return DIGITS_MEAN, DIGITS_STD
    from tpuddp.data.cifar10 import CIFAR10_MEAN, CIFAR10_STD

    return CIFAR10_MEAN, CIFAR10_STD


__all__ = [
    "DataLoader",
    "PrefetchLoader",
    "ShardedDataLoader",
    "SyntheticClassification",
    "load_datasets_for",
    "norm_stats_for",
    "flip_for",
    "compute_dtype_for",
]
