"""Multiply-accumulate counting from shapes, shared by the configurations'
FLOPs files. One multiply-accumulate is two operations. A training step
needs, for each convolution or matrix product, the forward product, the
gradient with respect to its weights, and, unless its input is the image
itself, the gradient with respect to its input: each as many operations as
the forward. Elementwise work, pooling, normalisation, the resize, the loss
and the optimizer are not counted, and nothing recomputed ever is.
"""

from __future__ import annotations


def conv_out(size: int, kernel: int, stride: int, pad: int) -> int:
    return (size + 2 * pad - kernel) // stride + 1


def conv_macs(out_hw: int, kernel: int, c_in: int, c_out: int) -> int:
    return out_hw * out_hw * kernel * kernel * c_in * c_out


def train_flops(layers) -> float:
    """``layers``: ``(macs, needs_input_grad)`` per product, in any order."""
    return float(sum(2 * macs * (3 if needs_dx else 2) for macs, needs_dx in layers))
