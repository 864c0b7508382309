"""Standard layers. NHWC layout; weights HWIO (the lax.conv native layout on
TPU, so XLA tiles convs straight onto the MXU without transposes).

Initialization follows the same fan-in uniform scheme the reference's model
zoo inherits from torch (U(-1/sqrt(fan_in), 1/sqrt(fan_in)) for Linear/Conv),
so loss curves are comparable at matched seeds-in-distribution.
"""

from __future__ import annotations

import functools
import logging
import math
from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax

from tpuddp.nn.core import Context, Module

IntOr2 = Union[int, Tuple[int, int]]

logger = logging.getLogger(__name__)


def _pair(v: IntOr2) -> Tuple[int, int]:
    return (v, v) if isinstance(v, int) else tuple(v)


class Linear(Module):
    """y = x @ W + b, W: (in, out). ``in_features`` is inferred at init."""

    def __init__(self, out_features: int, use_bias: bool = True, dtype=jnp.float32):
        self.out_features = out_features
        self.use_bias = use_bias
        self.dtype = dtype

    def init(self, key, x):
        in_features = x.shape[-1]
        bound = 1.0 / math.sqrt(in_features)
        wk, bk = jax.random.split(key)
        params = {
            "weight": jax.random.uniform(
                wk, (in_features, self.out_features), self.dtype, -bound, bound
            )
        }
        if self.use_bias:
            params["bias"] = jax.random.uniform(
                bk, (self.out_features,), self.dtype, -bound, bound
            )
        return params, ()

    def apply(self, params, state, x, ctx: Context):
        # params stay f32 masters; compute follows the activation dtype so a
        # bf16 pipeline runs the matmul on the MXU in bf16 (mixed precision)
        y = x @ params["weight"].astype(x.dtype)
        if self.use_bias:
            y = y + params["bias"].astype(x.dtype)
        return y, state

    def divergent_state(self) -> bool:
        return False  # parameters only, no buffers


class Embedding(Module):
    """Token-id lookup table: ``x`` int32 ids of any shape -> ``(*x.shape,
    features)`` rows of ``weight``. torch ``nn.Embedding`` parity: N(0, 1)
    init. The transformer LM head ties to this table (logits = h @ W.T), so
    the weight layout is ``(num_embeddings, features)`` exactly like torch."""

    def __init__(self, num_embeddings: int, features: int, dtype=jnp.float32):
        self.num_embeddings = num_embeddings
        self.features = features
        self.dtype = dtype

    def init(self, key, x):
        params = {
            "weight": jax.random.normal(
                key, (self.num_embeddings, self.features), self.dtype
            )
        }
        return params, ()

    def apply(self, params, state, x, ctx: Context):
        ids = jnp.asarray(x).astype(jnp.int32)
        return jnp.take(params["weight"], ids, axis=0), state

    def divergent_state(self) -> bool:
        return False  # parameters only, no buffers


# A strided convolution is a "thin-channel stem" up to this many input
# channels (image-like inputs: grey, RGB, RGBA): the direct lowering contracts
# only C_in values a tap there, over windows strided along H and W.
_THIN_CHANNELS = 4
# The least stride at which the blocked form is taken. Measured on the v5e
# (PERF.md, PR 25): at s = 4 (AlexNet 11x11/s4, batch 2048) the stem falls
# 16.8 -> 12.2 ms a step; at s = 2 (ResNet 7x7/s2, batch 256) the stem itself
# gains 0.2 ms of 2.6 and the step loses 0.7 ms, because XLA no longer fuses
# the flip's reverse into the stem's producer.
_MIN_STRIDE = 4


def _row_block(kh: int, s: int, p: int) -> int:
    """The divisor ``b >= 2`` of the stride whose blocks of ``b`` rows leave
    the fewest zero rows round the ``kh`` kernel rows once the kernel is
    shifted down by the padding; the larger on a tie. 11 rows at stride 4,
    padding 2: blocks of 2 span 12 rows, blocks of 4 span 16."""

    def rows(b):
        shift = -p % b
        return -(-(shift + kh) // b) * b

    return min((b for b in range(2, s + 1) if s % b == 0), key=lambda b: (rows(b), -b))


def space_to_depth_block(
    in_channels: int, kernel_size, strides, padding
) -> Optional[int]:
    """The block size when a convolution of these static shapes should
    lower through space-to-depth (:func:`_space_to_depth_conv`), else None
    for the direct lowering. Selected: a square stride ``s >= _MIN_STRIDE``,
    integer (symmetric) padding, a kernel larger than the stride in both
    dimensions, and at most ``_THIN_CHANNELS`` input channels: AlexNet's
    11x11/s4 stem. Everything else (ResNet's 7x7/s2 stem, 1x1/s2
    projections, 3x3/s1, wide strided 3x3s, string or per-side padding)
    lowers directly."""
    (kh, kw), (sh, sw) = kernel_size, strides
    if sh != sw or sh < _MIN_STRIDE or not isinstance(padding, int):
        return None
    if min(kh, kw) <= sh or in_channels > _THIN_CHANNELS:
        return None
    return _row_block(kh, sh, padding)


def _space_to_depth_conv(x, w, s: int, p: int, b: int):
    """``conv(x, w)`` at stride ``s`` and symmetric padding ``p``, computed
    as the same sum re-associated: the rows of the input blocked ``b`` at a
    time into its channels, ``(H, W, C) -> (H/b, W, b*C)`` (``b`` divides
    ``s``), the kernel shifted down by the padding inside a window of whole
    blocks (zero taps above and below) and blocked to match, one convolution
    at stride ``s/b`` along ``H`` that keeps stride ``s`` and padding ``p``
    along ``W``.

    Only ``H`` is blocked, and the input is never padded: XLA lays a
    thin-channel activation out with ``H`` as the major dimension (batch in
    the lanes, ``W`` in the sublanes), where splitting ``H`` and merging the
    split into ``C`` moves no data, while padding or blocking ``W`` costs a
    pass over the input each (PERF.md, PR 25). The convolution pads whole
    blocks itself. ``w`` keeps the ``(kh, kw, C, F)`` layout; its blocked
    view and the un-blocking of its gradient are small reshapes."""
    n, h, wd, c = x.shape
    kh, kw, _, f = w.shape
    oh = (h + 2 * p - kh) // s + 1
    top = -(-p // b)  # blocks of padding above
    shift = top * b - p  # zero taps above the kernel
    kb = -(-(shift + kh) // b)  # blocks the shifted kernel spans
    wb = (
        jnp.pad(w, ((shift, kb * b - kh - shift), (0, 0), (0, 0), (0, 0)))
        .reshape(kb, b, kw, c, f)
        .transpose(0, 2, 1, 3, 4)
        .reshape(kb, kw, b * c, f)
    )
    bh = -(-h // b)
    if h % b:  # the stems' own sizes (224) are whole blocks
        x = jnp.pad(x, ((0, 0), (0, bh * b - h), (0, 0), (0, 0)))
    xb = x.reshape(n, bh, b, wd, c).transpose(0, 1, 3, 2, 4).reshape(n, bh, wd, b * c)
    step = s // b
    bottom = (oh - 1) * step + kb - top - bh  # blocks below the last window
    if bottom < 0:
        xb, bottom = xb[:, : bh + bottom], 0
    return lax.conv_general_dilated(
        xb, wb, window_strides=(step, s), padding=[(top, bottom), (p, p)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


def _lowering_name(block: Optional[int]) -> str:
    return "direct" if block is None else f"space-to-depth, block {block}"


@functools.lru_cache(maxsize=None)
def _log_lowering(kind, features, kernel_size, strides, in_channels, block) -> None:
    """One line per distinct layer and choice, the first time it is traced:
    ``Conv2d(64, 11x11/s4, C_in=3): space-to-depth, block 4``."""
    (kh, kw), (sh, sw) = kernel_size, strides
    stride = f"s{sh}" if sh == sw else f"s{sh}x{sw}"
    logger.log(
        logging.DEBUG if block is None else logging.INFO,
        "%s(%d, %dx%d/%s, C_in=%d): %s",
        kind, features, kh, kw, stride, in_channels, _lowering_name(block),
    )


class Conv2d(Module):
    """2-D convolution, NHWC / HWIO. ``padding`` is 'SAME', 'VALID', or an int
    (symmetric, torch-style).

    A widely strided convolution over very few input channels (the AlexNet
    stem) lowers through space-to-depth; the choice is made where the
    layer is traced, from its own static shapes (:func:`space_to_depth_block`),
    and :func:`conv_lowering` states it. Parameters, init and results are
    those of the direct form."""

    def __init__(
        self,
        features: int,
        kernel_size: IntOr2,
        strides: IntOr2 = 1,
        padding: Union[str, int, Sequence[Tuple[int, int]]] = 0,
        use_bias: bool = True,
        dtype=jnp.float32,
    ):
        self.features = features
        self.kernel_size = _pair(kernel_size)
        self.strides = _pair(strides)
        self.padding = padding
        self.use_bias = use_bias
        self.dtype = dtype

    def _pad_arg(self):
        if isinstance(self.padding, str):
            return self.padding
        if isinstance(self.padding, int):
            p = self.padding
            return [(p, p), (p, p)]
        return list(self.padding)

    def init(self, key, x):
        in_ch = x.shape[-1]
        kh, kw = self.kernel_size
        fan_in = in_ch * kh * kw
        bound = 1.0 / math.sqrt(fan_in)
        wk, bk = jax.random.split(key)
        params = {
            "weight": jax.random.uniform(
                wk, (kh, kw, in_ch, self.features), self.dtype, -bound, bound
            )
        }
        if self.use_bias:
            params["bias"] = jax.random.uniform(
                bk, (self.features,), self.dtype, -bound, bound
            )
        return params, ()

    def _block(self, in_channels: int) -> Optional[int]:
        """Space-to-depth block size for this input, None for direct."""
        return space_to_depth_block(
            in_channels, self.kernel_size, self.strides, self.padding
        )

    def apply(self, params, state, x, ctx: Context):
        block = self._block(x.shape[-1])
        _log_lowering(
            type(self).__name__, self.features, self.kernel_size, self.strides,
            x.shape[-1], block,
        )
        w = params["weight"].astype(x.dtype)
        if block is None:
            y = lax.conv_general_dilated(
                x,
                w,
                window_strides=self.strides,
                padding=self._pad_arg(),
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
            )
        else:
            y = _space_to_depth_conv(x, w, self.strides[0], self.padding, block)
        if self.use_bias:
            y = y + params["bias"].astype(y.dtype)
        return y, state

    def divergent_state(self) -> bool:
        return False  # parameters only, no buffers


class SpaceToDepthConv2d(Conv2d):
    """:class:`Conv2d` forced through the space-to-depth lowering at any
    channel count and kernel size: what the exactness and fuzz tests hold
    against the direct form. ``Conv2d`` takes this lowering by itself where
    it was measured to pay, so models build ``Conv2d``. Requires a
    square integer stride and integer symmetric padding."""

    def __init__(self, features, kernel_size, strides, padding=0, use_bias=True, dtype=jnp.float32):
        super().__init__(features, kernel_size, strides, padding, use_bias, dtype)
        if self.strides[0] != self.strides[1] or self.strides[0] < 2:
            raise ValueError(
                f"SpaceToDepthConv2d needs a square stride >= 2 (its rows "
                f"are blocked by a divisor of it); got {self.strides}"
            )
        if not isinstance(padding, int):
            raise ValueError(
                "SpaceToDepthConv2d supports integer (symmetric) padding only"
            )

    def _block(self, in_channels: int) -> int:
        return _row_block(self.kernel_size[0], self.strides[0], self.padding)


def conv_lowering(layer: Conv2d, in_channels: int) -> str:
    """How ``layer`` lowers over ``in_channels`` input channels: ``"direct"``
    or ``"space-to-depth, block <s>"``."""
    return _lowering_name(layer._block(in_channels))


class _Pool2d(Module):
    def __init__(self, window: IntOr2, strides: Optional[IntOr2] = None, padding: Union[str, int] = 0):
        self.window = _pair(window)
        self.strides = _pair(strides) if strides is not None else self.window
        self.padding = padding

    def _pad_arg(self):
        if isinstance(self.padding, str):
            return self.padding
        p = self.padding
        return [(0, 0), (p, p), (p, p), (0, 0)]


class MaxPool2d(_Pool2d):
    def apply(self, params, state, x, ctx: Context):
        init_val = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min
        y = lax.reduce_window(
            x,
            init_val,
            lax.max,
            (1, *self.window, 1),
            (1, *self.strides, 1),
            self._pad_arg(),
        )
        return y, state


class AvgPool2d(_Pool2d):
    def apply(self, params, state, x, ctx: Context):
        wh, ww = self.window
        y = lax.reduce_window(
            x, 0.0, lax.add, (1, wh, ww, 1), (1, *self.strides, 1), self._pad_arg()
        )
        return y / (wh * ww), state


class AdaptiveAvgPool2d(Module):
    """torch-style adaptive average pooling to a fixed (H_out, W_out).

    Bin i covers [floor(i*N/M), ceil((i+1)*N/M)). When the bins are UNIFORM
    (same size and stride — e.g. AlexNet's 13->6, or any divisible shape)
    the layer lowers to a plain ``reduce_window`` average, whose VJP is far
    cheaper than the general path's (see :meth:`_uniform`; measured -0.08
    ms/step on AlexNet b128). Ragged bins fall back to a 2-D integral image
    (cumsum) with *static* gather indices: four corner lookups + area
    divide. Both paths are fully shape-static; no dynamic control flow.
    """

    def __init__(self, output_size: IntOr2):
        self.output_size = _pair(output_size)

    @staticmethod
    def _bounds_list(n_in: int, n_out: int):
        starts = [(i * n_in) // n_out for i in range(n_out)]
        ends = [-(-((i + 1) * n_in) // n_out) for i in range(n_out)]  # ceil div
        return starts, ends

    @classmethod
    def _bounds(cls, n_in: int, n_out: int):
        starts, ends = cls._bounds_list(n_in, n_out)
        return jnp.array(starts), jnp.array(ends)

    @classmethod
    def _uniform(cls, n_in: int, n_out: int):
        """If every bin has the same size and stride, return (window, stride)
        — the bins then ARE a plain average pool (e.g. AlexNet's 13->6: bins
        [0,3) [2,5) ... = window 3 stride 2), whose reduce_window lowering
        and VJP are far cheaper than the integral-image gather (no f32 cumsum
        chain in the backward). None when the bins are ragged or upsampling
        (n_out > n_in repeats bins: stride 0 is not a pool)."""
        starts, ends = cls._bounds_list(n_in, n_out)
        sizes = {e - s for s, e in zip(starts, ends)}
        strides = {b - a for a, b in zip(starts, starts[1:])} or {1}
        if len(sizes) == 1 and len(strides) == 1 and 0 not in strides:
            return sizes.pop(), strides.pop()
        return None

    def apply(self, params, state, x, ctx: Context):
        n, h, w, c = x.shape
        oh, ow = self.output_size
        uh, uw = self._uniform(h, oh), self._uniform(w, ow)
        if uh is not None and uw is not None:
            (kh, sh), (kw, sw) = uh, uw
            y = lax.reduce_window(
                x.astype(jnp.float32), 0.0, lax.add,
                (1, kh, kw, 1), (1, sh, sw, 1), "VALID",
            )
            return (y / (kh * kw)).astype(x.dtype), state
        in_dtype = x.dtype
        x = x.astype(jnp.float32)  # integral-image sums need f32 accumulation
        # integral image with a leading zero row/col: I[i, j] = sum(x[:i, :j])
        ii = jnp.cumsum(jnp.cumsum(x, axis=1), axis=2)
        ii = jnp.pad(ii, ((0, 0), (1, 0), (1, 0), (0, 0)))
        hs, he = self._bounds(h, oh)
        ws, we = self._bounds(w, ow)
        # window sum via 4 corners, broadcast over output grid
        a = ii[:, he[:, None], we[None, :], :]
        b = ii[:, he[:, None], ws[None, :], :]
        c_ = ii[:, hs[:, None], we[None, :], :]
        d = ii[:, hs[:, None], ws[None, :], :]
        sums = a - b - c_ + d
        areas = ((he - hs)[:, None] * (we - ws)[None, :]).astype(jnp.float32)
        return (sums / areas[None, :, :, None]).astype(in_dtype), state


class ReLU(Module):
    def apply(self, params, state, x, ctx: Context):
        return jax.nn.relu(x), state


class Flatten(Module):
    def apply(self, params, state, x, ctx: Context):
        return x.reshape(x.shape[0], -1), state


class Dropout(Module):
    """Inverted dropout; active only when ``ctx.train`` and ``ctx.rng`` given.
    Per-replica masks come from the step fn folding ``lax.axis_index`` into the
    key (tpuddp.seeding.fold_in_axis_index)."""

    def __init__(self, p: float = 0.5):
        if not 0.0 <= p < 1.0:
            raise ValueError(f"dropout p must be in [0, 1), got {p}")
        self.p = p

    def apply(self, params, state, x, ctx: Context):
        if not ctx.train or self.p == 0.0:
            return x, state
        if ctx.rng is None:
            raise ValueError("Dropout in train mode requires ctx.rng")
        keep = 1.0 - self.p
        mask = jax.random.bernoulli(ctx.rng, keep, x.shape)
        return jnp.where(mask, x / keep, 0.0).astype(x.dtype), state
