"""The short convolution's gates and taps' share of their roofline: the bytes
and operations that ``b * u``, the taps and ``c * z`` need, forward and
backward (``benchmark/flops``: ``short_conv_cost``, the larger of the two
bounds: the HBM's), over the device time under the convolution layers'
``conv`` scope, recomputation included in the time and not in the need."""

from benchmark import cells

LAYER = "short convolution (nn/sequence.py, models/hybrid_moe.py)"
UNIT = "%"
MOVES = "samples_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    layers = cells.load_module("layer_metrics", "_conv_layers", run["cell"].root)
    s = layers.seconds(run, kinds=(layers.SHORT_CONV,), parts=("conv",))
    if s is None:
        return None
    config = run["cell"].config
    ops, nbytes = layers.shared(run).flops_module(run).short_conv_cost(
        config, run["window"]["samples"] / run["cell"].chips
    )
    n = layers.layers_of(config, "conv")
    return layers.windowed(run).roofline_pct(run, (n * ops, n * nbytes), s)
