"""Causal attention over heads of 64 as a share of its roofline: the
operations and bytes that scores and values over the causal triangle need at
the published head width, forward and backward (``benchmark/flops``:
``attention_cost``; the larger of the two bounds), over the device time under
the attention layers' ``attention`` scope. Lanes a lowering fills with zeros,
blocks it computes and masks, and recomputation count in the time alone."""

from benchmark import cells

LAYER = "softmax attention (nn/sequence.py, models/hybrid_moe.py)"
UNIT = "%"
MOVES = "samples_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    layers = cells.load_module("layer_metrics", "_conv_layers", run["cell"].root)
    s = layers.seconds(run, kinds=(layers.FULL,), parts=("attention",))
    if s is None or not layers.is_this_model(run):
        return None
    config = run["cell"].config
    ops, nbytes = layers.shared(run).flops_module(run).attention_cost(
        config, run["window"]["samples"] / run["cell"].chips
    )
    n = layers.layers_of(config, "full_attention")
    return layers.windowed(run).roofline_pct(run, (n * ops, n * nbytes), s)
