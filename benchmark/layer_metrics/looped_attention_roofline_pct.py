"""Causal attention over the looped model's ungrouped heads of 128 as a share
of its roofline: the operations and bytes that scores and values over the
causal triangle need for every application of a layer (layers times passes),
forward and backward (``benchmark/flops``: ``attention_cost``; the larger of
the two bounds), over the device time under the looped layers' ``attention``
scope. Blocks a lowering computes and masks and recomputation count in the
time alone."""

from benchmark import cells

LAYER = "softmax attention (nn/sequence.py, models/hybrid_moe.py)"
UNIT = "%"
MOVES = "samples_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    layers = cells.load_module("layer_metrics", "_looped_layers", run["cell"].root)
    s = layers.pass_seconds(run, ("attention",))
    if s is None:
        return None
    config, flops = run["cell"].config, layers.flops_module(run)
    ops, nbytes = flops.attention_cost(config, layers.window_tokens(run))
    n = flops.layer_applications(config)
    return layers.roofline_pct(run, (n * ops, n * nbytes), s)
