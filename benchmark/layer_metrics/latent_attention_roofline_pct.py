"""Causal attention over the latent-attention model's ungrouped heads (scores
``nope + rope`` wide, values ``v_head_dim`` wide) as a share of its roofline:
the operations and bytes that scores and values over the causal triangle need
in every layer a token passes (the prediction module's among them), forward
and backward (``benchmark/flops``: ``attention_cost``; the larger of the two
bounds), over the device time under those layers' ``attention`` scope. Blocks
a lowering computes and masks and recomputation count in the time alone."""

from benchmark import cells

LAYER = "softmax attention (nn/sequence.py, models/hybrid_moe.py)"
UNIT = "%"
MOVES = "samples_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    layers = cells.load_module("layer_metrics", "_latent_layers", run["cell"].root)
    s = layers.seconds(run, parts=("attention",))
    if s is None:
        return None
    config, flops = run["cell"].config, layers.flops_module(run)
    ops, nbytes = flops.attention_cost(config, layers.window_tokens(run))
    n = flops.attention_layers(config)
    return layers.windowed(run).roofline_pct(run, (n * ops, n * nbytes), s)
