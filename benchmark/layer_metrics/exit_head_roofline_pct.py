"""The exits' head products as a share of their roofline: what one product of
every pass's states with the head needs forward and twice that backward
(``benchmark/flops``: ``exit_head_cost``; the larger of the compute and the
HBM bound), over the device time under ``exits``. The logsumexp, the gate and
the chunks' recomputation count in the time alone."""

from benchmark import cells

LAYER = "looped stack and its exits (models/hybrid_moe.py, nn/sequence.py)"
UNIT = "%"
MOVES = "samples_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    layers = cells.load_module("layer_metrics", "_looped_layers", run["cell"].root)
    s = layers.exit_seconds(run)
    if s is None:
        return None
    cost = layers.flops_module(run).exit_head_cost(run["cell"].config, layers.window_tokens(run))
    return layers.roofline_pct(run, cost, s)
