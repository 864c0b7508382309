"""The chunked gated-delta-rule scan's share of its roofline: the operations
and bytes its forward and backward need (``benchmark/flops``: ``scan_cost``,
the larger of the two bounds) over the device time under the DeltaNet layers'
``scan`` scope, recomputation included in the time and not in the need."""

from benchmark import cells

LAYER = "linear attention (nn/deltanet.py, models/hybrid_moe.py)"
UNIT = "%"
MOVES = "samples_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    shared = cells.load_module("layer_metrics", "_token_layers", run["cell"].root)
    if shared.seconds(run, kind=shared.DELTANET, part="scan") is None:
        return None
    config = run["cell"].config
    ops, nbytes = shared.flops_module(run).scan_cost(config, run["window"]["samples"] / run["cell"].chips)
    layers = shared.layers_of(config, full_attention=False)
    return shared.roofline_pct(run, (layers * ops, layers * nbytes), kind=shared.DELTANET, part="scan")
