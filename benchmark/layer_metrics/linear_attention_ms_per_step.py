"""Device time a step in the Gated DeltaNet mixers (projections, convolution,
chunked scan, gated norm), forward, backward and recomputation, under the
``<i>_GatedDeltaNet`` scopes outside their expert layers."""

from benchmark import cells

LAYER = "linear attention (nn/deltanet.py, models/hybrid_moe.py)"
UNIT = "ms/step"
MOVES = "samples_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    shared = cells.load_module("layer_metrics", "_token_layers", run["cell"].root)
    return shared.ms_per_step(run, kind=shared.DELTANET)
