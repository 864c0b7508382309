"""Device time a step in the gated softmax-attention mixers (projections,
rotary, blockwise causal attention, output gate), forward, backward and
recomputation, under the ``<i>_GatedAttention`` scopes outside their expert
layers."""

from benchmark import cells

LAYER = "softmax attention (nn/sequence.py, models/hybrid_moe.py)"
UNIT = "ms/step"
MOVES = "samples_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    shared = cells.load_module("layer_metrics", "_token_layers", run["cell"].root)
    return shared.ms_per_step(run, kind=shared.ATTENTION)
