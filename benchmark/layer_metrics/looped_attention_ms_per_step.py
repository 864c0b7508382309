"""Device time a step in the attention operator of the looped model's layers
(projections, rotary, causal attention in whichever lowering
``seq.causal_attention`` picked; no per-head norm), every pass of the stack,
forward, backward and recomputation, under the ``qkv``, ``attention`` and
``o_proj`` scopes of the ``<i>_FullAttention`` layers inside ``passes``."""

from benchmark import cells

LAYER = "softmax attention (nn/sequence.py, models/hybrid_moe.py)"
UNIT = "ms/step"
MOVES = "samples_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    layers = cells.load_module("layer_metrics", "_looped_layers", run["cell"].root)
    return layers.ms_per_step(run, layers.pass_seconds(run, layers.ATTENTION_PARTS))
