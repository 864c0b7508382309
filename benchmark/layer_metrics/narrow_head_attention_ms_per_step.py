"""Device time a step in the attention operator over heads of 64
(projections, per-head norms, rotary, causal attention in whichever lowering
``seq.causal_attention`` picked), forward, backward and recomputation, under
the ``qkv``, ``attention`` and ``o_proj`` scopes of the convolution-and-attention
model's ``<i>_FullAttention`` layers."""

from benchmark import cells

LAYER = "softmax attention (nn/sequence.py, models/hybrid_moe.py)"
UNIT = "ms/step"
MOVES = "samples_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    layers = cells.load_module("layer_metrics", "_conv_layers", run["cell"].root)
    if not layers.is_this_model(run):
        return None  # another family's full-attention layers
    return layers.ms_per_step(run, kinds=(layers.FULL,), parts=layers.ATTENTION_PARTS)
