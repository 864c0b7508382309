"""Device time a step in the dense feed-forward of the leading layers (norm,
the SwiGLU's three products, the residual), forward, backward and
recomputation, under the ``mlp`` scope of the convolution-and-attention
model's layers."""

from benchmark import cells

LAYER = "dense feed-forward (models/hybrid_moe.py)"
UNIT = "ms/step"
MOVES = "samples_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    layers = cells.load_module("layer_metrics", "_conv_layers", run["cell"].root)
    return layers.ms_per_step(run, kinds=(layers.SHORT_CONV,), parts=("mlp",))
