"""Device time a step in the dense feed-forward of the looped model's layers
(norm, the SwiGLU's three products 8,192 tokens at a time, the norm after
them, the residual), every pass of the stack, forward, backward and
recomputation, under the ``mlp`` scope of the layers inside ``passes``."""

from benchmark import cells

LAYER = "dense feed-forward (models/hybrid_moe.py)"
UNIT = "ms/step"
MOVES = "samples_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    layers = cells.load_module("layer_metrics", "_looped_layers", run["cell"].root)
    return layers.ms_per_step(run, layers.pass_seconds(run, ("mlp",)))
