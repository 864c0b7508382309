"""Device time a step in the gated short-convolution operators (the
projection to three streams, the gates and the taps, the projection back),
forward, backward and recomputation, under the ``in_proj``, ``conv`` and
``out_proj`` scopes of the ``<i>_ShortConv`` layers."""

from benchmark import cells

LAYER = "short convolution (nn/sequence.py, models/hybrid_moe.py)"
UNIT = "ms/step"
MOVES = "samples_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    layers = cells.load_module("layer_metrics", "_conv_layers", run["cell"].root)
    return layers.ms_per_step(run, kinds=(layers.SHORT_CONV,), parts=layers.CONV_PARTS)
