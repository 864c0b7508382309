"""Device time a step in the selection of the sparse-attention layers: from a
row's index scores to its 2,048 best earlier keys, exactly (the threshold a
bit at a time, ties to the earlier key), as a mask; forward and recomputation
(it has no gradient), under the ``index_select`` scope of every
``<i>_SparseAttention`` layer. No product, so no roofline share: this is time
the mathematics' count of operations does not hold."""

from benchmark import cells

LAYER = "softmax attention (nn/sequence.py, models/hybrid_moe.py)"
UNIT = "ms/step"
MOVES = "samples_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    layers = cells.load_module("layer_metrics", "_sparse_layers", run["cell"].root)
    return layers.ms_per_step(run, ("index_select",))
