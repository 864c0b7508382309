"""Device time a step in the indexer of the sparse-attention layers: its three
projections from the layer's normed input (queries a head, the one key head
under its LayerNorm, a weight a head; rotary on both) and the index scores
``sum_j w relu(qI . kI)`` of every query against every earlier key, forward,
backward and recomputation, under the ``index_proj`` and ``index_scores``
scopes of every ``<i>_SparseAttention`` layer."""

from benchmark import cells

LAYER = "softmax attention (nn/sequence.py, models/hybrid_moe.py)"
UNIT = "ms/step"
MOVES = "samples_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    layers = cells.load_module("layer_metrics", "_sparse_layers", run["cell"].root)
    return layers.ms_per_step(run, ("index_proj", "index_scores"))
