"""Device time a step in attention under the selection and in the indexer's
objective, which reads attention's own probabilities: scores, softmax and
values over a query's selected keys in whichever lowering
``seq.sparse_attention_rows`` picked, the heads' mean probability, the KL to
the indexer's distribution and its gradient; forward, backward and
recomputation, under the ``attention`` and ``indexer_loss`` scopes of every
``<i>_SparseAttention`` layer."""

from benchmark import cells

LAYER = "softmax attention (nn/sequence.py, models/hybrid_moe.py)"
UNIT = "ms/step"
MOVES = "samples_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    layers = cells.load_module("layer_metrics", "_sparse_layers", run["cell"].root)
    return layers.ms_per_step(run, ("attention", "indexer_loss"))
