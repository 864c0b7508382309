"""The index scores as a share of their roofline: the operations and bytes
that ``sum_j w relu(qI . kI)`` over the causal triangle needs in every layer,
forward and backward (``benchmark/flops``: ``index_scores_cost``; the larger
of the two bounds), over the device time under the ``index_scores`` scope of
every ``<i>_SparseAttention`` layer. Blocks a lowering computes above the
diagonal, scores it writes and reads back, and recomputation count in the
time alone."""

from benchmark import cells

LAYER = "softmax attention (nn/sequence.py, models/hybrid_moe.py)"
UNIT = "%"
MOVES = "samples_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    layers = cells.load_module("layer_metrics", "_sparse_layers", run["cell"].root)
    s = layers.seconds(run, ("index_scores",))
    if s is None:
        return None
    config = run["cell"].config
    ops, nbytes = layers.flops_module(run).index_scores_cost(config, layers.window_tokens(run))
    n = config["num_hidden_layers"]
    return layers.roofline_pct(run, (n * ops, n * nbytes), s)
