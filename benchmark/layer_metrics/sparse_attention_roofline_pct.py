"""Attention under the selection as a share of its roofline: the operations
and bytes that scores and values over the SELECTED (query, key) pairs need,
forward and backward (``benchmark/flops``: ``sparse_attention_cost``; the
pairs are the window's own, the ``index_selected_pairs`` counter summed over
steps and layers; the larger of the two bounds), over the device time under
the ``attention`` and ``indexer_loss`` scopes of every ``<i>_SparseAttention``
layer (``sparse_attention_ms_per_step``'s time). A lowering that scores whole
blocks under the mask, or that computes the scores a second time for the
objective, reads low; one that skips what was not selected can approach 100%
and not pass it. Recomputation counts in the time alone."""

from benchmark import cells

LAYER = "softmax attention (nn/sequence.py, models/hybrid_moe.py)"
UNIT = "%"
MOVES = "samples_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    layers = cells.load_module("layer_metrics", "_sparse_layers", run["cell"].root)
    s, pairs = layers.seconds(run, ("attention", "indexer_loss")), layers.counter(run, "index_selected_pairs")
    if s is None or pairs is None:
        return None
    config = run["cell"].config
    tokens = layers.window_tokens(run) * config["num_hidden_layers"]  # a token's rows are read in every layer
    return layers.roofline_pct(run, layers.flops_module(run).sparse_attention_cost(config, tokens, pairs), s)
