"""Largest over mean tokens at any of ALL the router's experts, a layer and a
step, from the program's own counter (``moe_router_tokens_max`` of
``tpuddp/nn/moe.py``, summed over the window's sparse layers and steps by the
feed): what the selection bias balances. 1 is an even load. The mean is the
window's tokens times the experts a token over the router's width."""

from benchmark import cells

LAYER = "expert layer (nn/moe.py)"
UNIT = "ratio"
MOVES = "samples_per_s_per_chip"
SOURCE = "program_counter"


def read(run):
    largest, tokens = run["window"]["counters"].get("moe_router_tokens_max"), run["window"]["samples"]
    if largest is None or not tokens:
        return None
    config = run["cell"].config
    layers = cells.load_module("layer_metrics", "_conv_layers", run["cell"].root)
    mean = tokens * config["num_experts_per_tok"] * layers.sparse_layers(config) / config["deployment"]["experts_published"]
    return largest / mean
