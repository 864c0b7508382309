"""Largest over mean tokens at any of ALL the router's experts, a layer and a
step, in the latent-attention model, from the program's own counter
(``moe_router_tokens_max`` of ``tpuddp/nn/moe.py``, summed over the window's
sparse layers, the prediction module's among them, and steps by the feed):
what the selection biases balance. 1 is an even load. The mean is the window's
tokens times the experts a token over the router's width."""

from benchmark import cells

LAYER = "expert layer (nn/moe.py)"
UNIT = "ratio"
MOVES = "samples_per_s_per_chip"
SOURCE = "program_counter"


def read(run):
    counters, tokens = run["window"]["counters"], run["window"]["samples"]
    largest = counters.get("moe_router_tokens_max")
    if largest is None or not tokens:
        return None
    config = run["cell"].config
    layers = cells.load_module("flops", run["cell"].config_name, run["cell"].root).sparse_layers(config)
    mean = tokens * config["num_experts_per_tok"] * layers / config["deployment"]["experts_published"]
    return largest / mean
