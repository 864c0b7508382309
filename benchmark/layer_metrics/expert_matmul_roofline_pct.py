"""The grouped expert products' share of their roofline: the operations and
bytes that the window's own count of assignments at held experts needs
(``moe_expert_tokens_held``; ``benchmark/flops``: ``expert_matmul_cost``,
the larger of the two bounds), over the device time of the compiler's
``ragged-dot-*`` kernels and of what runs under the expert layers' ``experts``
scope around them."""

from benchmark import cells

LAYER = "expert layer (nn/moe.py)"
UNIT = "%"
MOVES = "samples_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    shared = cells.load_module("layer_metrics", "_token_layers", run["cell"].root)
    held = run["window"]["counters"].get("moe_expert_tokens_held")
    steps = run["window"]["steps"]
    if not held or not steps or shared.seconds(run, moe=True, part="experts") is None:
        return None
    config = run["cell"].config
    layers = config["num_hidden_layers"]
    # the weights are read once a layer a step; the pairs are the window's own
    ops, nbytes = shared.flops_module(run).expert_matmul_cost(config, held / (layers * steps))
    return shared.roofline_pct(
        run, (layers * steps * ops, layers * steps * nbytes),
        extra_seconds=shared.expert_kernel_seconds(run), moe=True, part="experts",
    )
