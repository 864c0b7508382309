"""The grouped expert products' share of their roofline in the
convolution-and-attention model: the operations and bytes that the window's
own count of assignments at held experts needs (``moe_expert_tokens_held``;
``benchmark/flops``: ``expert_matmul_cost``, the larger of the two bounds),
over the device time of the grouped-product kernels, in whichever lowering
``nn/moe.py`` picked (the compiler's ``ragged-dot-*`` or the Pallas ``gmm`` and
``tgmm``, forward, recomputed and backward), and of what runs under the
expert layers' ``experts`` scope around them."""

from benchmark import cells

LAYER = "expert layer (nn/moe.py)"
UNIT = "%"
MOVES = "samples_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    layers = cells.load_module("layer_metrics", "_conv_layers", run["cell"].root)
    held = run["window"]["counters"].get("moe_expert_tokens_held")
    steps = run["window"]["steps"]
    if not held or not steps or not layers.is_this_model(run):
        return None
    scoped = layers.seconds(run, moe=True, parts=("experts",))
    if scoped is None:
        return None
    config, shared = run["cell"].config, layers.shared(run)
    n = layers.sparse_layers(config)
    # the weights are read once a layer a step; the pairs are the window's own
    ops, nbytes = shared.flops_module(run).expert_matmul_cost(config, held / (n * steps))
    return layers.windowed(run).roofline_pct(
        run, (n * steps * ops, n * steps * nbytes), scoped + layers.windowed(run).grouped_kernel_seconds(run)
    )
