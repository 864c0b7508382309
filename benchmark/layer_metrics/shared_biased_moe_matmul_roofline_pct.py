"""The grouped expert products' share of their roofline in the
latent-attention model: the operations and bytes that the window's own count
of assignments at held experts needs (``moe_expert_tokens_held``;
``benchmark/flops``: ``expert_matmul_cost``, the larger of the two bounds),
over the device time of the grouped-product kernels, in whichever lowering
``nn/moe.py`` picked (the compiler's ``ragged-dot-*`` or the Pallas ``gmm`` and
``tgmm``, forward, recomputed and backward), and of what runs under the
expert layers' ``experts`` scope around them. The shared expert is a dense
product under ``shared_expert`` and is in neither the need nor the time."""

from benchmark import cells

LAYER = "expert layer (nn/moe.py)"
UNIT = "%"
MOVES = "samples_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    layers = cells.load_module("layer_metrics", "_latent_layers", run["cell"].root)
    held, steps = run["window"]["counters"].get("moe_expert_tokens_held"), run["window"]["steps"]
    if not held or not steps or not layers.is_this_model(run):
        return None  # no counter, or a capture that names no latent-attention layer
    scoped = layers.seconds(run, parts=("experts",), moe=True)
    config, flops = run["cell"].config, layers.flops_module(run)
    n = flops.sparse_layers(config)
    # the weights are read once a layer a step; the pairs are the window's own
    ops, nbytes = flops.expert_matmul_cost(config, held / (n * steps))
    return layers.windowed(run).roofline_pct(
        run, (n * steps * ops, n * steps * nbytes), scoped + layers.windowed(run).grouped_kernel_seconds(run)
    )
