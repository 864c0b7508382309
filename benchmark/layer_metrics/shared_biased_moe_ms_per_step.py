"""Device time a step in the expert layers of the latent-attention model (the
sigmoid router with its selection bias and the bias's update, dispatch,
grouped expert products, combine, and the shared expert that is added
ungated), forward, backward and recomputation: under the ``moe`` scope of
every ``<i>_LatentAttention`` layer, the prediction module's among them, plus
the compiler's own ``ragged-dot-*`` kernels, which carry no scope. What
``biased_moe_ms_per_step`` reads for the convolution-and-attention model,
whose reader does not see these layers' scopes."""

from benchmark import cells

LAYER = "expert layer (nn/moe.py)"
UNIT = "ms/step"
MOVES = "samples_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    layers = cells.load_module("layer_metrics", "_latent_layers", run["cell"].root)
    scoped = layers.ms_per_step(run, layers.seconds(run, moe=True))
    if scoped is None:
        return None
    return scoped + 1e3 * layers.unscoped_expert_kernel_seconds(run) / run["window"]["steps"]
