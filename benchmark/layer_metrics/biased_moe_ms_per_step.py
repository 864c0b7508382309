"""Device time a step in the expert layers of the convolution-and-attention
model (the sigmoid router with its selection bias and the bias's update,
dispatch, grouped expert products, combine), forward, backward and
recomputation: under the ``moe`` scope of every layer, plus the compiler's own
``ragged-dot-*`` kernels, which carry no scope. What ``moe_ms_per_step`` reads
for the DeltaNet hybrid, whose reader does not see these layers' scopes."""

from benchmark import cells

LAYER = "expert layer (nn/moe.py)"
UNIT = "ms/step"
MOVES = "samples_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    layers = cells.load_module("layer_metrics", "_conv_layers", run["cell"].root)
    if not layers.is_this_model(run):
        return None
    scoped = layers.ms_per_step(run, moe=True)
    if scoped is None:
        return None
    return scoped + 1e3 * layers.shared(run).expert_kernel_seconds(run) / run["window"]["steps"]
