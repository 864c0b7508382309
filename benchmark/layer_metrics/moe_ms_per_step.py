"""Device time a step in the expert layers (router, dispatch, grouped expert
products, combine, shared expert), forward, backward and recomputation: under
the ``moe`` scope of every layer, plus the compiler's own ``ragged-dot-*``
kernels, which carry no scope."""

from benchmark import cells

LAYER = "expert layer (nn/moe.py)"
UNIT = "ms/step"
MOVES = "samples_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    shared = cells.load_module("layer_metrics", "_token_layers", run["cell"].root)
    scoped = shared.ms_per_step(run, moe=True)
    if scoped is None:
        return None
    return scoped + 1e3 * shared.expert_kernel_seconds(run) / run["window"]["steps"]
