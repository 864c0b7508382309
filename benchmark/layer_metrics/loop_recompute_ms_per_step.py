"""Device time a step that the looped layers' backward pass spends computing
forward values again (phase ``recompute`` under ``passes``): what the policy
across passes costs (a pass keeps its input alone and is walked again, and
inside that walk each half of a layer is recomputed once more)."""

from benchmark import cells

LAYER = "looped stack and its exits (models/hybrid_moe.py, nn/sequence.py)"
UNIT = "ms/step"
MOVES = "samples_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    layers = cells.load_module("layer_metrics", "_looped_layers", run["cell"].root)
    return layers.ms_per_step(run, layers.pass_seconds(run, phases=("recompute",)))
