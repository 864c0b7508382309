"""Device time a step under the ``exits`` scope of the loss phase: the head
product of every exit a chunk of tokens at a time, its logsumexp, the gate,
the exit distribution and the weighted sums, forward, backward and the
chunks' recomputation."""

from benchmark import cells

LAYER = "looped stack and its exits (models/hybrid_moe.py, nn/sequence.py)"
UNIT = "ms/step"
MOVES = "samples_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    layers = cells.load_module("layer_metrics", "_looped_layers", run["cell"].root)
    return layers.ms_per_step(run, layers.exit_seconds(run))
