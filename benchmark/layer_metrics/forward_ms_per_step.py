"""Device-operation time a step on operations the program names
``tpuddp.forward`` (not transposed) or ``tpuddp.loss``: the model's forward
pass and the criterion (``benchmark/scope_reduce.py``)."""

from benchmark import scope_reduce

LAYER = "models + nn (models/, nn/)"
UNIT = "ms/step"
MOVES = "samples_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    return scope_reduce.ms_per_step(run, "forward", "loss")
