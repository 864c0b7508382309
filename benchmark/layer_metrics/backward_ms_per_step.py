"""Device-operation time a step on operations named ``transpose(...)`` of
``tpuddp.forward``: the backward pass. It holds the weight-gradient products
and whatever XLA fused onto them, Adam's update of that weight included (a
fusion has one name; ``benchmark/scope_reduce.py``)."""

from benchmark import scope_reduce

LAYER = "models + nn (models/, nn/)"
UNIT = "ms/step"
MOVES = "samples_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    return scope_reduce.ms_per_step(run, scope_reduce.BACKWARD)
