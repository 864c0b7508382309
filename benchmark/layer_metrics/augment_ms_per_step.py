"""Device-operation time a step on operations the program names
``tpuddp.augment``: the resize, flip and normalisation of a batch on the
device (``benchmark/scope_reduce.py``)."""

from benchmark import scope_reduce

LAYER = "augment (data/transforms.py, on the device)"
UNIT = "ms/step"
MOVES = "samples_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    return scope_reduce.ms_per_step(run, "augment")
