"""Device-operation time a step on operations the program names
``tpuddp.clip``, ``tpuddp.guard`` or ``tpuddp.optimizer``: the part of the
update XLA left outside the weight-gradient fusions
(``benchmark/scope_reduce.py``; ``optimizer_share_pct`` holds both parts)."""

from benchmark import scope_reduce

LAYER = "optimizer (optim.py)"
UNIT = "ms/step"
MOVES = "samples_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    return scope_reduce.ms_per_step(run, *scope_reduce.UPDATE_SCOPES)
