"""Share of device-operation time on operations that carry any ``tpuddp.``
scope: what the program's names cover (``benchmark/scope_reduce.py``). The
rest is what the step builders leave unnamed: the scan's slices and copies."""

from benchmark import scope_reduce

LAYER = "step builders (training/step.py, parallel/ddp.py)"
UNIT = "%"
MOVES = "samples_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    reduced = scope_reduce.for_run(run)
    if reduced is None or not reduced["op_s"]:
        return None
    return 100.0 * (1.0 - reduced["phases_s"][scope_reduce.UNSCOPED] / reduced["op_s"])
