"""Union of the device-operation intervals on one chip's plane over the
steps completed in the traced window."""

from benchmark.trace_reduce import first_plane

LAYER = "step builders (training/step.py, parallel/ddp.py)"
UNIT = "ms/step"
MOVES = "samples_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    trace = run["trace"]
    if trace is None or not run["window"]["steps"]:
        return None
    return 1e3 * first_plane(trace)["busy_s"] / run["window"]["steps"]
