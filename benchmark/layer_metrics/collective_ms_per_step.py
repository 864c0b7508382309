"""Time in all-reduce, reduce-scatter, all-gather and collective-permute
operations on one chip's plane over steps. A one-chip cell has none."""

from benchmark.trace_reduce import first_plane

LAYER = "gradient exchange (parallel/comm.py, collectives.py)"
UNIT = "ms/step"
MOVES = "samples_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    trace = run["trace"]
    if trace is None or run["cell"].chips < 2 or not run["window"]["steps"]:
        return None
    return 1e3 * first_plane(trace)["collective_s"] / run["window"]["steps"]
