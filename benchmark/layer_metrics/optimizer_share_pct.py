"""Share of device-operation time in the "weight-grad + optimizer (fused)"
bucket. XLA fuses Adam's update into the weight-gradient products, so the
bucket holds those products too."""

from benchmark.trace_reduce import first_plane

LAYER = "optimizer (optim.py)"
UNIT = "%"
MOVES = "samples_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    if run["trace"] is None:
        return None
    plane = first_plane(run["trace"])
    if not plane["op_s"]:
        return None
    return 100.0 * plane["buckets_s"]["weight-grad + optimizer (fused)"] / plane["op_s"]
