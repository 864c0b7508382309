"""Analytic forward+backward operations of a step (``benchmark/flops``,
from shapes; nothing recomputed counts) over the device's busy time for it,
against the chip's bf16 peak. Times (1 - idle share) it is the end-to-end
MFU."""

from benchmark.trace_reduce import first_plane

LAYER = "kernels (XLA conv/dot)"
UNIT = "%"
MOVES = "samples_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    if run["trace"] is None:
        return None
    busy_s = first_plane(run["trace"])["busy_s"]
    if not busy_s:
        return None
    flops = run["flops_per_sample"] * run["window"]["samples"] / run["cell"].chips
    return 100.0 * flops / busy_s / run["peaks"]["bf16_flops_per_s"]
