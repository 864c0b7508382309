"""Share of the collective time during which no compute operation ran on
the same chip."""

from benchmark.trace_reduce import first_plane

LAYER = "gradient exchange (parallel/comm.py, collectives.py)"
UNIT = "%"
MOVES = "samples_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    if run["trace"] is None or run["cell"].chips < 2:
        return None
    plane = first_plane(run["trace"])
    if not plane["collective_s"]:
        return None
    return 100.0 * plane["collective_exposed_s"] / plane["collective_s"]
