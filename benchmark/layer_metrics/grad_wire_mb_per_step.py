"""Bytes one replica puts on the wire for one gradient reduction, as
``ddp.grad_comm_bytes_per_step`` counts them. A count, reported as a count:
it explains ``collective_ms_per_step``."""

LAYER = "gradient exchange (parallel/comm.py, collectives.py)"
UNIT = "MB/step"
MOVES = "samples_per_s_per_chip"
SOURCE = "program_counter"


def read(run):
    wire = run["counters"].get("grad_comm_bytes_per_step")
    if wire is None or run["cell"].chips < 2:
        return None
    return wire / 1e6
