"""Share of the traced window in which no operation ran on the device,
averaged over the chips used."""

LAYER = "device"
UNIT = "%"
MOVES = "samples_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    trace = run["trace"]
    return None if trace is None else trace["idle_pct"]
