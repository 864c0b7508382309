"""Time the runner spent blocked on ``next(loader)``, from ``run_pass``'s own
stall clock (``host_stall_s`` of ``tel.post_dispatch``), over steps. Only a
loader-fed cell has it."""

LAYER = "loader (data/loader.py, data/_native)"
UNIT = "ms/step"
MOVES = "samples_per_s_per_chip"
SOURCE = "program_counter"


def read(run):
    stall = run["window"]["counters"].get("host_stall_s")
    if stall is None or not run["window"]["steps"]:
        return None
    return 1e3 * stall / run["window"]["steps"]
