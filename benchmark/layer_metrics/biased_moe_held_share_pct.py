"""Assignments at held experts over all assignments in the window, from the
program's own counters (``moe_expert_tokens_held`` and
``moe_absent_assignments``): the held experts' own share is ``held /
published`` (25% of 32 where 8 are held); a share's router that drifts onto
its own experts reads above it."""

LAYER = "expert layer (nn/moe.py)"
UNIT = "%"
MOVES = "samples_per_s_per_chip"
SOURCE = "program_counter"


def read(run):
    counters = run["window"]["counters"]
    held, absent = counters.get("moe_expert_tokens_held"), counters.get("moe_absent_assignments")
    if held is None or absent is None or not held + absent:
        return None
    return 100.0 * held / (held + absent)
