"""Largest over mean tokens at a held expert, a layer and a step, from the
program's own counters (``moe_expert_tokens_max`` and
``moe_expert_tokens_held`` of ``tpuddp/nn/moe.py``, summed over the window's
layers and steps by the feed). 1 is an even load; the deployment's figure
would be over all 512 experts, which one share cannot see."""

LAYER = "expert layer (nn/moe.py)"
UNIT = "ratio"
MOVES = "samples_per_s_per_chip"
SOURCE = "program_counter"


def read(run):
    counters = run["window"]["counters"]
    largest, held = counters.get("moe_expert_tokens_max"), counters.get("moe_expert_tokens_held")
    if largest is None or not held:
        return None
    return run["cell"].config["num_experts"] * largest / held
