"""The mean exit of the window's tokens under the gate's distribution:
``sum_t t * loop_exit_mass_t / sum_t loop_exit_mass_t`` from the counters the
step carries out (``nn/sequence.py``: ``DeferredExits``; over a step's tokens
the sum of ``p_t``, an exit each), summed over the window by the cell's feed.
A fresh gate over four exits reads 1.875."""

LAYER = "looped stack and its exits (models/hybrid_moe.py, nn/sequence.py)"
UNIT = "pass"
MOVES = "samples_per_s_per_chip"
SOURCE = "program_counter"

_MASS = "loop_exit_mass_"


def read(run):
    counters = run["window"]["counters"]
    masses = {int(k[len(_MASS):]): v for k, v in counters.items() if k.startswith(_MASS)}
    if not masses or not sum(masses.values()):
        return None
    return sum(t * m for t, m in masses.items()) / sum(masses.values())
