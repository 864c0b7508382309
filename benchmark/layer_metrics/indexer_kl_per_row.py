"""The indexer's objective a row a layer over the window, in nats: the KL
divergence of the heads' mean attention distribution to the indexer's
``softmax(I)`` over a row's selected keys, ``indexer_kl_sum / indexer_rows``
from the counters the step carries out (``nn/sequence.py``:
``SPARSE_COUNTERS``), summed over the window by the cell's feed. It enters
the gradient and not the reported loss, so this is where it shows. A fresh
indexer's distribution is near uniform and so is a fresh attention's, so it
starts near 0 and says little until either has learnt something."""

LAYER = "softmax attention (nn/sequence.py, models/hybrid_moe.py)"
UNIT = "nat"
MOVES = "samples_per_s_per_chip"
SOURCE = "program_counter"


def read(run):
    counters = run["window"]["counters"]
    total, rows = counters.get("indexer_kl_sum"), counters.get("indexer_rows")
    if total is None or not rows:
        return None
    return total / rows
