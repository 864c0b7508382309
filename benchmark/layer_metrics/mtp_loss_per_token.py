"""The second head's cross-entropy a token over the window, in nats: the
token after next against the prediction module's logits, ``mtp_loss_sum /
mtp_tokens`` from the counters the step carries out (``nn/sequence.py``:
``DeferredLogits``), summed over the window by the cell's feed. It enters the
gradient and not the reported loss, so this is where it shows: it starts at
the logarithm of the held vocabulary and falls as the stream is learnt."""

LAYER = "second prediction head (models/hybrid_moe.py, nn/sequence.py)"
UNIT = "nat"
MOVES = "samples_per_s_per_chip"
SOURCE = "program_counter"


def read(run):
    counters = run["window"]["counters"]
    total, tokens = counters.get("mtp_loss_sum"), counters.get("mtp_tokens")
    if total is None or not tokens:
        return None
    return total / tokens
