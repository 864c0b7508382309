"""Device time a step in the latent-attention mixers (the low-rank pairs with
their norms, rotary on the queries' slice and on the one key all heads share,
that key's broadcast, causal attention in whichever lowering
``seq.causal_attention`` picked, the output projection), forward, backward and
recomputation, under the ``q_latent``, ``kv_latent``, ``attention`` and
``o_proj`` scopes of every ``<i>_LatentAttention`` layer, the prediction
module's among them."""

from benchmark import cells

LAYER = "softmax attention (nn/sequence.py, models/hybrid_moe.py)"
UNIT = "ms/step"
MOVES = "samples_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    layers = cells.load_module("layer_metrics", "_latent_layers", run["cell"].root)
    return layers.ms_per_step(run, layers.seconds(run, parts=layers.MIXER_PARTS))
