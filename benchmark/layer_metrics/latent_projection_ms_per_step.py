"""Of the latent-attention mixers' device time a step, what runs under
``q_latent`` and ``kv_latent``: the down-projections, the norms inside the
pairs, the up-projections, rotary on the queries' slice and on the shared key
and that key's broadcast over the heads, forward, backward and recomputation:
what the latents cost where a plain attention layer has its ``qkv``."""

from benchmark import cells

LAYER = "softmax attention (nn/sequence.py, models/hybrid_moe.py)"
UNIT = "ms/step"
MOVES = "samples_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    layers = cells.load_module("layer_metrics", "_latent_layers", run["cell"].root)
    return layers.ms_per_step(run, layers.seconds(run, parts=("q_latent", "kv_latent")))
