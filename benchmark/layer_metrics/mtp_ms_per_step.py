"""Device time a step under both ``mtp`` scopes: in the forward phase the
prediction module (the projection of the joined state and next token's
embedding, its whole layer, its norm), forward, backward and recomputation;
in the loss phase the second head's products a chunk of tokens at a time and
their loss. The module's grouped expert products under the compiler's own
``ragged-dot-*`` names carry no scope and are not in this time
(``shared_biased_moe_ms_per_step`` counts them)."""

from benchmark import cells

LAYER = "second prediction head (models/hybrid_moe.py, nn/sequence.py)"
UNIT = "ms/step"
MOVES = "samples_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    layers = cells.load_module("layer_metrics", "_latent_layers", run["cell"].root)
    return layers.ms_per_step(run, layers.module_seconds(run))
