"""Device time a step under the Gated DeltaNet mixers' ``conv`` scope
(``<i>_GatedDeltaNet/conv``): the short causal convolution, SiLU and the l2
norms of queries and keys between the input projection and the scan, forward,
backward and recomputation. Whichever lowering ``deltanet.short_conv`` chose
runs under that scope, so a program built before the function existed reads
its plain operations here and the ledger holds the before and the after."""

from benchmark import cells

LAYER = "linear attention (nn/deltanet.py, models/hybrid_moe.py)"
UNIT = "ms/step"
MOVES = "samples_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    shared = cells.load_module("layer_metrics", "_token_layers", run["cell"].root)
    return shared.ms_per_step(run, kind=shared.DELTANET, part="conv")
