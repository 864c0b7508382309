"""Device time a step in the fused attention kernels of the
convolution-and-attention model (the library's ``splash_*`` custom calls:
forward, recomputed forward and backward), as
``attention_kernel_ms_per_step`` reads them in the DeltaNet hybrid's cell.
With ``narrow_head_attention_ms_per_step`` it splits the time under the
``attention`` scope into the kernels and what stands round them: the sum of
the one-kernel backward's partial ``dq``, and the slices of a call that goes
a group of heads at a time. Nothing where the capture has none of this
model's layers."""

from benchmark import cells

LAYER = "softmax attention (nn/sequence.py, models/hybrid_moe.py)"
UNIT = "ms/step"
MOVES = "samples_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    load = lambda name: cells.load_module("layer_metrics", name, run["cell"].root)
    if not load("_conv_layers").is_this_model(run):
        return None  # another family's kernels
    return load("attention_kernel_ms_per_step").read(run)
