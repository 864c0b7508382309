"""Device time a step in the short convolution's fused kernels themselves:
the operations that ``deltanet.short_conv``'s fused lowering puts on the
device (``deltanet_conv_fwd``, one an output, and ``deltanet_conv_bwd``
custom calls), forward, backward and recomputation. It says that the lowering
engaged: 0.0 where the scope ran plain, as it does in a program built before
the lowering existed. The kernels keep the program's scope
(``.../<i>_GatedDeltaNet/conv/.../pallas_call``), so
``deltanet_conv_ms_per_step`` and ``linear_attention_ms_per_step`` hold this
time too; they are found by the name the program gives the call, which is
also the operation's name in the capture, so that a kernel that lost its
scope would still be counted; the scan's kernels (``deltanet_chunk_*``) are
another metric's. This file's own copy of that name, like ``scope_reduce``'s
of the program's."""

from benchmark import scope_reduce

LAYER = "linear attention (nn/deltanet.py, models/hybrid_moe.py)"
UNIT = "ms/step"
MOVES = "samples_per_s_per_chip"
SOURCE = "device_trace"

KERNEL = "deltanet_conv_"


def is_kernel(event) -> bool:
    if (event.get("name") or "").startswith(KERNEL):
        return True
    path = ((event.get("args") or {}).get("tf_op") or "").rstrip(":").split("/")
    return path[-1] == "pallas_call" and any(part.startswith(KERNEL) for part in path[:-1])


def read(run):
    steps = run["window"]["steps"]
    if scope_reduce.for_run(run) is None or not steps:
        return None
    leaves = scope_reduce.first_plane_leaves(run["events"])
    return sum(e["dur"] for e in leaves if is_kernel(e)) / 1e3 / steps  # microseconds in the capture
