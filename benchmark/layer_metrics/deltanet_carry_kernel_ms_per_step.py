"""Device time a step in the DeltaNet scan's carry kernels themselves: the
operations that ``deltanet.chunk_gated_delta_rule``'s fused lowering puts on
the device for the carry over chunks (``deltanet_carry_fwd``,
``deltanet_carry_bwd`` custom calls: the state in VMEM, the output rows and
the cotangents of what a chunk computes alone), forward, backward and
recomputation. It says that the carry's kernels engaged: 0.0 where the carry
is a loop, as it is in every program built before they existed and wherever
``deltanet.scan_lowering`` answers "plain". The kernels keep the program's
scope (``.../<i>_GatedDeltaNet/scan/.../pallas_call``), so
``linear_attention_ms_per_step`` and ``deltanet_scan_roofline_pct`` hold this
time too; they are found by the name the program gives the call, which is
also the operation's name in the capture, so that a kernel that lost its
scope would still be counted; the chunk-local kernels (``deltanet_chunk_*``)
and the short convolution's (``deltanet_conv_*``) are other metrics'. This
file's own copy of that name, like ``scope_reduce``'s of the program's."""

from benchmark import scope_reduce

LAYER = "linear attention (nn/deltanet.py, models/hybrid_moe.py)"
UNIT = "ms/step"
MOVES = "samples_per_s_per_chip"
SOURCE = "device_trace"

KERNEL = "deltanet_carry_"


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
