"""What the token model's readers share: device time a step under the layer
scopes that the hybrid family opens under ``tpuddp.forward``
(``<i>_GatedDeltaNet``, ``<i>_GatedAttention``, and inside each ``moe`` and
the mixer's parts), forward, backward and recomputation together, from
``scope_reduce``'s layer table; and a roofline share from it. The names are
this file's own copy, like ``scope_reduce``'s."""

from benchmark import cells, scope_reduce

DELTANET, ATTENTION, MOE = "_GatedDeltaNet", "_GatedAttention", "moe"
# what loops and conditionals put into an operation's path: not parts of a layer
_CONTROL = ("while", "body", "cond", "closed_call")


def seconds(run, kind=None, part=None, moe=False):
    """Device seconds in the window on operations whose layer path starts
    with a layer of ``kind`` (any kind if ``None``), lies inside (``moe``
    True) or outside the expert layer, and, if ``part`` is given, inside that
    part of it. ``None`` where the capture names no such layer."""
    reduced = scope_reduce.for_run(run)
    if reduced is None:
        return None
    total, found = 0.0, False
    for path, by_phase in reduced["layers_s"].items():
        parts = path.split("/")
        if kind is not None and not parts[0].endswith(kind):
            continue
        if kind is None and not parts[0].endswith((DELTANET, ATTENTION)):
            continue
        inner = [c for c in parts[1:] if c not in _CONTROL and not c.startswith("branch")]
        if (MOE in inner) != moe:
            continue
        if moe:
            inner = inner[inner.index(MOE) + 1:]
        if part is not None and (not inner or inner[0] != part):
            continue
        found = True
        total += sum(by_phase.values())
    return total if found else None


EXPERT_KERNEL = "ragged-dot"  # the TPU compiler's grouped-product kernels keep their own name


def expert_kernel_seconds(run):
    """Device seconds in the window on the compiler's own grouped-product
    kernels (``ragged-dot-*`` custom calls: what ``jax.lax.ragged_dot``
    becomes on the TPU). The compiler gives them its own ``op_name``, so they
    carry no scope of the program's; only the expert layer calls them."""
    if scope_reduce.for_run(run) is None:
        return None
    return sum(
        e["dur"] for e in scope_reduce.first_plane_leaves(run["events"])
        if (e.get("name") or "").startswith(EXPERT_KERNEL)
        and scope_reduce.attribute((e.get("args") or {}).get("tf_op") or "")[0] == scope_reduce.UNSCOPED
    ) / 1e6


def ms_per_step(run, **where):
    s = seconds(run, **where)
    if s is None or not run["window"]["steps"]:
        return None
    return 1e3 * s / run["window"]["steps"]


def roofline_pct(run, cost, extra_seconds=0.0, **where):
    """``cost``: ``(operations, bytes)`` the kernel needs for the whole
    window; the larger of its two bounds over the device time it took
    (the scope's, plus ``extra_seconds`` counted by name)."""
    s = seconds(run, **where)
    if not s:
        return None
    s += extra_seconds
    ops, nbytes = cost
    least = max(ops / run["peaks"]["bf16_flops_per_s"], nbytes / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / s


def flops_module(run):
    return cells.load_module("flops", run["cell"].config_name, run["cell"].root)


def layers_of(config, full_attention: bool) -> int:
    every = config["full_attention_interval"]
    full = sum((i + 1) % every == 0 for i in range(config["num_hidden_layers"]))
    return full if full_attention else config["num_hidden_layers"] - full
