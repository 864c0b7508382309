"""What the readers of the latent-attention model share: device time under
the layer scopes that only this model opens under ``tpuddp.forward``
(``<i>_LatentAttention``: ``q_latent``, ``kv_latent``, ``attention``,
``o_proj``, and inside the layer the feed-forward, ``mlp`` or ``moe``), forward,
backward and recomputation together, from ``scope_reduce``'s layer table. The
prediction module's layer keeps the same names under an ``mtp`` scope
(``mtp/<i>_LatentAttention/...``, beside ``mtp/proj``) and is read with the
others; under ``tpuddp.loss`` the second head's products and loss lie in
``mtp``, which the layer table does not hold (it is the forward phase's), so
those operations are walked here. The names are this file's own copy, like
``scope_reduce``'s."""

from benchmark import cells, scope_reduce

LATENT, MTP, MOE = "_LatentAttention", "mtp", "moe"
MIXER_PARTS = ("q_latent", "kv_latent", "attention", "o_proj")
# what loops and conditionals put into an operation's path: not parts of a layer
_CONTROL = ("while", "body", "cond", "closed_call")


def _named(path: str) -> list:
    return [c for c in path.split("/") if c not in _CONTROL and not c.startswith("branch")]


def _inside_layer(names: list):
    """What follows the ``<i>_LatentAttention`` component of a layer path (a
    stack's layer, or the module's under ``mtp``), or ``None`` for any other
    path."""
    at = 1 if names[:1] == [MTP] else 0
    if len(names) <= at or not names[at].endswith(LATENT):
        return None
    return names[at + 1:]


def seconds(run, parts=None, moe=False):
    """Device seconds in the window under the latent-attention layers, the
    module's among them: inside (``moe`` True) or outside their expert layer
    and, with ``parts``, under those parts of it alone. ``None`` where the
    capture names no such layer."""
    reduced = scope_reduce.for_run(run)
    if reduced is None:
        return None
    total, found = 0.0, False
    for path, by_phase in reduced["layers_s"].items():
        inner = _inside_layer(_named(path))
        if inner is None:
            continue
        found = True
        if (MOE in inner) != moe:
            continue
        if moe:
            inner = inner[inner.index(MOE) + 1:]
        if parts is None or (inner and inner[0] in parts):
            total += sum(by_phase.values())
    return total if found else None


def is_this_model(run) -> bool:
    """Whether the capture names a ``<i>_LatentAttention`` layer at all."""
    return seconds(run) is not None


def module_seconds(run):
    """Device seconds in the window under both ``mtp`` scopes: the module in
    the forward phase (projection, its layer, its norm; forward, backward and
    recomputation) and the second head's products and loss in the loss phase.
    ``None`` where the capture names neither."""
    reduced = scope_reduce.for_run(run)
    if reduced is None:
        return None
    found = [sum(by.values()) for path, by in reduced["layers_s"].items() if _named(path)[:1] == [MTP]]
    head = [
        e["dur"] / 1e6 for e in scope_reduce.first_plane_leaves(run["events"])
        if _in_loss_scope((e.get("args") or {}).get("tf_op") or "")
    ]
    return sum(found) + sum(head) if found or head else None


def _in_loss_scope(tf_op: str) -> bool:
    return scope_reduce.attribute(tf_op)[0] == "loss" and MTP in tf_op.rstrip(":").split("/")


def ms_per_step(run, s):
    if s is None or not run["window"]["steps"]:
        return None
    return 1e3 * s / run["window"]["steps"]


def windowed(run):
    """The window-and-full model's shared file, of the same checkout: the
    grouped-product kernels by either lowering's names and the roofline
    share."""
    return cells.load_module("layer_metrics", "_window_layers", run["cell"].root)


def unscoped_expert_kernel_seconds(run):
    """The compiler's own ``ragged-dot-*`` kernels, which carry no scope
    (``_token_layers``'s count, of the same checkout)."""
    return cells.load_module("layer_metrics", "_token_layers", run["cell"].root).expert_kernel_seconds(run)


def flops_module(run):
    return cells.load_module("flops", run["cell"].config_name, run["cell"].root)


def window_tokens(run) -> float:
    return run["window"]["samples"] / run["cell"].chips
