"""What the readers of the looped model share: device time under the scopes
that only a looped stack opens. Under ``tpuddp.forward`` the pass loop lies in
a ``passes`` scope and its layers keep their names inside it
(``passes/while/body/<i>_FullAttention/...``: ``qkv``, ``attention``,
``o_proj``, ``mlp``), forward, backward and recomputation together, from
``scope_reduce``'s layer table; under ``tpuddp.loss`` the exits' head products
and their loss lie in ``exits`` (the gate in ``exits/gate``), which the layer
table does not hold (it is the forward phase's), so the operations are walked
here. Another model's ``<i>_FullAttention`` layers are under no ``passes`` and
are not read as this one's. The names are this file's own copy, like
``scope_reduce``'s."""

from benchmark import cells, scope_reduce

PASSES, FULL, EXITS = "passes", "_FullAttention", "exits"
ATTENTION_PARTS = ("qkv", "attention", "o_proj")
# what loops and conditionals put into an operation's path: not parts of a layer
_CONTROL = ("while", "body", "cond", "closed_call")


def _named(path: str) -> list:
    return [c for c in path.split("/") if c not in _CONTROL and not c.startswith("branch")]


def pass_seconds(run, parts=None, phases=None):
    """Device seconds in the window under the looped layers and, in them,
    under ``parts`` (anywhere in them if ``None``), of ``phases`` (forward,
    backward and recomputation together if ``None``); ``None`` where the
    capture names no looped layer."""
    reduced = scope_reduce.for_run(run)
    if reduced is None:
        return None
    total, found = 0.0, False
    for path, by_phase in reduced["layers_s"].items():
        names = _named(path)
        if len(names) < 2 or names[0] != PASSES or not names[1].endswith(FULL):
            continue
        found = True
        if parts is None or (len(names) > 2 and names[2] in parts):
            total += sum(s for phase, s in by_phase.items() if phases is None or phase in phases)
    return total if found else None


def exit_seconds(run):
    """Device seconds in the window under the ``exits`` scope of the loss
    phase, forward, backward and recomputation; ``None`` where the capture
    names no such scope."""
    if scope_reduce.for_run(run) is None:
        return None
    total, found = 0.0, False
    for e in scope_reduce.first_plane_leaves(run["events"]):
        tf_op = (e.get("args") or {}).get("tf_op") or ""
        if scope_reduce.attribute(tf_op)[0] == "loss" and EXITS in tf_op.rstrip(":").split("/"):
            found = True
            total += e["dur"]
    return total / 1e6 if found else None


def ms_per_step(run, seconds):
    if seconds is None or not run["window"]["steps"]:
        return None
    return 1e3 * seconds / run["window"]["steps"]


def roofline_pct(run, cost, seconds):
    """The window-and-full model's shared file's, of the same checkout:
    ``cost`` ``(operations, bytes)`` needed for the whole window, the larger
    of its two bounds over the ``seconds`` of device time it took."""
    return cells.load_module("layer_metrics", "_window_layers", run["cell"].root).roofline_pct(run, cost, seconds)


def flops_module(run):
    return cells.load_module("flops", run["cell"].config_name, run["cell"].root)


def window_tokens(run) -> float:
    return run["window"]["samples"] / run["cell"].chips
