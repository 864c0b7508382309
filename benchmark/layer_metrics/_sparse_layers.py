"""What the readers of the sparse-attention model share: device time under
the layer scopes that only this model opens under ``tpuddp.forward``
(``<i>_SparseAttention``: ``qkv``, ``index_proj``, ``index_scores``,
``index_select``, ``attention``, ``indexer_loss``, ``o_proj``, and inside the
layer the feed-forward, ``moe``), forward, backward and recomputation
together, from ``scope_reduce``'s layer table; the program counters the step
carries out (``indexer_kl_sum``, ``indexer_rows``, ``index_selected_pairs``);
and a roofline share. The names are this file's own copy, like
``scope_reduce``'s. A program without the scopes or the counters (the parent's,
another cell's) gives every reader nothing to read, and none raises."""

from benchmark import cells, scope_reduce

SPARSE, MOE = "_SparseAttention", "moe"
# what loops and conditionals put into an operation's path: not parts of a layer
_CONTROL = ("while", "body", "cond", "closed_call")


def seconds(run, parts):
    """Device seconds in the window under ``parts`` of the sparse-attention
    layers' mixers. ``None`` where the capture names no such layer."""
    reduced = scope_reduce.for_run(run)
    if reduced is None:
        return None
    total, found = 0.0, False
    for path, by_phase in reduced["layers_s"].items():
        names = [c for c in path.split("/") if c not in _CONTROL and not c.startswith("branch")]
        if not names or not names[0].endswith(SPARSE):
            continue
        found = True
        if MOE not in names[1:] and len(names) > 1 and names[1] in parts:
            total += sum(by_phase.values())
    return total if found else None


def ms_per_step(run, parts):
    s = seconds(run, parts)
    if s is None or not run["window"]["steps"]:
        return None
    return 1e3 * s / run["window"]["steps"]


def counter(run, name):
    """The window's sum of a program counter, or ``None`` where the step
    carries none of that name out."""
    return run["window"]["counters"].get(name)


def roofline_pct(run, cost, s):
    """``cost``: ``(operations, bytes)`` the need has for the whole window;
    the larger of its two bounds over the ``s`` device seconds it took (the
    window-and-full model's shared file's share, of the same checkout)."""
    return cells.load_module("layer_metrics", "_window_layers", run["cell"].root).roofline_pct(run, cost, s)


def flops_module(run):
    return cells.load_module("flops", run["cell"].config_name, run["cell"].root)


def window_tokens(run) -> float:
    return run["window"]["samples"] / run["cell"].chips
