"""What the readers of the convolution-and-attention model share: device time
under the layer scopes that its two layer types open under ``tpuddp.forward``
(``<i>_ShortConv``: ``in_proj``, ``conv``, ``out_proj``; ``<i>_FullAttention``:
``qkv``, ``attention``, ``o_proj``; inside either the feed-forward, ``mlp`` or
``moe``), forward, backward and recomputation together. The sums over one
type's scopes are ``_token_layers``'s, of the same checkout; the names are
this file's own copy, like ``scope_reduce``'s."""

from benchmark import cells

SHORT_CONV, FULL = "_ShortConv", "_FullAttention"
CONV_PARTS, ATTENTION_PARTS = ("in_proj", "conv", "out_proj"), ("qkv", "attention", "o_proj")


def shared(run):
    return cells.load_module("layer_metrics", "_token_layers", run["cell"].root)


def seconds(run, kinds=(SHORT_CONV, FULL), parts=(None,), **where):
    """Device seconds in the window under the layers of ``kinds`` and, in
    them, under ``parts`` (anywhere in them if ``None``), summed; ``None``
    where the capture names none of them."""
    found = [
        s for s in (
            shared(run).seconds(run, kind=kind, part=part, **where) for kind in kinds for part in parts
        ) if s is not None
    ]
    return sum(found) if found else None


def is_this_model(run) -> bool:
    """Whether the capture names a ``<i>_ShortConv`` layer at all: another
    family's ``<i>_FullAttention`` layers and expert layers are not read as
    this model's."""
    return seconds(run, kinds=(SHORT_CONV,)) is not None


def windowed(run):
    """The window-and-full model's shared file, of the same checkout: the
    grouped-product kernels by either lowering's names and the roofline share
    are what this model's readers need of it."""
    return cells.load_module("layer_metrics", "_window_layers", run["cell"].root)


def ms_per_step(run, **where):
    s = seconds(run, **where)
    if s is None or not run["window"]["steps"]:
        return None
    return 1e3 * s / run["window"]["steps"]


def layers_of(config, layer_type: str) -> int:
    first = config["deployment"]["first_layer"]
    return config["layer_types"][first:first + config["num_hidden_layers"]].count(layer_type)


def sparse_layers(config) -> int:
    return config["num_hidden_layers"] - config["num_dense_layers"]
