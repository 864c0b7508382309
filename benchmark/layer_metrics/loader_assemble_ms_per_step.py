"""The loader's time at work a step: the total of its own ``loader_order``
(the epoch's order, once a pass), ``loader_gather`` (a batch's row gather) and
``loader_pad`` (labels, weights, padding and the concatenation over local
replicas) spans, whichever thread opened them (a ``PrefetchLoader``'s workers
overlap, and their spans add up), over steps. To set beside
``input_wait_ms_per_step``, the time the runner waited for it: assembly well
under the wait is hand-over or an empty queue, assembly near it is a queue
that starts every pass cold. Summed from the annotation events of
``run["events"]`` by name, not from ``run["spans"]["seconds"]``, which two
worker threads update without a lock (``_program_spans``)."""

from benchmark.layer_metrics import _program_spans

LAYER = "loader (data/loader.py, data/_native)"
UNIT = "ms/step"
MOVES = "samples_per_s_per_chip"
SOURCE = "program_span"

SPANS = ("loader_order", "loader_gather", "loader_pad")


def read(run):
    return _program_spans.ms_per_step(run, SPANS)
