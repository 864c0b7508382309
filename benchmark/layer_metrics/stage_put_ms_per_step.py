"""Host time a step in ``run_pass``'s ``stage_put`` spans: ``ddp.shard_stacked``
(or ``ddp.shard``) placing a staged chunk on the mesh, inside ``stage``: the
host-side re-tile and the issue of the transfer, not its completion on the
device. A program built before the span existed has none and the reader
returns nothing. Summed from the annotation events of ``run["events"]`` by
name, not from ``run["spans"]["seconds"]`` (``_program_spans``)."""

from benchmark.layer_metrics import _program_spans

LAYER = "async runner (training/pipeline.py)"
UNIT = "ms/step"
MOVES = "samples_per_s_per_chip"
SOURCE = "program_span"


def read(run):
    return _program_spans.ms_per_step(run, ("stage_put",))
