"""Share of the first chip's idle time in the traced window that lies under
any span the program opened itself: coverage, not attribution. The idle
intervals are the window less the busy union of that chip's leaf operations
(``device_idle_pct``'s, for one chip); they are intersected with the union of
the program's spans on any thread, and the covered part is given over the
idle total.

It cannot be read off ``idle_by_host_activity_s``: the harness's
``loader_next`` wrapper sits inside the program's ``input_wait`` (the runner
calls ``next`` on the wrapped iterator), so the innermost rule gives a wait
under which no worker span is open to ``loader_next``. The feed's own
``readback`` span round its ``device_get`` shares a name with ``run_pass``'s:
both count. The list is this reader's own copy of the program's span names.
Read from the annotation events of ``run["events"]``, not from
``run["spans"]["seconds"]`` (``_program_spans``)."""

from benchmark import scope_reduce
from benchmark import trace_reduce as tr
from benchmark.layer_metrics import _program_spans

LAYER = "device"
UNIT = "%"
MOVES = "samples_per_s_per_chip"
SOURCE = "device_trace"

PROGRAM_SPANS = (
    "stage", "stage_stack", "stage_put", "dispatch", "readback", "grad_comm",
    "input_wait", "loader_order", "loader_gather", "loader_pad",
)


def read(run):
    events = run.get("events")
    if run.get("trace") is None or not events:
        return None
    opened = _program_spans.window(events)
    if opened is None:
        return None
    try:
        leaves = scope_reduce.first_plane_leaves(events)
    except tr.TraceError:
        return None
    busy = tr.clip(tr.union((e["ts"], e["ts"] + e["dur"]) for e in leaves), *opened)
    idle = tr.subtract([opened], busy)
    spans = tr.clip(tr.union(_program_spans.intervals(events, PROGRAM_SPANS)), *opened)
    if not tr.total(idle) or not spans:
        return None
    return 100.0 * (1.0 - tr.total(tr.subtract(idle, spans)) / tr.total(idle))
