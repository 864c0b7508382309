"""Host time at the head of a pass that nothing in the loader feed can hide:
over the window's passes, the mean time from the end of the feed's
``between_passes`` span (the re-shuffle; the feed has fetched the last pass's
metrics before it, so the device has nothing left to run) to the start of the
pass's first ``dispatch`` span (``run_pass`` hands the device its first chunk).
In between lie the waits for the first chunk's batches from a loader whose
queue starts every pass empty, their ``stage_stack`` and their ``stage_put``.

A pass the clock stopped before its first dispatch opens no ``dispatch`` span
before the next ``between_passes`` (or the window's end) and is left out. Only
a loader-fed cell has passes; a run with no ``dispatch`` span reads nothing.
Read from the annotation events of ``run["events"]`` by name, not from
``run["spans"]["seconds"]`` (``_program_spans``)."""

from benchmark.layer_metrics import _program_spans

LAYER = "async runner (training/pipeline.py)"
UNIT = "ms"
MOVES = "samples_per_s_per_chip"
SOURCE = "program_span"


def read(run):
    events = run.get("events")
    if not events:
        return None
    opened = _program_spans.window(events)
    if opened is None:
        return None
    lo = opened[0]
    hi = min(opened[1], lo + 1e6 * run["window"]["window_s"])
    issued = sorted(
        start for start, _ in _program_spans.intervals(events, ("dispatch",)) if start < hi
    )
    # where each pass's run_pass begins, and where the next pass's begins
    heads = sorted(
        end for _, end in _program_spans.intervals(events, ("between_passes",))
        if lo <= end < hi
    )
    waits = []
    for head, until in zip(heads, heads[1:] + [hi]):
        first = next((start for start in issued if start >= head), None)
        if first is not None and first < until:
            waits.append(first - head)
    if not waits:
        return None
    return sum(waits) / len(waits) / 1e3
