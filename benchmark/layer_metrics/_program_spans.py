"""The spans the program opens itself on the pass boundary (``run_pass`` and
the loader it iterates, through the ``tracer=`` the feed hands them), read
back from a traced run's events: ``trace_reduce.capture_events`` lays every
span of ``host_spans.json`` on the capture's clock as a ``bench:<name>``
annotation.

The readers sum those annotation events by name and do not read
``run["spans"]["seconds"]``: the loader's spans come from its worker threads,
and the harness's recorder is not locked (its ``intervals.append`` is atomic,
its ``seconds[name] +=`` from two threads is not).

The span names are this file's and its readers' own copies, as
``scope_reduce`` keeps its own of the program's scopes: a renamed span makes a
reader fall silent and cannot move it. The window annotation and the interval
arithmetic are ``trace_reduce``'s, imported."""

from benchmark import trace_reduce as tr


def window(events):
    """``(start, end)`` of the ``bench:window`` annotation in microseconds of
    the capture's clock, or ``None``."""
    found = sorted(
        (e["ts"], e["ts"] + e["dur"]) for e in events
        if tr._complete(e) and tr._annotation(e) == tr.WINDOW_ANNOTATION
    )
    return found[0] if found else None


def intervals(events, names) -> list:
    """One ``(start, end)`` for every annotation event named in ``names``,
    whichever thread opened it; not merged."""
    wanted = {tr.ANNOTATION_PREFIX + name for name in names}
    return [
        (e["ts"], e["ts"] + e["dur"]) for e in events
        if tr._complete(e) and tr._annotation(e) in wanted
    ]


def ms_per_step(run, names):
    """Total milliseconds of the spans named in ``names`` over the window's
    steps, or ``None`` where the run has no such span. The window is the one
    ``window_s`` measures, from the annotation's start to the last value
    fetch: a pass the clock stopped before its dispatch opens spans after
    that and adds no step, and is left out. Spans of several threads add up:
    the figure is time at work, not time elapsed."""
    events, steps = run.get("events"), run["window"]["steps"]
    if not events or not steps:
        return None
    opened = window(events)
    if opened is None:
        return None
    lo = opened[0]
    hi = min(opened[1], lo + 1e6 * run["window"]["window_s"])
    found = tr.clip(intervals(events, names), lo, hi)
    if not found:
        return None
    return tr.total(found) / 1e3 / steps
