"""The benchmark's own host spans, put round its calls into each layer.

Every span is timed on the host's clock and also kept as an interval of the
wall clock, which is the clock the profiler stamps its capture with: after a
traced run ``trace_reduce`` lays the intervals beside the device's timeline
and says what the host was doing in each gap. The profiler's own host tracer stays
off: it logs every slice of a staged chunk's relayout, some millions of events
a pass, and made the device wait seven times longer for its input than an
untraced run does (chip runs, PR 26). ``RunnerTracer`` hands the same recorder
to ``pipeline.run_pass`` through its ``tracer=`` interface, so the runner's own
``stage``, ``dispatch`` and ``readback`` spans land here under their own
names.
"""

from __future__ import annotations

import collections
import contextlib
import json
import time

from benchmark.trace_reduce import ANNOTATION_PREFIX, WINDOW_ANNOTATION

# the span the measured window runs under; trace_reduce clips to it
WINDOW = WINDOW_ANNOTATION[len(ANNOTATION_PREFIX):]


class Spans:
    def __init__(self):
        self.seconds = collections.defaultdict(float)
        self.counts = collections.Counter()
        self.intervals = []  # (name, start, end) in nanoseconds of the wall clock

    @contextlib.contextmanager
    def span(self, name: str):
        wall0 = time.time_ns()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.seconds[name] += time.perf_counter() - t0
            self.counts[name] += 1
            self.intervals.append((name, wall0, time.time_ns()))

    def reset(self) -> None:
        self.seconds.clear()
        self.counts.clear()
        self.intervals.clear()

    def save(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.intervals, f)


class _Open:
    __slots__ = ("name", "ctx")

    def __init__(self, name, ctx):
        self.name, self.ctx = name, ctx


class RunnerTracer:
    """``tpuddp.observability.trace``'s two-method tracer interface
    (``start_span``/``end_span``) over a :class:`Spans`. Zero-length
    annotation spans of the runner (``grad_comm``) are recorded like any
    other; attributes are dropped."""

    enabled = True

    def __init__(self, spans: Spans):
        self.spans = spans

    def start_span(self, name, kind=None, **_):
        ctx = self.spans.span(name)
        ctx.__enter__()
        return _Open(name, ctx)

    def end_span(self, span, **_):
        if isinstance(span, _Open) and span.ctx is not None:
            ctx, span.ctx = span.ctx, None
            ctx.__exit__(None, None, None)
