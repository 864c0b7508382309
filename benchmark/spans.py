"""The benchmark's own host spans, put round its calls into each layer.

Every span is timed on the host's clock and, while the profiler runs, also
written into the profiler's trace (``jax.profiler.TraceAnnotation``), so that
``trace_reduce`` can say what the host was doing in each gap of the device's
timeline. ``RunnerTracer`` hands the same recorder to ``pipeline.run_pass``
through its ``tracer=`` interface, so the runner's own ``stage``,
``dispatch`` and ``readback`` spans land here under their own names.
"""

from __future__ import annotations

import collections
import contextlib
import time

import jax

from benchmark.trace_reduce import ANNOTATION_PREFIX, WINDOW_ANNOTATION

# the span the measured window runs under; trace_reduce clips to it
WINDOW = WINDOW_ANNOTATION[len(ANNOTATION_PREFIX):]


class Spans:
    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self.seconds = collections.defaultdict(float)
        self.counts = collections.Counter()

    @contextlib.contextmanager
    def span(self, name: str):
        note = (
            jax.profiler.TraceAnnotation(ANNOTATION_PREFIX + name)
            if self.annotate else contextlib.nullcontext()
        )
        t0 = time.perf_counter()
        with note:
            try:
                yield
            finally:
                self.seconds[name] += time.perf_counter() - t0
                self.counts[name] += 1

    def reset(self) -> None:
        self.seconds.clear()
        self.counts.clear()


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
