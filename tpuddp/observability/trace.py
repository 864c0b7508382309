"""Causal tracing plane — host-side span trees across training, serving, fleet.

Everything the repo measures today is *aggregate*: percentiles, windows,
counters. None of it answers the causal question — WHICH queue wait, prefill,
decode steps, and failover episode produced a slow serving p99, or WHICH of
staging / dispatch / collective / readback ate a training step's wall time.
This module is the span model that closes that gap:

- a **span** is one timed host-side interval: ``trace_id`` (the tree it
  belongs to), ``span_id``, ``parent_id`` (nesting), a typed ``kind`` (one of
  :data:`SPAN_KINDS`), monotonic start/end clocks (``perf_counter_ns`` —
  wall-clock steps under NTP must not corrupt durations), free-form ``attrs``
  (wire bytes, tenant, replica index), and an optional ``follows_from`` link
  — the causal edge that keeps a failover-resumed decode stream one trace;
- a :class:`Tracer` holds a **bounded per-process ring** of completed spans
  (oldest dropped with explicit ``dropped`` accounting — a long run must not
  grow host memory per span), the open-span set (the crash evidence: the
  flight recorder embeds it on abnormal exits, so a dump shows *where in the
  step* the process died), cumulative per-kind counters, and a small
  slowest-span table;
- export is two-way: :meth:`Tracer.export` writes ``trace_<role>.json`` — a
  Chrome-trace-event artifact (``traceEvents`` + a ``tpuddp`` provenance
  block, schema v9) loadable directly in Perfetto and read by
  ``tpuddp_inspect trace`` — and the live ``/trace`` endpoint on the
  :class:`~tpuddp.observability.exporter.MetricsExporter` serves the last-N
  completed spans (:meth:`Tracer.endpoint_payload`).

Everything is host-side by construction: spans bracket calls the hot paths
already make, never add a ``block_until_ready``, and never touch the compiled
step program — tracing on/off lowers to the identical HLO and a traced run's
loss trajectory is bitwise the untraced one (asserted in tests and the full
gate's tracing leg). Default OFF via the ``observability.tracing`` config
knob; when off the :data:`NULL` tracer's no-op methods are all the hot path
pays.

Clock model: span timestamps are ``perf_counter_ns`` (monotonic). The tracer
captures ONE wall↔monotonic anchor at construction (``clock_sync`` in the
artifact: ``unix_us`` + ``perf_ns`` taken back to back), so export maps every
span onto the unix-epoch microsecond axis Chrome/Perfetto expect. On a pod,
each host's telemetry shard carries the same anchor pair through the
heartbeat channel (:func:`tpuddp.observability.aggregate.make_shard`), which
is what lets a merger correct cross-host skew when overlaying per-host trace
artifacts.
"""

from __future__ import annotations

import json
import logging
import os
import threading
import time
from collections import Counter, deque
from typing import Callable, Dict, List, Optional

logger = logging.getLogger("tpuddp")

DEFAULT_CAPACITY = 4096
_SLOWEST_TABLE = 8  # spans retained in the slowest-span summary table
# /trace serves the last-N completed spans by default: the payload is built
# UNDER the tracer lock, and copying the whole 4096-capacity ring per scrape
# would stall hot-path end_span calls behind every poller
ENDPOINT_SPANS_DEFAULT = 256

# Typed span kinds. Training: one epoch span per epoch, with stage (host
# batch -> device placement), dispatch (the jitted call's issue window),
# collective (the comm hook's bucketed exchange, annotated with wire bytes —
# an annotation span: the exchange itself runs inside the compiled program),
# readback (deferred metric drain / explicit sync) and queue_wait (the runner
# blocked on the loader's next batch) children; load is the host moving a
# batch's bytes: the loader's order / gather / pad, from whichever thread
# assembles, and the two halves of a stage (stack, put). The halves are not
# stage spans themselves because readers that sum by kind (the advisor's
# phase shares, the inspect tool) would count a stage's interval twice. Serving:
# one request span per admitted request with admission / queue_wait /
# prefill / serve children; decode_step spans are the engine-side step
# timeline; failover and probation mark survivability episodes. Fleet: one
# job span per submitted job with action children (start/resize/preempt).
KIND_EPOCH = "epoch"
KIND_STAGE = "stage"
KIND_DISPATCH = "dispatch"
KIND_COLLECTIVE = "collective"
KIND_READBACK = "readback"
KIND_LOAD = "load"
KIND_REQUEST = "request"
KIND_ADMISSION = "admission"
KIND_QUEUE_WAIT = "queue_wait"
KIND_PREFILL = "prefill"
KIND_SERVE = "serve"
KIND_DECODE_STEP = "decode_step"
KIND_FAILOVER = "failover"
KIND_PROBATION = "probation"
KIND_JOB = "job"
KIND_ACTION = "action"

SPAN_KINDS = (
    KIND_EPOCH, KIND_STAGE, KIND_DISPATCH, KIND_COLLECTIVE, KIND_READBACK,
    KIND_LOAD, KIND_REQUEST, KIND_ADMISSION, KIND_QUEUE_WAIT, KIND_PREFILL,
    KIND_SERVE, KIND_DECODE_STEP, KIND_FAILOVER, KIND_PROBATION, KIND_JOB,
    KIND_ACTION,
)


class Span:
    """One completed-or-open host interval. Mutable only through the owning
    tracer (``end_span`` stamps ``t_end_ns``); ``attrs`` is the free-form
    annotation dict callers extend at end time."""

    __slots__ = (
        "trace_id", "span_id", "parent_id", "name", "kind",
        "t_start_ns", "t_end_ns", "attrs", "follows_from", "tid",
    )

    def __init__(
        self, trace_id: str, span_id: int, parent_id: Optional[int],
        name: str, kind: str, t_start_ns: int, tid: str,
        attrs: Optional[dict] = None, follows_from: Optional[int] = None,
    ):
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.name = name
        self.kind = kind
        self.t_start_ns = t_start_ns
        self.t_end_ns: Optional[int] = None
        self.attrs = dict(attrs) if attrs else {}
        self.follows_from = follows_from
        self.tid = tid

    @property
    def duration_ms(self) -> Optional[float]:
        if self.t_end_ns is None:
            return None
        return (self.t_end_ns - self.t_start_ns) / 1e6

    def summary(self) -> dict:
        """Compact dict form (flight-recorder embed, /trace endpoint)."""
        return {
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "name": self.name,
            "kind": self.kind,
            "t_start_ns": self.t_start_ns,
            "duration_ms": (
                None if self.duration_ms is None else round(self.duration_ms, 4)
            ),
            "tid": self.tid,
            "follows_from": self.follows_from,
            "attrs": dict(self.attrs),
        }


class _NullSpan:
    """The inert span the :data:`NULL` tracer hands out — attribute writes
    land nowhere, so instrumented hot paths never branch on enablement."""

    __slots__ = ()
    trace_id = None
    span_id = None
    parent_id = None
    kind = None
    follows_from = None
    duration_ms = None
    attrs: dict = {}

    def summary(self) -> dict:
        return {}


NULL_SPAN = _NullSpan()


class _NullTracer:
    """No-op stand-in when ``observability.tracing`` is off (the default):
    the hot paths call the same two methods unconditionally and pay two
    no-op calls — the NULL-telemetry pattern. Nothing is recorded, no
    artifact is ever written."""

    enabled = False
    role = None

    def new_trace(self) -> None:
        return None

    def start_span(self, *a, **kw) -> _NullSpan:
        return NULL_SPAN

    def end_span(self, span, **attrs) -> None:
        pass

    def open_span_summaries(self) -> list:
        return []

    def endpoint_payload(self, limit=None) -> dict:
        return {"enabled": False, "spans": [], "open": [], "dropped": 0}

    def summary_record(self) -> dict:
        return {}

    def describe(self) -> None:
        return None  # the run_meta ``tracing`` block: null = tracing off

    def export(self, path: Optional[str] = None) -> None:
        return None


NULL = _NullTracer()
NULL_TRACER = NULL  # the package-level export name


class Tracer:
    """The live span recorder for one process and one role (train / serving
    / decode / fleet). Thread-safe: serving dispatch threads and the client
    submit path share one tracer."""

    enabled = True

    def __init__(
        self,
        role: str,
        capacity: int = DEFAULT_CAPACITY,
        run_dir: Optional[str] = None,
        process_index: Optional[int] = None,
    ):
        if process_index is None:
            try:
                import jax

                process_index = jax.process_index()
            except Exception:
                process_index = 0
        self.role = str(role)
        self.capacity = max(1, int(capacity))
        self.run_dir = run_dir
        self.process_index = int(process_index)
        self._lock = threading.Lock()
        self._ring: deque = deque()  # completed spans, oldest first
        self._open: Dict[int, Span] = {}
        self._ids = 0
        self._traces = 0
        self.dropped = 0
        self.completed = 0
        self.kind_counts: Counter = Counter()
        self._slowest: List[dict] = []  # [{name, kind, duration_ms, span_id}]
        self._tids: Dict[str, int] = {}  # tid name -> chrome tid int
        # the ONE wall<->monotonic anchor (taken back to back): every export
        # maps perf_counter_ns onto the unix-us axis through this pair, and
        # the pod shard channel republishes it for cross-host skew correction
        self.clock_unix_us = int(time.time() * 1e6)
        self.clock_perf_ns = time.perf_counter_ns()

    # ------------------------------------------------------------ recording --
    def new_trace(self) -> str:
        """Mint a trace id (one span tree: a training run, one request, one
        job). Unique within this process's artifact, stable across export."""
        with self._lock:
            self._traces += 1
            return f"{self.role}-p{self.process_index}-{self._traces:06d}"

    def start_span(
        self,
        name: str,
        kind: str,
        *,
        trace_id: Optional[str] = None,
        parent=None,
        follows_from: Optional[int] = None,
        tid: Optional[str] = None,
        attrs: Optional[dict] = None,
    ) -> Span:
        """Open one span. ``parent`` (a Span) supplies the trace and the
        nesting edge unless overridden; no parent and no trace_id mints a
        fresh trace. ``follows_from`` is a *causal, non-nesting* predecessor
        span id (the failover link). ``tid`` names the timeline row the span
        renders on (defaults to the parent's row, else the role)."""
        if kind not in SPAN_KINDS:
            raise ValueError(f"unknown span kind {kind!r}; one of {SPAN_KINDS}")
        parent_id = None
        if parent is not None and getattr(parent, "span_id", None) is not None:
            parent_id = parent.span_id
            if trace_id is None:
                trace_id = parent.trace_id
            if tid is None:
                tid = parent.tid
        if trace_id is None:
            trace_id = self.new_trace()
        now = time.perf_counter_ns()
        with self._lock:
            self._ids += 1
            span = Span(
                trace_id, self._ids, parent_id, str(name), kind, now,
                tid if tid is not None else self.role, attrs,
                follows_from=follows_from,
            )
            self._open[span.span_id] = span
        return span

    def end_span(self, span, **attrs) -> None:
        """Close one span (idempotent; the NULL span is ignored): stamp the
        end clock, move it into the bounded ring (dropping — and counting —
        the oldest past capacity), update the per-kind counters and the
        slowest-span table. The stamp, the attrs merge, AND the
        already-closed check all happen under the tracer lock: a /trace
        scrape or flight dump iterating ``span.attrs`` under the same lock
        must never see it mid-update, and two racing closers must never
        ring the same span twice."""
        if not isinstance(span, Span):
            return
        now = time.perf_counter_ns()
        with self._lock:
            if span.t_end_ns is not None:
                return
            span.t_end_ns = now
            if attrs:
                span.attrs.update(attrs)
            self._open.pop(span.span_id, None)
            if len(self._ring) >= self.capacity:
                self._ring.popleft()
                self.dropped += 1
            self._ring.append(span)
            self.completed += 1
            self.kind_counts[span.kind] += 1
            dur = span.duration_ms or 0.0
            if (
                len(self._slowest) < _SLOWEST_TABLE
                or dur > self._slowest[-1]["duration_ms"]
            ):
                self._slowest.append({
                    "name": span.name,
                    "kind": span.kind,
                    "duration_ms": round(dur, 4),
                    "span_id": span.span_id,
                })
                self._slowest.sort(
                    key=lambda r: r["duration_ms"], reverse=True
                )
                del self._slowest[_SLOWEST_TABLE:]

    # ------------------------------------------------------------ live views --
    def open_span_summaries(self) -> List[dict]:
        """The still-open spans, outermost first — what the flight recorder
        embeds on abnormal exit so a crash dump names the exact stage the
        process died in. Summaries are built UNDER the lock: an open span's
        attrs may be mid-update by a concurrent ``end_span`` otherwise."""
        with self._lock:
            return [
                s.summary()
                for s in sorted(self._open.values(), key=lambda s: s.span_id)
            ]

    def endpoint_payload(
        self, limit: Optional[int] = ENDPOINT_SPANS_DEFAULT
    ) -> dict:
        """The ``/trace`` endpoint's JSON: the last-``limit`` completed
        spans (newest last; ``None``/0 = the whole ring) plus the open set
        and drop accounting. Copied under the lock — which is why the
        default is bounded: a scrape must not hold the lock for a
        4096-span copy while dispatch threads wait to end spans.
        Serialization happens in the endpoint, outside the lock."""
        with self._lock:
            spans = list(self._ring)
            if limit is not None and limit > 0:
                spans = spans[-int(limit):]
            payload = {
                "enabled": True,
                "role": self.role,
                "process_index": self.process_index,
                "capacity": self.capacity,
                "completed": self.completed,
                "dropped": self.dropped,
                "spans": [s.summary() for s in spans],
                "open": [
                    s.summary()
                    for s in sorted(self._open.values(), key=lambda s: s.span_id)
                ],
            }
        return payload

    def summary_record(self) -> dict:
        """The typed ``trace_summary`` history record (schema v9): span and
        drop accounting plus the slowest-span table — the one-line causal
        digest a reader gets without opening the artifact."""
        with self._lock:
            return {
                "role": self.role,
                "spans": self.completed,
                "dropped": self.dropped,
                "open_spans": len(self._open),
                "traces": self._traces,
                "by_kind": dict(self.kind_counts),
                "slowest": [dict(r) for r in self._slowest],
            }

    def describe(self) -> dict:
        """The run_meta ``tracing`` provenance block (schema v9)."""
        return {"capacity": self.capacity, "artifact": self.artifact_name()}

    # --------------------------------------------------------------- export --
    def artifact_name(self) -> str:
        """``trace_<role>.json``; non-zero processes qualify the name (the
        run dir is shared on a pod — the flight-recorder convention)."""
        if self.process_index == 0:
            return f"trace_{self.role}.json"
        return f"trace_{self.role}_p{self.process_index}.json"

    def _ts_us(self, t_ns: int) -> float:
        return self.clock_unix_us + (t_ns - self.clock_perf_ns) / 1e3

    def _tid_for(self, name: str) -> int:
        if name not in self._tids:
            self._tids[name] = len(self._tids)
        return self._tids[name]

    def chrome_payload(self) -> dict:
        """The full Chrome-trace-event artifact payload: completed spans as
        ``ph: "X"`` complete events, still-open spans as X events flagged
        ``open`` (their dur runs to "now" — the honest crash view), flow
        ``s``/``f`` pairs for every ``follows_from`` edge whose predecessor
        survived the ring, and process/thread metadata rows.

        The whole event build runs under the tracer lock (export is a
        drain/crash-path rarity): an open span's attrs may be mid-``end_span``
        on a live dispatch thread otherwise."""
        from tpuddp.observability import schema

        now_ns = time.perf_counter_ns()
        with self._lock:
            spans = list(self._ring) + sorted(
                self._open.values(), key=lambda s: s.span_id
            )
            meta = {
                "type": "trace",
                "schema_version": None,  # stamped by the caller (export)
                "role": self.role,
                "process_index": self.process_index,
                "capacity": self.capacity,
                "spans": self.completed,
                "dropped": self.dropped,
                "open_spans": len(self._open),
                "traces": self._traces,
                "by_kind": dict(self.kind_counts),
                "slowest": [dict(r) for r in self._slowest],
                "clock_sync": {
                    "unix_us": self.clock_unix_us,
                    "perf_ns": self.clock_perf_ns,
                },
            }
            meta["schema_version"] = schema.SCHEMA_VERSION
            pid = self.process_index
            by_id = {s.span_id: s for s in spans}  # O(1) follows_from lookups
            events = [
                {
                    "ph": "M", "name": "process_name", "pid": pid, "tid": 0,
                    "args": {"name": f"tpuddp {self.role} p{pid}"},
                },
            ]
            for tname in sorted({s.tid for s in spans}):
                events.append({
                    "ph": "M", "name": "thread_name", "pid": pid,
                    "tid": self._tid_for(tname), "args": {"name": tname},
                })
            flow = 0
            for s in spans:
                open_span = s.t_end_ns is None
                end_ns = now_ns if open_span else s.t_end_ns
                args = {
                    "trace_id": s.trace_id,
                    "span_id": s.span_id,
                    "parent_id": s.parent_id,
                    **s.attrs,
                }
                if s.follows_from is not None:
                    args["follows_from"] = s.follows_from
                if open_span:
                    args["open"] = True
                events.append({
                    "ph": "X",
                    "name": s.name,
                    "cat": s.kind,
                    "pid": pid,
                    "tid": self._tid_for(s.tid),
                    "ts": round(self._ts_us(s.t_start_ns), 3),
                    "dur": round(max(end_ns - s.t_start_ns, 0) / 1e3, 3),
                    "args": args,
                })
                if s.follows_from is not None and s.follows_from in by_id:
                    pred = by_id[s.follows_from]
                    flow += 1
                    pred_end = (
                        pred.t_end_ns if pred.t_end_ns is not None else now_ns
                    )
                    events.append({
                        "ph": "s", "id": flow, "name": "follows_from",
                        "cat": "flow", "pid": pid,
                        "tid": self._tid_for(pred.tid),
                        "ts": round(self._ts_us(pred_end), 3),
                    })
                    events.append({
                        "ph": "f", "bp": "e", "id": flow,
                        "name": "follows_from",
                        "cat": "flow", "pid": pid,
                        "tid": self._tid_for(s.tid),
                        "ts": round(self._ts_us(s.t_start_ns), 3),
                    })
        return {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "tpuddp": meta,
        }

    def export(self, path: Optional[str] = None) -> Optional[str]:
        """Write the artifact atomically (tmp+fsync+rename — the flight
        recorder's contract: drains and crash paths call this and must
        proceed regardless). Returns the path, or None without a
        destination / on a failed best-effort write."""
        if path is None:
            if self.run_dir is None:
                return None
            path = os.path.join(self.run_dir, self.artifact_name())
        tmp = f"{path}.tmp.{os.getpid()}"
        try:
            os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
            from tpuddp.observability.metrics import json_sanitize

            with open(tmp, "w") as f:
                json.dump(
                    json_sanitize(self.chrome_payload()), f, allow_nan=False
                )
                f.write("\n")
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
        except (OSError, ValueError) as e:
            logger.warning("trace export (%s) failed: %s", path, e)
            try:
                os.remove(tmp)
            except OSError:
                pass
            return None
        logger.info(
            "trace: %d span(s) (%d dropped) -> %s",
            self.completed, self.dropped, path,
        )
        return path


def end_request_trace(tracer, request, error) -> None:
    """Close a queued/serving request's trace context — the ONE
    close-with-error sequence every failure exit shares across both serving
    engines (shed, retry exhaustion, max-failovers, mortuary): stringify
    the error (exception or reason string), end the open child span if any,
    end the root, clear ``request.trace``. No-op for untraced requests."""
    trace = getattr(request, "trace", None)
    if not trace:
        return
    reason = error if isinstance(error, str) else repr(error)
    open_span = trace.get("open")
    if open_span is not None:
        tracer.end_span(open_span, error=reason)
    tracer.end_span(trace["root"], error=reason)
    request.trace = None


def tracer_from_config(
    obs_cfg, role: str, run_dir: Optional[str] = None
):
    """Build the role's tracer from a resolved ``observability`` block
    (tpuddp/config.py:OBSERVABILITY_DEFAULTS): :data:`NULL` unless
    ``tracing`` is armed — the off path must cost nothing and write
    nothing."""
    if not obs_cfg or not obs_cfg.get("tracing"):
        return NULL
    return Tracer(
        role,
        capacity=int(obs_cfg.get("trace_capacity") or DEFAULT_CAPACITY),
        run_dir=run_dir,
    )
