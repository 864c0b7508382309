"""Async pipelined runner — keep the device busy while the host stages.

Every dispatch pays host batch assembly, staging and the runtime's dispatch
latency *serially* unless they are overlapped. Scan fusion amortizes
the per-dispatch cost but cannot hide the host work between dispatches. This
module owns the overlap:

- **host staging pipeline**: the pass stages up to ``depth`` device chunks
  ahead of the dispatch cursor (``jax.device_put``/sharded placement is
  async, so chunk N+1's host->HBM transfer rides the runtime's stream while
  chunk N's compute runs). The staged queue is byte-capped against the shared
  ~256 MB staging budget (``tpuddp/utils/batching.py``) — depth x chunk bytes
  is real HBM. ``depth`` is a lookahead, not a start-up threshold: the first
  chunk of a pass is dispatched the moment it is staged (the device has
  nothing to run until then, and a staged chunk is in HBM on either side of
  the queue), and the queue fills behind it. There is something to overlap
  only when a pass is several chunks, which is what ``scan_steps: auto``
  sees to (``training/loop.py:resolve_scan_steps``: at least four dispatches
  a pass).
- **dispatch pipelining**: dispatch N+1 is enqueued before N's results land
  (JAX dispatch is asynchronous; the state dependency chains on device), and
  per-dispatch metric pytrees are harvested by a *deferred readback drain* —
  accumulated device-side in dispatch order, fetched only at the telemetry
  window fence / epoch boundary. No per-dispatch ``block_until_ready``,
  ever, unless ``sync_readback`` explicitly asks for the serial cadence
  (the baseline an on/off A/B of the pipeline measures against).
- **occupancy accounting**: the pass reports, per dispatch, the time it spent
  blocked acquiring host batches (``host_stall``), the staged-chunk queue
  depth, and the number of issued-but-unobserved dispatches (in-flight
  depth) through the telemetry hooks -> ``step_stats`` windows
  (schema v3 fields), so wall/device -> 1.0 is directly observable.

Correctness contract: the pipeline NEVER touches the compiled step program
(HLO is byte-identical pipeline-on/off) and never reorders dispatches, so a
pipelined run is bitwise-identical to the synchronous path on params,
opt-state, and comm_state at every depth — asserted in
``tests/test_pipeline.py`` and the full gate's pipeline leg. A preemption
drain returns the state as of the last *issued* dispatch; the emergency
checkpoint's device fetch flushes every in-flight dispatch before anything is
written, so no batch is lost or double-applied.
"""

from __future__ import annotations

import time
from collections import deque
from dataclasses import asdict, dataclass

import jax

from tpuddp.observability import telemetry as telemetry_lib
from tpuddp.observability import trace as trace_lib
from tpuddp.training.step import accumulate_metrics, stack_batches
from tpuddp.utils import batching

# The training.pipeline config block (unknown keys refused — the
# training-block contract, tpuddp/config.py::_merge_refusing_unknown).
PIPELINE_DEFAULTS = {
    "depth": 2,  # staged device chunks held ahead of the dispatch cursor
    # once the pass's first dispatch is out (byte-capped by the ~256 MB
    # staging budget; 1 = single-chunk lookahead)
    "host_workers": 2,  # PrefetchLoader worker threads assembling host
    # batches (0 = inline loading on the dispatch thread)
    "device_augment": True,  # fold normalize/flip/resize into the compiled
    # step (managed path; the native step always compiles augment in) so host
    # workers only decode and stack
    "sync_readback": False,  # serial cadence: block on every dispatch's
    # results before issuing the next (the pre-pipeline A/B baseline; bitwise
    # identical, strictly slower)
}


@dataclass(frozen=True)
class PipelineConfig:
    depth: int = 2
    host_workers: int = 2
    device_augment: bool = True
    sync_readback: bool = False

    def as_dict(self) -> dict:
        return asdict(self)


DEFAULT = PipelineConfig()
# ``pipeline: false`` — the synchronous A/B reference: no staged lookahead,
# no loader workers, one blocking readback per dispatch. device_augment stays
# at its default on purpose: augment placement changes the compiled program,
# and the on/off pair must stay HLO- and bitwise-identical.
SYNCHRONOUS = PipelineConfig(depth=1, host_workers=0, sync_readback=True)


def resolve_pipeline(block) -> PipelineConfig:
    """Resolve the ``training.pipeline`` knob: None/True -> defaults, False ->
    the synchronous reference mode, a dict -> defaults overridden with
    unknown-key refusal (a typo'd knob must not silently run a different
    pipeline than the file says)."""
    if isinstance(block, PipelineConfig):
        return block
    if block is None or block is True:
        return DEFAULT
    if block is False:
        return SYNCHRONOUS
    if not isinstance(block, dict):
        raise ValueError(
            f"training.pipeline must be true/false or a mapping, got {block!r}"
        )
    from tpuddp.config import _merge_refusing_unknown

    cfg = _merge_refusing_unknown(PIPELINE_DEFAULTS, block, "training.pipeline")
    depth = int(cfg["depth"])
    if depth < 1:
        raise ValueError(f"training.pipeline.depth must be >= 1, got {depth}")
    workers = int(cfg["host_workers"])
    if workers < 0:
        raise ValueError(
            f"training.pipeline.host_workers must be >= 0, got {workers}"
        )
    return PipelineConfig(
        depth=depth,
        host_workers=workers,
        device_augment=bool(cfg["device_augment"]),
        sync_readback=bool(cfg["sync_readback"]),
    )


def staging_depth_for(depth: int, chunk_nbytes) -> int:
    """Byte-cap the staged-chunk queue: ``depth`` chunks, bounded so
    depth x chunk bytes stays inside the shared staging budget (the queue is
    real HBM; one policy with every other device-queue cap —
    ``batching.resolve_fuse``). Unknown chunk bytes keep the configured
    depth — the chunker upstream already bounded one chunk by the same
    budget."""
    return batching.resolve_fuse(chunk_nbytes, cap=max(1, int(depth)))


def dispatches_per_pass(n_batches: int, scan_k: int, accum: int = 1) -> int:
    """How many dispatches :func:`run_pass` cuts a pass of ``n_batches`` into:
    the whole ``scan_k``-chunks, then the remainder as single steps (one
    padded scan under ``accum > 1``)."""
    whole, rest = divmod(n_batches, max(1, scan_k))
    return whole + (min(rest, 1) if accum > 1 else rest)


def _leaf_ready(metrics) -> bool:
    """Best-effort 'has this dispatch completed?' probe: True when the first
    array leaf reports ready. Arrays without the probe count as complete —
    the drain then folds eagerly, which is always correct (folding is
    device-side, order-preserving, and never a host sync)."""
    for leaf in jax.tree_util.tree_leaves(metrics):
        ready = getattr(leaf, "is_ready", None)
        if ready is not None:
            try:
                return bool(ready())
            except Exception:
                return True
        return True
    return True


class _ReadbackDrain:
    """Deferred metric harvest: per-dispatch metric pytrees fold into the
    running accumulator in dispatch order (device-side tree adds — async, no
    fetch). The fold is deferred while the dispatch is observably in flight,
    which is what makes the in-flight depth an honest, measurable number;
    the actual host readback happens only at the window fence / epoch end."""

    def __init__(self):
        self.acc = None
        self._pending = deque()

    def offer(self, metrics):
        self._pending.append(metrics)
        # fold every entry whose dispatch has completed (cheap host probe);
        # entries still in flight stay queued — their fold costs nothing to
        # delay, and len(pending) is the in-flight depth telemetry reports
        while self._pending and _leaf_ready(self._pending[0]):
            self.acc = accumulate_metrics(self.acc, self._pending.popleft())

    @property
    def inflight(self) -> int:
        return len(self._pending)

    def drain(self):
        """Fold everything (end of pass / early return). Still no host sync —
        the caller's metric fetch or checkpoint is the fence."""
        while self._pending:
            self.acc = accumulate_metrics(self.acc, self._pending.popleft())
        return self.acc


class StallClock:
    """Accumulates time the dispatch loop spends blocked acquiring host
    batches. With loader workers this is true starvation (the queue was
    empty); with inline loading it is the host batch-assembly time the
    pipeline exists to overlap — either way it is the host-side bound on
    wall/device."""

    def __init__(self):
        self.total = 0.0
        self._since_dispatch = 0.0

    def add(self, dt: float) -> None:
        self.total += dt
        self._since_dispatch += dt

    def take(self) -> float:
        dt, self._since_dispatch = self._since_dispatch, 0.0
        return dt


def stalled_iter(loader, stall: StallClock, tracer=trace_lib.NULL, trace_parent=None):
    """``loader``'s batches, each ``next`` timed into ``stall`` and bracketed
    by an ``input_wait`` span round the same interval (the ``next`` that
    finds the loader exhausted has its span, marked so, and no stall: it
    hands no batch to a dispatch). Closing the generator closes the loader's
    iterator, which is what reaps a ``PrefetchLoader``'s workers."""
    it = iter(loader)
    try:
        while True:
            wait = tracer.start_span(
                "input_wait", trace_lib.KIND_QUEUE_WAIT, parent=trace_parent,
            )
            t0 = time.perf_counter()
            try:
                batch = next(it)
            except StopIteration:
                tracer.end_span(wait, exhausted=True)
                return
            stall.add(time.perf_counter() - t0)
            tracer.end_span(wait)
            yield batch
    finally:
        close = getattr(it, "close", None)
        if close is not None:
            close()


def _pad_to_cycles(chunk, accum: int):
    """Pad a ragged tail chunk with all-padding (weight-0) micro-batches to a
    whole number of accumulation cycles. Padding batches carry zero sample
    weight, so they contribute nothing to gradients, metrics, or BatchNorm
    statistics (nn/loss.py, nn/norm.py) — the cycle's update averages over
    the live samples only. Cost: up to ``accum - 1`` wasted tail micro-steps
    per epoch, the price of keeping the scan shape static."""
    import numpy as np

    x0, y0, w0 = chunk[-1]
    pad = (-len(chunk)) % accum
    return chunk + [(x0, y0, np.zeros_like(w0))] * pad


def _never():
    return False


def run_pass(
    ddp, state, loader, scan_k: int, step_one, step_many, *,
    cfg: PipelineConfig = DEFAULT, probe_cb=None, accum: int = 1,
    poll=_never, inject_cb=None, tel=None, tracer=None, trace_parent=None,
    comm_attrs=None, snap_cb=None, init_acc=None,
):
    """One pipelined pass over ``loader``: K-fused dispatch with a
    ``cfg.depth``-chunk staged device queue and a deferred readback drain.
    Shared by the train and eval passes; ``step_*(state, batch) ->
    (state, metrics)``.

    Semantics are the synchronous pass's, exactly: same batches, same order,
    same dispatch granularity (``scan_k``-chunks, a padded tail under
    ``accum > 1``, single steps for the remainder), so the result is bitwise
    identical at every depth. The first chunk of the pass (the first batch
    on the per-batch cadence) is dispatched as soon as it is staged: until
    then the device has nothing of this pass to run. After it, up to
    ``cfg.depth`` staged chunks are held ahead of the dispatch cursor and
    each newly staged one releases the oldest. ``poll`` (the preemption
    flag) is checked at every batch boundary; an interrupted pass returns
    early with the state as of the last issued dispatch —
    staged-but-undispatched chunks are dropped (the redone epoch re-derives
    them), and the emergency checkpoint's device fetch flushes the in-flight
    dispatches before anything is written.
    ``inject_cb`` (the ``nan@step=N`` chaos hook) may rewrite each host batch
    before staging. ``tel`` (a :class:`~tpuddp.observability.RunTelemetry`;
    None -> inert) brackets each dispatch and receives the occupancy fields
    (host stall, staged queue depth, in-flight depth).

    Tracing (``tracer``, an :mod:`~tpuddp.observability.trace` Tracer; None
    -> inert). On the thread that runs the pass, all children of
    ``trace_parent`` (the driver's epoch span): each ``next`` on the loader an
    ``input_wait`` span (kind ``queue_wait``; the interval the stall clock is
    given, so its total is ``host_stall_s``), each staged placement a
    ``stage`` span with two children of kind ``load``, ``stage_stack``
    (``stack_batches``; absent on the single-batch path) and ``stage_put``
    (``ddp.shard_stacked`` / ``ddp.shard``: the host-side re-tile and the
    transfer's issue), each jitted call a ``dispatch`` span (issue-time
    window — dispatch is async, so the span measures what the HOST paid,
    matching the recorder's lap semantics; ``index`` says which dispatch of
    the pass it is, and the first carries ``head_s``, the time from the
    start of ``run_pass`` to its issue: what the device waited at the head
    of the pass), the deferred metric drain a
    ``readback`` span, and — when ``comm_attrs`` names a live comm hook — a
    zero-length ``collective`` annotation span per dispatch carrying the
    wire-byte accounting. A loader with ``set_tracer`` is handed the tracer
    and the parent for the length of the pass (taken back in a ``finally``)
    and opens ``loader_order``, ``loader_gather`` and ``loader_pad`` (kind
    ``load``) on whichever thread assembles: this one with inline loading,
    inside ``input_wait``; a ``PrefetchLoader`` producer or pool worker
    otherwise. Pure host bracketing of calls this pass already makes: no new
    fences, bitwise identity untouched.

    Step snapshots (``snap_cb``, the async checkpoint engine's hook): called
    between dispatches — AFTER dispatch N's telemetry posts and BEFORE
    dispatch N+1 is issued — with ``(state, real_batches_dispatched,
    drain)``. "Real" excludes the all-padding micro-batches a ragged tail
    stages, so the count addresses actual loader positions. The hook is
    host-side bookkeeping plus async device copies: it must never block
    (the engine skips when its writer queue is full), so the staged queue
    never drains and bitwise identity/HLO are untouched. ``init_acc`` seeds
    the readback drain's accumulator — a resumed mid-epoch pass passes the
    cursor's partial fold so the epoch total equals an uninterrupted run's,
    bitwise.

    Returns ``(state, accumulated_metrics, interrupted)``.
    """
    if tel is None:
        tel = telemetry_lib.NULL
    if tracer is None:
        tracer = trace_lib.NULL
    depth = staging_depth_for(
        cfg.depth,
        (getattr(loader, "batch_nbytes", None) or 0) * max(1, scan_k) or None,
    )
    drain = _ReadbackDrain()
    if init_acc is not None:
        drain.acc = init_acc
    stall = StallClock()
    staged = deque()  # (staged_chunk, n_steps, n_real, n_samples, use_many)
    dispatched_real = 0  # real (non-padding) micro-batches dispatched so far
    n_dispatched = 0  # dispatches issued by this pass
    t_pass = time.perf_counter()

    def dispatch_oldest():
        nonlocal state, dispatched_real, n_dispatched
        chunk, n_steps, n_real, n_samples, use_many = staged.popleft()
        tel.pre_dispatch(n_steps)
        attrs = {"steps": n_steps, "samples": n_samples, "index": n_dispatched}
        if n_dispatched == 0:
            attrs["head_s"] = time.perf_counter() - t_pass
        n_dispatched += 1
        dsp = tracer.start_span(
            "dispatch", trace_lib.KIND_DISPATCH, parent=trace_parent, attrs=attrs,
        )
        if use_many:
            state, metrics = step_many(state, chunk)
        else:
            state, metrics = step_one(state, chunk)
        if cfg.sync_readback:
            # the serial A/B cadence: results land before the next dispatch
            rsp = tracer.start_span(
                "readback", trace_lib.KIND_READBACK, parent=dsp,
            )
            jax.block_until_ready(metrics)
            tracer.end_span(rsp, sync=True)
        drain.offer(metrics)
        if comm_attrs is not None:
            # the comm hook's bucketed exchange runs INSIDE the compiled
            # program — the host cannot time it, so this is an annotation
            # span (zero-length, nested in the dispatch): which hook, how
            # many wire bytes per optimizer update, how many updates this
            # dispatch carried
            tracer.end_span(tracer.start_span(
                "grad_comm", trace_lib.KIND_COLLECTIVE, parent=dsp,
                attrs={**comm_attrs, "updates": max(1, n_steps // max(1, accum))},
            ))
        tracer.end_span(dsp, inflight=drain.inflight)
        tel.post_dispatch(
            n_steps, n_samples, metrics,
            host_stall_s=stall.take(),
            staging_depth=len(staged),
            inflight_depth=drain.inflight,
        )
        dispatched_real += n_real
        if snap_cb is not None:
            # step-boundary snapshot hook: after this dispatch's telemetry,
            # before the next dispatch — never blocking (see docstring)
            snap_cb(state, dispatched_real, drain)

    def stage(host, n_steps, n_real, n_samples, use_many):
        """Place ``host`` on the mesh and queue it: a list of batches to
        stack into one scan chunk (``use_many``), or one batch."""
        ssp = tracer.start_span(
            "stage", trace_lib.KIND_STAGE, parent=trace_parent,
            attrs={"steps": n_steps},
        )
        if use_many:
            csp = tracer.start_span("stage_stack", trace_lib.KIND_LOAD, parent=ssp)
            host = stack_batches(host)
            tracer.end_span(csp)
        csp = tracer.start_span("stage_put", trace_lib.KIND_LOAD, parent=ssp)
        placed = ddp.shard_stacked(host) if use_many else ddp.shard(host)
        tracer.end_span(csp)
        staged.append((placed, n_steps, n_real, n_samples, use_many))
        tracer.end_span(ssp)

    def release():
        """Dispatch what the queue may not hold: everything beyond ``depth``
        staged chunks, and whatever is staged while this pass has issued
        nothing (or, on the serial cadence, at all)."""
        while len(staged) > depth or (
            staged and (n_dispatched == 0 or cfg.sync_readback)
        ):
            dispatch_oldest()

    def drain_all():
        rsp = tracer.start_span(
            "readback", trace_lib.KIND_READBACK, parent=trace_parent,
            attrs={"pending": drain.inflight},
        )
        acc = drain.drain()
        tracer.end_span(rsp)
        return acc

    hand = None if tracer is trace_lib.NULL else getattr(loader, "set_tracer", None)
    if hand is not None:
        hand(tracer, trace_parent)
    batches = stalled_iter(loader, stall, tracer, trace_parent)
    try:
        chunk = []
        for batch_idx, host_batch in enumerate(batches):
            if inject_cb is not None:
                host_batch = inject_cb(host_batch)
            if probe_cb is not None:
                probe_cb(batch_idx, host_batch)
            tel.offer_batch(host_batch)
            if poll():
                return state, drain_all(), True
            if scan_k <= 1 and accum <= 1:
                # per-batch cadence: the staging queue still overlaps batch N+1's
                # placement with batch N's dispatch (the pre-pipeline path staged
                # nothing ahead here and paid the transfer serially). Same depth
                # semantics as the scan path: the first batch goes out at
                # once, then `depth` batches are held staged ahead.
                stage(host_batch, 1, 1, len(host_batch[1]), False)
                release()
                continue
            chunk.append(host_batch)
            if len(chunk) == scan_k:
                stage(chunk, scan_k, scan_k, sum(len(b[1]) for b in chunk), True)
                chunk = []
                # the first chunk goes out at once; after it keep at most
                # `depth` chunks staged ahead and dispatch the oldest beyond
                # that (dispatch is async — the device is already busy)
                release()
        if poll():
            return state, drain_all(), True
        while staged:
            dispatch_oldest()
        if chunk and accum > 1:
            # tail under accumulation: pad to whole cycles, one scan dispatch
            # (a per-batch step would fire a full-scale update per micro-batch)
            tail_samples = sum(len(b[1]) for b in chunk)
            n_real_tail = len(chunk)  # padding batches are not loader positions
            tail = _pad_to_cycles(chunk, accum)
            stage(tail, len(tail), n_real_tail, tail_samples, True)
            dispatch_oldest()
            return state, drain_all(), poll()
        for host_batch in chunk:  # remainder: single steps, same semantics
            if poll():
                return state, drain_all(), True
            stage(host_batch, 1, 1, len(host_batch[1]), False)
            dispatch_oldest()
        return state, drain_all(), poll()
    finally:
        # an interrupted pass abandons the loader mid-epoch: its workers are
        # reaped here, before the tracer they open spans on is taken back
        batches.close()
        if hand is not None:
            hand(None)
