"""RunTelemetry — the one object an epoch driver wires through its hot loop.

Bundles the step recorder (:mod:`recorder`), the $TPUDDP_PROFILE_STEPS
window profiler and the SIGUSR1 epoch-trace trigger (:mod:`profiling`)
behind two per-dispatch calls:

    tel.pre_dispatch(n_steps)                  # before issuing the dispatch
    tel.post_dispatch(n_steps, n_samples, m)   # after, m = its output pytree

plus ``start_epoch``/``end_epoch`` at epoch boundaries and ``finish`` in the
driver's ``finally``. Everything is host-side: the compiled step program is
never touched (telemetry on/off lowers to the identical HLO), no collectives
are added, and the only device syncs are the per-window fence and the
profiler's end-of-window flush.

The live telemetry plane (ISSUE 10) attaches here too: ``attach_live``
wires the /metrics exporter (this run's gauges/counters/summaries), the
telemetry-shard publisher (this host's last window into the heartbeat
channel), and the main-process pod aggregator — all pumped at the
per-window boundary the recorder already fences, so "exporter + aggregator
on" adds zero device syncs and zero collectives.
"""

from __future__ import annotations

from typing import Callable, Optional

from tpuddp.observability import profiling
from tpuddp.observability.recorder import StepStatsRecorder, estimate_step_flops


class _NullTelemetry:
    """Inert stand-in so hot loops call the hooks unconditionally — a
    dispatch site can never forget a ``tel is not None`` guard because
    there is none."""

    def offer_batch(self, host_batch) -> None:
        pass

    def pre_dispatch(self, n_steps: int) -> None:
        pass

    def post_dispatch(self, n_steps: int, n_samples: int, fence=None, **occ) -> None:
        pass

    def start_epoch(self, epoch: int) -> None:
        pass

    def end_epoch(self) -> dict:
        return {}

    def finish(self) -> None:
        pass


NULL = _NullTelemetry()


class RunTelemetry:
    def __init__(
        self,
        writer=None,
        save_dir: Optional[str] = None,
        step_stats_every: int = 0,
        flops_lower_fn: Optional[Callable] = None,
        device_kind: Optional[str] = None,
    ):
        """``flops_lower_fn``: zero-arg callable returning the lowered
        single-step program, compiled once (lazily, failure-tolerant, and
        only where the chip has a peak to divide by) to resolve per-step
        FLOPs for the MFU fields; None leaves MFU null.
        ``device_kind``: the MESH device's kind (for the peak-FLOPs lookup)
        — pass it so a CPU-ladder run on a TPU-attached host (or the
        reverse) reports MFU against the right ceiling."""
        from tpuddp.observability.recorder import device_peak_flops

        self.recorder = StepStatsRecorder(
            writer=writer,
            window=step_stats_every,
            peak_flops=device_peak_flops(device_kind),
        )
        self.window_profiler = profiling.StepWindowProfiler(save_dir)
        self.writer = writer
        self.save_dir = save_dir
        self.flops_lower_fn = flops_lower_fn
        self.batch_struct = None
        self._flops_probed = False
        self._epoch_trace = False
        self._last_fence = None
        # live plane (attach_live): exporter/aggregator/shard channel plus
        # driver-updated gauges (skip counters, comm bytes, last losses)
        self.exporter = None
        self.aggregator = None
        self._shard_dir = None
        self._shard_pid = 0
        self.live: dict = {}
        self.recorder.on_window = self._on_window
        profiling.install_sigusr1_trigger()

    # -- live telemetry plane (ISSUE 10) -----------------------------------

    def attach_live(
        self,
        exporter=None,
        aggregator=None,
        shard_dir=None,
        process_id: int = 0,
    ) -> None:
        """Wire the live plane: ``exporter`` gets this run's training source,
        ``shard_dir`` arms per-window shard publishing into the heartbeat
        channel (also registered as the watchdog beat's payload so liveness
        rewrites carry the freshest shard), ``aggregator`` (main process) is
        pumped at every window boundary. All host-side, all at the existing
        per-window cadence — no new fences."""
        self.exporter = exporter
        self.aggregator = aggregator
        self._shard_dir = shard_dir
        self._shard_pid = int(process_id)
        if exporter is not None:
            exporter.register_source("training", self.export_source())
            if aggregator is not None:
                exporter.register_source("pod", aggregator.export_source())
        if shard_dir is not None:
            from tpuddp.resilience import watchdog as wd

            wd.set_heartbeat_payload(self._shard)

    def update_live(self, **fields) -> None:
        """Driver-side live gauges the recorder cannot see (guard skip
        totals, comm bytes, last epoch losses) — merged into the exporter's
        training source and the published shard."""
        self.live.update(fields)

    def _shard(self):
        from tpuddp.observability import aggregate

        return aggregate.make_shard(
            self.recorder.live_snapshot(),
            skipped_steps=self.live.get("skipped_steps") or 0,
        )

    def _on_window(self) -> None:
        """Recorder window-boundary pump: publish this host's shard, merge
        the pod view (main process). The window fence already happened —
        this is file IO + arithmetic only."""
        if self._shard_dir is not None:
            from tpuddp.observability import aggregate

            aggregate.publish_shard(
                self._shard_dir, self._shard_pid, self._shard()
            )
        if self.aggregator is not None:
            self.aggregator.update()

    def export_source(self):
        """The exporter's training source: cumulative counters + the last
        emitted window's percentiles (exactly what history.jsonl flushed)."""
        from tpuddp.observability import exporter as exp

        def source():
            live = self.recorder.live_snapshot()
            series = {
                "train_steps_total": exp.counter(
                    live.get("step"), "train steps since loop entry"
                ),
                "train_samples_total": exp.counter(
                    live.get("samples_total"), "global samples dispatched"
                ),
                "epoch": exp.gauge(live.get("epoch"), "current epoch"),
                "step_time_ms": exp.summary(
                    {
                        "0.5": live.get("step_time_ms_p50"),
                        "0.95": live.get("step_time_ms_p95"),
                        "0.99": live.get("step_time_ms_p99"),
                        "1.0": live.get("step_time_ms_max"),
                    },
                    "last-window per-step wall time",
                ),
                "train_samples_per_sec": exp.gauge(
                    live.get("samples_per_sec"), "last-window throughput"
                ),
                "mfu": exp.gauge(
                    live.get("mfu_p50"), "last-window achieved MFU at p50"
                ),
                "host_stall_ms_total": exp.counter(
                    live.get("host_stall_ms_total"),
                    "cumulative host-blocked time",
                ),
                "step_stats_windows_total": exp.counter(
                    live.get("windows_emitted"), "step_stats rows flushed"
                ),
            }
            for key, help_text in (
                ("skipped_steps", "guard-skipped updates (total)"),
                ("grad_comm_bytes_total", "gradient bytes on the wire"),
                ("train_loss", "last completed epoch train loss"),
                ("test_loss", "last completed epoch test loss"),
                ("test_accuracy", "last completed epoch test accuracy (%)"),
            ):
                if key in self.live:
                    kind = (
                        exp.counter
                        if key in ("skipped_steps", "grad_comm_bytes_total")
                        else exp.gauge
                    )
                    series[key] = kind(self.live[key], help_text)
            return series

        return source

    def offer_batch(self, host_batch) -> None:
        """Capture the abstract (shape, dtype) structure of one host batch —
        the FLOPs probe lowers the step program against it later. Reads only
        array metadata; nothing is copied or placed."""
        if self.batch_struct is not None:
            return
        try:
            import jax
            import numpy as np

            self.batch_struct = tuple(
                jax.ShapeDtypeStruct(np.shape(b), np.asarray(b).dtype)
                for b in host_batch
            )
        except Exception:  # metadata-only best effort; MFU stays null
            self.batch_struct = ()

    # -- hot-loop hooks (cheap: integer compares + perf_counter) -----------

    def pre_dispatch(self, n_steps: int) -> None:
        self.window_profiler.before_dispatch(self.recorder.global_step, n_steps)

    def post_dispatch(
        self, n_steps: int, n_samples: int, fence=None, *,
        host_stall_s: float = 0.0, staging_depth: int = 0,
        inflight_depth: int = 0,
    ) -> None:
        """``host_stall_s``/``staging_depth``/``inflight_depth``: the async
        pipeline's occupancy sample for this dispatch (time the dispatch loop
        spent blocked acquiring host batches since the previous dispatch, the
        staged-chunk queue depth, and issued-but-unobserved dispatches) —
        surfaced in step_stats windows and the epoch summary."""
        self._last_fence = fence
        self.recorder.record(
            n_steps, n_samples, fence=fence, host_stall_s=host_stall_s,
            staging_depth=staging_depth, inflight_depth=inflight_depth,
        )
        self.window_profiler.after_dispatch(self.recorder.global_step, fence)

    # -- epoch boundaries --------------------------------------------------

    def start_epoch(self, epoch: int) -> None:
        self.recorder.start_epoch(epoch)
        if profiling.consume_sigusr1_request():
            self._epoch_trace = profiling.start_epoch_trace(self.save_dir, epoch)
            if self._epoch_trace and self.writer is not None:
                from tpuddp.observability import schema

                self.writer.write(
                    schema.stamp(
                        "event", {"event": "profile_epoch", "epoch": epoch}
                    )
                )

    def stop_epoch_trace(self) -> None:
        """Flush an active SIGUSR1 epoch trace. Runs inside :meth:`end_epoch`
        by default; a driver whose train summary happens BEFORE evaluation
        (the managed loop) passes ``stop_trace=False`` there and calls this
        after eval, so the 'trace the next epoch' contract covers the whole
        epoch on both drivers."""
        if self._epoch_trace:
            profiling.stop_profiler()
            self._epoch_trace = False

    def end_epoch(self, stop_trace: bool = True) -> dict:
        """Step-time/MFU fields for the epoch's history row (call after the
        epoch's metric fetch — the device is already fenced there)."""
        if stop_trace:
            self.stop_epoch_trace()
        if (
            not self._flops_probed and self.flops_lower_fn is not None
            and self.recorder.peak_flops  # no table entry: MFU is null anyway
        ):
            # once per run, at the FIRST epoch boundary (never in the hot
            # loop): compiles the single-step program, executes nothing
            self._flops_probed = True
            self.recorder.flops_per_step = estimate_step_flops(
                self.flops_lower_fn
            )
        return self.recorder.epoch_summary()

    def finish(self) -> None:
        """Driver ``finally``: flush any partial step-window trace (it is the
        post-mortem artifact), release the trace latch, and detach the live
        plane (heartbeat shards must not outlive the telemetry they carry)."""
        self.window_profiler.finish(self._last_fence)
        self.stop_epoch_trace()
        if self._shard_dir is not None:
            from tpuddp.resilience import watchdog as wd

            wd.set_heartbeat_payload(None)
            self._shard_dir = None
        if self.exporter is not None:
            self.exporter.unregister_source("training")
            self.exporter.unregister_source("pod")
            self.exporter = None
        self.aggregator = None
