"""Step-level telemetry recorder — per-step wall times, percentiles, MFU.

The epoch drivers dispatch in *batch groups* (one jitted call covering
``scan_k`` fused steps), and dispatch is asynchronous: a ``perf_counter``
lap around one dispatch measures issue time, not execution. Timing therefore
works at the honest granularity:

- every dispatch contributes ``n_steps`` ring-buffer entries of
  ``lap / n_steps`` (per-step wall time at dispatch resolution — uniform
  within a fused group, exact at ``scan_k = 1``);
- at *window boundaries* (``training.step_stats_every`` steps) the recorder
  blocks on the last dispatch's metrics — ONE device sync per window, never
  inside a compiled program, so fused/scan paths stay fused and the step
  program is untouched (HLO-identical with telemetry on or off) — and emits
  a ``step_stats`` record;
- the epoch summary (percentiles over the whole epoch's entries) lands in
  the epoch's ``history.jsonl`` row, where the epoch barrier has already
  fenced the device, making the aggregate honest even with windows disabled.

Achieved MFU is best-effort: FLOPs come from XLA cost analysis of the exact
step program when a probe is available (``estimate_step_flops``), the peak
from the chip's spec-sheet bf16 ceiling (:data:`PEAK_FLOPS` — also the
bench's table). Unknown chip or unresolvable FLOPs -> MFU fields are null,
never guessed — and a probe that fails says why at warning level.
"""

from __future__ import annotations

import logging
import time
from typing import Callable, Optional

import numpy as np

from tpuddp.observability import schema

logger = logging.getLogger("tpuddp")

# Peak bf16 MXU FLOP/s per chip by device kind (public spec sheets). MFU is
# always reported against the bf16 peak: on TPU, f32 matmuls execute on the
# MXU with bf16 multiplies by default, so bf16 peak is the one ceiling.
PEAK_FLOPS = {
    "TPU v5 lite": 197e12,  # v5e
    "TPU v5e": 197e12,
    "TPU v4": 275e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,  # v6e / Trillium
}


def device_peak_flops(kind: Optional[str] = None) -> Optional[float]:
    """Spec-sheet bf16 peak FLOP/s for the (first) local device; None when
    the chip is unknown (e.g. the CPU test world) — MFU is then null."""
    if kind is None:
        import jax

        devices = jax.devices()
        if not devices:
            return None
        kind = devices[0].device_kind
    return PEAK_FLOPS.get(kind)


def percentiles(step_times_s, keys=(50, 95, 99)) -> dict:
    """``{"p50": ..., "p95": ..., "p99": ..., "max": ...}`` in SECONDS over a
    sequence of per-step times; all-None when the sequence is empty."""
    arr = np.asarray(list(step_times_s), dtype=np.float64)
    if arr.size == 0:
        return {f"p{k}": None for k in keys} | {"max": None}
    out = {f"p{k}": float(np.percentile(arr, k)) for k in keys}
    out["max"] = float(arr.max())
    return out


def step_time_fields(step_times_s, flops_per_step=None, peak_flops=None) -> dict:
    """The shared record fields: step-time percentiles in ms plus the
    achieved-MFU transform of the same percentiles (MFU at the median step
    time, and at the p95 tail — the straggler-visible figure)."""
    pct = percentiles(step_times_s)
    fields = {
        f"step_time_ms_{k}": (None if v is None else round(v * 1e3, 4))
        for k, v in pct.items()
    }

    def mfu(t):
        if t is None or not t or not flops_per_step or not peak_flops:
            return None
        # 6 decimals: tiny-but-real utilizations (a toy model on a big chip)
        # must not round to a dishonest exact 0
        return round(flops_per_step / t / peak_flops, 6)

    fields["mfu_p50"] = mfu(pct["p50"])
    fields["mfu_p95"] = mfu(pct["p95"])
    return fields


def estimate_step_flops(lower_fn: Callable[[], "object"]) -> Optional[float]:
    """Per-chip FLOPs of one step from XLA cost analysis of the COMPILED
    single-step program. The TPU plug-in offers no cost analysis of a program
    that is only lowered (``Lowered.cost_analysis`` is unimplemented on PJRT
    C-API backends and returns None), so the probe pays one compile of the
    single-step program, once per run; with the persistent compile cache on
    (utils/compile_cache.py) later runs load it instead.

    ``lower_fn`` returns a ``jax.stages.Lowered`` for the SINGLE-step program
    (no scan-body counting ambiguity). A compiled program is the partitioned,
    per-device one, so its figure is already per chip: one chip at batch 128
    and four chips at global batch 512 both report 4.88e11 for AlexNet@224
    (chip runs, PR 21), in ``shard_map`` and ``auto`` mode alike. Any failure
    (tracing, unsupported backend, zero figure) returns None and logs the
    reason: MFU is reported as unknown, never guessed, and never quietly."""
    try:
        t0 = time.perf_counter()
        cost = lower_fn().compile().cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        flops = float(cost.get("flops", 0.0))
        if flops <= 0:
            raise ValueError(f"cost analysis reports {flops} flops")
        logger.info(
            "FLOPs probe: %.3g flops per step per chip, resolved in %.1f s",
            flops, time.perf_counter() - t0,
        )
        return flops
    except Exception as e:
        logger.warning(
            "FLOPs probe failed (%s: %s); the MFU fields stay null",
            type(e).__name__, e,
        )
        return None


class StepStatsRecorder:
    """Host-side ring buffer of per-step wall times for ONE training run.

    ``record(n_steps, n_samples, fence=...)`` is called once per dispatch by
    the epoch driver; everything else is bookkeeping around the ring. The
    ring (``capacity`` entries, oldest overwritten) bounds memory on long
    runs; the *epoch* slice used for summaries is reset by
    :meth:`epoch_summary`, so an epoch longer than the capacity degrades to
    the newest ``capacity`` steps with a recorded ``step_stats_truncated``
    count instead of silently skewing percentiles."""

    def __init__(
        self,
        writer=None,
        window: int = 0,
        capacity: int = 65536,
        flops_per_step: Optional[float] = None,
        peak_flops="auto",
        on_window=None,
    ):
        """``peak_flops``: the chip ceiling for MFU — "auto" looks up the
        default device's kind; pass an explicit value (or None, a legitimate
        "unknown" for chips without a table entry) when the caller knows the
        mesh's device better than the default platform does.
        ``on_window``: zero-arg callable invoked right after each window row
        is emitted — the live telemetry plane's pump (shard publish +
        pod aggregation, tpuddp/observability/aggregate.py); host-side only,
        runs at the per-window fence that already exists."""
        self.writer = writer
        self.on_window = on_window
        self.window = max(0, int(window or 0))
        self.capacity = int(capacity)
        self.flops_per_step = flops_per_step
        self.peak_flops = (
            device_peak_flops() if peak_flops == "auto" else peak_flops
        )
        self._ring = np.zeros((self.capacity,), np.float64)
        self._n = 0  # total entries ever written (ring index = _n % capacity)
        self.global_step = 0  # train steps since loop entry (resume-relative)
        # live-plane state: the last emitted step_stats record (what a
        # /metrics scrape and the pod shard publish — both read-only, both
        # matching the flushed history exactly) and run-cumulative counters
        self.last_window: Optional[dict] = None
        self.windows_emitted = 0
        self.total_samples = 0
        self.total_stall_s = 0.0
        self._epoch = 0
        self._epoch_start_n = 0
        self._epoch_samples = 0
        self._epoch_t0: Optional[float] = None
        self._last_t: Optional[float] = None
        # pipeline-occupancy accounting (tpuddp/training/pipeline.py): host
        # stall accumulates per epoch/window; queue depths keep the window max
        self._epoch_stall = 0.0
        self._win_stall = 0.0
        self._win_staging_max = 0
        self._win_inflight_max = 0
        # window accounting
        self._win_start_n = 0
        self._win_start_step = 0
        self._win_samples = 0
        self._win_t0: Optional[float] = None

    # -- epoch lifecycle ---------------------------------------------------

    def start_epoch(self, epoch: int) -> None:
        now = time.perf_counter()
        self._epoch = int(epoch)
        self._epoch_start_n = self._n
        self._epoch_samples = 0
        self._epoch_t0 = now
        self._last_t = now
        self._epoch_stall = 0.0
        self._win_stall = 0.0
        self._win_staging_max = 0
        self._win_inflight_max = 0
        self._win_start_n = self._n
        self._win_start_step = self.global_step
        self._win_samples = 0
        self._win_t0 = now

    def record(
        self, n_steps: int, n_samples: int, fence=None, *,
        host_stall_s: float = 0.0, staging_depth: int = 0,
        inflight_depth: int = 0,
    ) -> None:
        """One dispatch of ``n_steps`` fused steps covering ``n_samples``
        global samples. ``fence`` is the dispatch's output (any pytree of
        device arrays); it is blocked on ONLY at a window boundary.
        ``host_stall_s``/``staging_depth``/``inflight_depth`` are the async
        pipeline's occupancy sample for this dispatch (host-blocked seconds
        since the previous one; staged-chunk / in-flight queue lengths)."""
        now = time.perf_counter()
        if self._last_t is None:  # record() without start_epoch: self-arm
            self.start_epoch(self._epoch)
            now = self._last_t
        lap = now - self._last_t
        n_steps = max(1, int(n_steps))
        per_step = lap / n_steps
        for i in range(n_steps):
            self._ring[(self._n + i) % self.capacity] = per_step
        self._n += n_steps
        self.global_step += n_steps
        self._epoch_samples += int(n_samples)
        self._win_samples += int(n_samples)
        self.total_samples += int(n_samples)
        self._epoch_stall += float(host_stall_s)
        self._win_stall += float(host_stall_s)
        self.total_stall_s += float(host_stall_s)
        self._win_staging_max = max(self._win_staging_max, int(staging_depth))
        self._win_inflight_max = max(self._win_inflight_max, int(inflight_depth))
        self._last_t = now
        if self.window and (self._n - self._win_start_n) >= self.window:
            self._emit_window(fence)

    def _slice(self, start_n: int) -> np.ndarray:
        """Ring entries [start_n, self._n), newest-capacity-bounded."""
        lo = max(start_n, self._n - self.capacity)
        if lo >= self._n:
            return np.zeros((0,), np.float64)
        idx = np.arange(lo, self._n) % self.capacity
        return self._ring[idx]

    def _emit_window(self, fence) -> None:
        if fence is not None:
            # the one telemetry device sync: block on the *latest* dispatch's
            # output so every step in the window has actually executed — the
            # window wall time is then honest, and the compiled program was
            # never touched
            import jax

            jax.block_until_ready(fence)
            self._last_t = time.perf_counter()
        times = self._slice(self._win_start_n)
        wall = self._last_t - (self._win_t0 or self._last_t)
        record = {
            "epoch": self._epoch,
            "step_start": self._win_start_step,
            "steps": int(self._n - self._win_start_n),
            **step_time_fields(times, self.flops_per_step, self.peak_flops),
            "samples_per_sec": round(self._win_samples / max(wall, 1e-9), 2),
            # pipeline occupancy (schema v3): how much of this window's wall
            # the dispatch loop spent blocked on host data, and how deep the
            # staged/in-flight queues ran — wall/device -> 1.0 is observable
            # per window, not just per run
            "host_stall_ms": round(self._win_stall * 1e3, 3),
            "staging_queue_depth": int(self._win_staging_max),
            "inflight_depth": int(self._win_inflight_max),
        }
        if self.writer is not None:
            self.writer.write(schema.stamp("step_stats", record))
        # the live plane reads exactly what the history flushed — a /metrics
        # scrape can never disagree with history.jsonl beyond one window
        self.last_window = record
        self.windows_emitted += 1
        self._win_start_n = self._n
        self._win_start_step = self.global_step
        self._win_samples = 0
        self._win_stall = 0.0
        self._win_staging_max = 0
        self._win_inflight_max = 0
        self._win_t0 = self._last_t
        if self.on_window is not None:
            self.on_window()

    def live_snapshot(self) -> dict:
        """Host-only live view for the exporter and the pod shard: cumulative
        counters plus the LAST emitted window's percentiles (when the window
        cadence is armed) or, without windows, percentiles over the newest
        ring entries at dispatch resolution. Never touches a device — no
        fence beyond the once-per-window one that already happened."""
        snap = {
            "epoch": self._epoch,
            "step": self.global_step,
            "samples_total": self.total_samples,
            "host_stall_ms_total": round(self.total_stall_s * 1e3, 3),
            "windows_emitted": self.windows_emitted,
        }
        if self.last_window is not None:
            for k in (
                "step_time_ms_p50", "step_time_ms_p95", "step_time_ms_p99",
                "step_time_ms_max", "samples_per_sec", "mfu_p50",
                "host_stall_ms",
            ):
                snap[k] = self.last_window.get(k)
            snap["window"] = {
                "epoch": self.last_window.get("epoch"),
                "step_start": self.last_window.get("step_start"),
                "steps": self.last_window.get("steps"),
            }
        else:
            # no window cadence: percentiles over the newest entries, at the
            # honest dispatch resolution (issue-time laps, not fenced)
            tail = self._slice(max(self._epoch_start_n, self._n - 256))
            snap.update(
                step_time_fields(tail, self.flops_per_step, self.peak_flops)
            )
            snap["samples_per_sec"] = None
            snap["window"] = None
        return snap

    def epoch_summary(self) -> dict:
        """Percentile fields for the finished epoch's history row, then reset
        the epoch slice.

        The wall basis is epoch start to the LAST train dispatch (not "now"):
        calling this after the eval pass must not fold eval time into the
        train-throughput figure. That basis is dispatch-resolution — exact
        under the per-window fences, convergent under device backpressure
        otherwise — matching the per-step ring entries it summarizes."""
        steps = self._n - self._epoch_start_n
        times = self._slice(self._epoch_start_n)
        end = self._last_t if self._last_t is not None else time.perf_counter()
        wall = end - (self._epoch_t0 if self._epoch_t0 is not None else end)
        fields = {
            "train_steps": int(steps),
            **step_time_fields(times, self.flops_per_step, self.peak_flops),
            "train_samples_per_sec": round(
                self._epoch_samples / max(wall, 1e-9), 2
            ),
            # whole-epoch host-stall total (the pipeline's residual host
            # bound; 0.0 when nothing stalled or no pipeline ran)
            "host_stall_ms": round(self._epoch_stall * 1e3, 3),
        }
        if steps > self.capacity:
            fields["step_stats_truncated"] = int(steps - self.capacity)
        return fields
