"""Cross-host telemetry aggregation + straggler detection.

A multi-host pod has per-host step timing (each process's
``StepStatsRecorder``) but no pod-level view: the watchdog's heartbeat files
answer only alive/dead. This module rides the SAME channel — each process's
``hb_<pid>`` file (tpuddp/resilience/watchdog.py) gains a one-line JSON
*telemetry shard* under its timestamp: the host's last-window step-time p50,
host-stall total, skipped-update count. One shared-filesystem file per host,
rewritten atomically at the per-window cadence the recorder already fences —
**zero new device fences, zero new collectives** (the DCN never carries a
telemetry message; the checkpoint dir's shared FS does).

The main process runs a :class:`PodAggregator`: every window it merges the
shards into pod-level percentiles, feeds the exporter's per-host series, and
detects stragglers — a host whose window p50 exceeds ``straggler_ratio`` x
the pod median for ``straggler_windows`` CONSECUTIVE fresh windows lands
exactly one typed ``straggler`` event row (host id, ratio, window streak) in
``history.jsonl``, and is reported again only after recovering first.

Shard reads are tolerant by contract: a peer mid-rewrite can present a torn
JSON line; the reader skips it with a warning and uses the previous view —
it never crashes the aggregator or fails the run (satellite of ISSUE 10).
"""

from __future__ import annotations

import logging
import time
from typing import Callable, Dict, Optional

from tpuddp.observability import schema

logger = logging.getLogger("tpuddp")

# the shard fields a publisher fills from StepStatsRecorder.live_snapshot();
# everything optional but the window index (freshness cursor)
SHARD_FIELDS = (
    "window_index",
    "epoch",
    "step",
    "step_time_ms_p50",
    "host_stall_ms",
    "skipped_steps",
    "samples_per_sec",
)


def make_shard(
    live: dict, skipped_steps: int = 0, window_index: Optional[int] = None
) -> dict:
    """Build one host's telemetry shard from a recorder live snapshot.

    ``clock`` is the host's wall<->monotonic anchor (unix µs + the
    ``perf_counter_ns`` taken beside it) — the cross-host skew signal the
    tracing plane's merge workflow uses: per-host ``trace_<role>.json``
    artifacts timestamp spans through their OWN anchor, and differencing
    two hosts' shard anchors bounds the wall-clock skew between their
    timelines."""
    return {
        "window_index": (
            int(window_index)
            if window_index is not None
            else int(live.get("windows_emitted") or 0)
        ),
        "epoch": live.get("epoch"),
        "step": live.get("step"),
        "step_time_ms_p50": live.get("step_time_ms_p50"),
        "host_stall_ms": live.get("host_stall_ms_total"),
        "skipped_steps": int(skipped_steps or 0),
        "samples_per_sec": live.get("samples_per_sec"),
        "t": time.time(),
        "clock": {
            "unix_us": int(time.time() * 1e6),
            "perf_ns": time.perf_counter_ns(),
        },
    }


def publish_shard(directory: str, process_id: int, shard: dict) -> None:
    """Write this host's shard through the heartbeat channel (atomic
    tmp+replace — a reader sees the old whole file or the new whole file,
    and the heartbeat timestamp rides along so publishing IS beating)."""
    # lazy: resilience.watchdog reaches back into observability for its
    # event writer — a module-level import here would be circular
    from tpuddp.resilience import watchdog as wd

    try:
        wd.write_heartbeat(directory, process_id, payload=shard)
    except OSError as e:  # shared-FS hiccup: telemetry is best-effort
        logger.warning("telemetry shard publish failed: %s", e)


def read_shard(directory: str, process_id: int) -> Optional[dict]:
    """This peer's shard, or None (no file, no payload yet, or a torn JSON
    line mid-rewrite — skipped with a warning, never an exception)."""
    from tpuddp.resilience import watchdog as wd

    return wd.read_heartbeat_payload(directory, process_id)


class PodAggregator:
    """Main-process merge of per-host telemetry shards.

    ``update()`` is called at the window cadence (the recorder's
    ``on_window`` hook) and at epoch boundaries; it is pure host-side file
    reads + arithmetic. ``writer`` is the run's MetricsWriter (straggler
    events become typed history rows); None keeps detection in-memory only
    (tests, exporters without a history)."""

    def __init__(
        self,
        directory: str,
        num_processes: int,
        writer=None,
        straggler_ratio: float = 1.5,
        straggler_windows: int = 3,
        shard_reader: Optional[Callable[[int], Optional[dict]]] = None,
    ):
        if straggler_ratio <= 1.0:
            raise ValueError(
                f"straggler_ratio must be > 1.0, got {straggler_ratio} "
                "(a host at the pod median would be a 'straggler')"
            )
        if straggler_windows < 1:
            raise ValueError(
                f"straggler_windows must be >= 1, got {straggler_windows}"
            )
        self.directory = directory
        self.num_processes = int(num_processes)
        self.writer = writer
        self.straggler_ratio = float(straggler_ratio)
        self.straggler_windows = int(straggler_windows)
        self._read = shard_reader or (
            lambda pid: read_shard(self.directory, pid)
        )
        self._last_window: Dict[int, int] = {}  # host -> freshest window seen
        self._streak: Dict[int, int] = {}  # host -> consecutive slow windows
        self._fired: set = set()  # hosts in an already-reported episode
        self.straggler_events = 0
        self.last: Optional[dict] = None

    # ------------------------------------------------------------- merge --
    def collect(self) -> Dict[int, dict]:
        shards = {}
        for pid in range(self.num_processes):
            shard = self._read(pid)
            if shard is not None:
                shards[pid] = shard
        return shards

    def update(self) -> Optional[dict]:
        """Merge the current shards; detect + record stragglers. Returns the
        merged pod view (also kept on ``self.last``), or None when no shard
        is readable yet."""
        import numpy as np

        shards = self.collect()
        p50s = {
            pid: s["step_time_ms_p50"]
            for pid, s in shards.items()
            if isinstance(s.get("step_time_ms_p50"), (int, float))
        }
        if not p50s:
            return None
        values = np.asarray(list(p50s.values()), np.float64)
        pod_median = float(np.median(values))
        merged = {
            "hosts_reporting": len(p50s),
            "pod_step_time_ms_p50": round(pod_median, 4),
            "pod_step_time_ms_max": round(float(values.max()), 4),
            "pod_step_time_ms_p95": round(float(np.percentile(values, 95)), 4),
            "pod_host_stall_ms": round(sum(
                float(s.get("host_stall_ms") or 0.0) for s in shards.values()
            ), 3),
            "pod_skipped_steps": sum(
                int(s.get("skipped_steps") or 0) for s in shards.values()
            ),
            "hosts": {
                str(pid): {
                    k: shards[pid].get(k)
                    for k in ("window_index", "epoch", "step",
                              "step_time_ms_p50", "host_stall_ms",
                              "skipped_steps", "clock")
                }
                for pid in sorted(shards)
            },
            "stragglers": [],
        }
        for pid, p50 in sorted(p50s.items()):
            win = int(shards[pid].get("window_index") or 0)
            # "fresh" = the shard's window cursor MOVED (any direction: a
            # resumed run restarts its window count below a leftover shard's
            # — a monotonic test would freeze that host's streak forever)
            fresh = win != self._last_window.get(pid)
            self._last_window[pid] = win
            ratio = (p50 / pod_median) if pod_median > 0 else 1.0
            if ratio > self.straggler_ratio:
                if fresh:
                    # only a NEW window extends the streak: a stalled shard
                    # must not convict a host on one repeated measurement
                    self._streak[pid] = self._streak.get(pid, 0) + 1
            else:
                self._streak[pid] = 0
                self._fired.discard(pid)  # recovered: a relapse re-reports
            streak = self._streak.get(pid, 0)
            if streak >= self.straggler_windows:
                merged["stragglers"].append(pid)
                if pid not in self._fired:
                    self._fired.add(pid)
                    self.straggler_events += 1
                    event = {
                        "event": "straggler",
                        "host": pid,
                        "ratio": round(ratio, 3),
                        "windows": streak,
                        "window_p50_ms": round(float(p50), 4),
                        "pod_p50_ms": round(pod_median, 4),
                        "epoch": shards[pid].get("epoch"),
                        "step": shards[pid].get("step"),
                    }
                    logger.warning(
                        "straggler: host %d window p50 %.2f ms is %.2fx the "
                        "pod median %.2f ms for %d consecutive window(s)",
                        pid, p50, ratio, pod_median, streak,
                    )
                    if self.writer is not None:
                        self.writer.write(schema.stamp("event", event))
        self.last = merged
        return merged

    # ---------------------------------------------------------- exporter --
    def export_source(self) -> Callable[[], dict]:
        """Exporter source: pod-level gauges + per-host labeled series from
        the last merge (scrapes never re-read the shard files — update()
        owns the cadence)."""
        from tpuddp.observability import exporter as exp

        def source():
            merged = self.last
            if merged is None:
                return {}
            series = {
                "pod_hosts_reporting": exp.gauge(
                    merged["hosts_reporting"], "hosts with a readable shard"
                ),
                "pod_step_time_ms": exp.summary(
                    {
                        "0.5": merged["pod_step_time_ms_p50"],
                        "0.95": merged["pod_step_time_ms_p95"],
                        "1.0": merged["pod_step_time_ms_max"],
                    },
                    "pod-level percentiles over per-host window p50s",
                ),
                "pod_stragglers": exp.gauge(
                    len(merged["stragglers"]),
                    "hosts currently past the straggler threshold",
                ),
                "pod_straggler_events_total": exp.counter(
                    self.straggler_events, "straggler episodes reported"
                ),
            }
            host_series = {"type": "gauge", "help": (
                "per-host last-window step-time p50"
            ), "values": []}
            for pid, h in merged["hosts"].items():
                host_series["values"].append(
                    ({"host": pid}, h.get("step_time_ms_p50"))
                )
            series["host_step_time_ms_p50"] = host_series
            return series

        return source
