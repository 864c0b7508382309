"""Utility subsystems: debugging. (Observability graduated to
the ``tpuddp.observability`` package; the re-exports below keep old import
paths working.)"""

from tpuddp.utils.observability import (  # noqa: F401
    MetricsWriter,
    check_finite,
    maybe_start_profiler,
    stop_profiler,
)

__all__ = ["MetricsWriter", "check_finite", "maybe_start_profiler", "stop_profiler"]
