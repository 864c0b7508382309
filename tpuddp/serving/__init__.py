"""tpuddp.serving — continuous-batching multi-tenant inference engine.

The ROADMAP's "millions of users" north star needs an inference path, not
just epochs (open item 3). This package serves checkpoints produced by the
training stack on the same mesh the training stack runs on, treating the
local devices as a pool of independently schedulable model replicas (the
MPMD program-partitioning view of PAPERS.md arxiv 2412.14374) instead of one
lockstep program:

- :mod:`queue`     — thread-safe bounded request queue with per-tenant
  quotas, round-robin fairness, and reject-with-reason admission control;
- :mod:`scheduler` — coalesces variable-size requests into padded,
  power-of-two-bucketed device batches (the shared shape-key bucketing and
  staging-budget policy of ``tpuddp/utils/batching.py`` — the same machinery
  the scan-fused eval uses — so the compile cache stays warm and compile
  storms are impossible);
- :mod:`replica`   — N independent model replicas across the local devices,
  loaded from a training checkpoint via the existing sha256-verified
  ``restore_latest`` path;
- :mod:`stats`     — SLO metrics (queue/device/end-to-end latency
  percentiles, throughput, batch occupancy, rejects) emitted as typed
  ``serving_stats``/``event`` rows through ``tpuddp/observability``;
- :mod:`engine`    — :class:`ServingEngine`, tying the above together with
  one dispatch loop per replica and a drain path reusing the resilience
  exit-code contract (SIGTERM -> finish in-flight work -> exit 75).

Token traffic has its own sub-package: :mod:`tpuddp.serving.decode` is the
autoregressive engine — paged KV-cache pool, continuous batching at TOKEN
granularity (sequences join/leave the running batch every decode step),
prefill/decode split scheduling, and per-token streaming — over the
transformer family of ``tpuddp/models/transformer.py``.

``python -m tpuddp.serving --settings <yaml>`` stands the engine up from a
settings file's ``serving`` block (``--decode`` for the token engine from
its ``serving.decode`` block); ``tools/loadgen.py`` drives it with
closed/open-loop load and writes latency-vs-throughput curves in the bench
artifact format (``--decode`` for tokens/sec + TTFT curves).
"""

from tpuddp.serving.decode import (  # noqa: F401
    DecodeEngine,
    DecodeRequest,
    DecodeStats,
    PagedKVCache,
    StreamedResult,
)
from tpuddp.serving.engine import ServingEngine  # noqa: F401
from tpuddp.serving.queue import (  # noqa: F401
    AdmissionError,
    Request,
    RequestQueue,
    ServedResult,
)
from tpuddp.serving.replica import Replica, ReplicaPool  # noqa: F401
from tpuddp.serving.scheduler import Batch, BatchScheduler  # noqa: F401
from tpuddp.serving.stats import ServingStats  # noqa: F401
from tpuddp.serving.survive import (  # noqa: F401
    NoHealthyReplicaError,
    RetryBudget,
    SurvivePolicy,
)

__all__ = [
    "AdmissionError",
    "NoHealthyReplicaError",
    "RetryBudget",
    "SurvivePolicy",
    "Batch",
    "BatchScheduler",
    "DecodeEngine",
    "DecodeRequest",
    "DecodeStats",
    "PagedKVCache",
    "StreamedResult",
    "Replica",
    "ReplicaPool",
    "Request",
    "RequestQueue",
    "ServedResult",
    "ServingEngine",
    "ServingStats",
]
