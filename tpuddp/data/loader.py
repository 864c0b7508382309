"""Host-side batch loaders.

Two loaders mirror the two dataloading shapes in the reference:

- :class:`DataLoader` — a plain single-stream loader (the accelerate
  entrypoint's unsharded loaders, multi-GPU-training-accelerate.py:22-36, and
  its deliberately-unprepared test loader, :129-131 / quirk Q3);
- :class:`ShardedDataLoader` — the DP loader. The reference gives each of N
  single-GPU processes its own ``DataLoader(sampler=DistributedSampler(...))``
  (multi-GPU-training-torch.py:72-101). On TPU one process drives many chips,
  so this loader runs one :class:`DistributedSampler` per *local replica* and
  assembles their microbatches, in mesh order, into the process-local slice of
  the global batch; ``tpuddp.parallel.mesh.shard_batch`` then places it on the
  mesh (multi-host: every process loads ONLY its shard — the global
  permutation stays consistent because every sampler keys off the same
  seed+epoch).

TPU-first batching: every batch has a static shape. Final partial batches are
padded and carry a 0/1 weight vector ``w`` (consumed by the masked loss /
metric math) instead of producing a ragged last batch that would retrigger XLA
compilation.
"""

from __future__ import annotations

import math
from typing import Iterator, Optional, Sequence, Tuple

import queue
import threading

import jax
import numpy as np

from tpuddp.observability import trace as trace_lib
from tpuddp.parallel.sampler import DistributedSampler
from tpuddp.utils import batching

try:
    from tpuddp.data import _native
except ImportError:  # missing native package: numpy path only
    class _native:  # type: ignore[no-redef]
        @staticmethod
        def gather_rows(src, indices, pad_rows=0):
            return None


def _fetch(dataset, indices: np.ndarray):
    """Vectorized batch fetch when the dataset supports it."""
    if hasattr(dataset, "get_batch"):
        return dataset.get_batch(indices)
    xs, ys = zip(*(dataset[int(i)] for i in indices))
    return np.stack(xs), np.asarray(ys)


def _pad_batch(x: np.ndarray, y: np.ndarray, batch_size: int):
    """Pad to the static batch size; w marks real samples. The one padding
    implementation is shared with eval fusion and serving
    (tpuddp/utils/batching.py)."""
    return batching.pad_batch(x, y, batch_size)


def _gather(dataset, indices: np.ndarray, batch_size: int):
    """The rows of one microbatch: ``(x, y)``. Datasets exposing contiguous
    ``.images`` / ``.labels`` arrays (CIFAR10, SyntheticClassification) take
    the native C++ multi-threaded row-gather fast path (tpuddp/data/_native),
    which pads as it gathers and leaves the labels to :func:`_pad`
    (``y is None``); everything else falls back to numpy with identical
    results."""
    images = getattr(dataset, "images", None)
    if images is not None and getattr(dataset, "labels", None) is not None:
        x = _native.gather_rows(images, indices, pad_rows=batch_size)
        if x is not None:
            return x, None
    return _fetch(dataset, indices)


def _pad(dataset, indices: np.ndarray, batch_size: int, x, y):
    """``(x, y, w)`` at the static batch size from what :func:`_gather`
    returned: labels and weights beside natively gathered rows, else
    :func:`_pad_batch`."""
    if y is not None:
        return _pad_batch(x, y, batch_size)
    n = len(indices)
    labels = dataset.labels
    w = np.ones(batch_size, np.float32)
    w[n:] = 0.0
    y = np.zeros((batch_size, *labels.shape[1:]), labels.dtype)  # a token set's labels are rows
    y[:n] = labels[np.asarray(indices)]
    return x, y, w


def _fetch_padded(dataset, indices: np.ndarray, batch_size: int):
    """Fetch + pad in one step."""
    return _pad(dataset, indices, batch_size, *_gather(dataset, indices, batch_size))


# (tracer, parent span, the thread that handed them over)
_UNTRACED = (trace_lib.NULL, None, None)


class _Traced:
    """What a loader needs to open its own spans (``loader_order``,
    ``loader_gather``, ``loader_pad``, kind ``load``) where its work happens:
    :meth:`set_tracer` for the length of a pass (``pipeline.run_pass`` hands
    over its tracer and epoch span and takes them back in a ``finally``), and
    the pair a batch plan brackets its calls with. A plan keeps the tracer it
    was made under, so an abandoned pass's workers end their spans where they
    opened them. Without a tracer the two calls are the NULL tracer's no-ops.
    A span opened on another thread than the one that handed the tracer over
    (a ``PrefetchLoader`` worker) lands on a timeline row of that thread's
    name, not its parent's."""

    _trace = _UNTRACED

    def set_tracer(self, tracer, parent=None) -> None:
        self._trace = (
            _UNTRACED if tracer is None
            else (tracer, parent, threading.get_ident())
        )


def _open(trace, name: str):
    tracer, parent, owner = trace
    tid = None
    if owner is not None and threading.get_ident() != owner:
        tid = threading.current_thread().name
    return tracer.start_span(name, trace_lib.KIND_LOAD, parent=parent, tid=tid)


def _per_sample_nbytes(dataset):
    """Input bytes of one sample (x only), when the dataset exposes a
    contiguous ``.images`` array (the protocol _fetch_padded relies on);
    None otherwise."""
    images = getattr(dataset, "images", None)
    if images is None or not hasattr(images, "itemsize"):
        return None
    return int(np.prod(images.shape[1:])) * images.itemsize


class DataLoader(_Traced):
    """Single-stream host loader yielding ``(x, y, w)`` numpy batches.

    ``sampler``: optional index source with the DistributedSampler protocol
    (iter + set_epoch). Without one, iterates sequentially or shuffled
    (``shuffle=True``, reshuffled per epoch via ``set_epoch`` like the
    sampler-based path).
    """

    def __init__(
        self,
        dataset,
        batch_size: int,
        sampler: Optional[DistributedSampler] = None,
        shuffle: bool = False,
        seed: int = 0,
        drop_last: bool = False,
    ):
        self.dataset = dataset
        self.batch_size = batch_size
        self.sampler = sampler
        self.shuffle = shuffle
        self.seed = seed
        self.drop_last = drop_last
        self.epoch = 0

    @property
    def batch_nbytes(self):
        """Input bytes of one host batch (x only) — the epoch driver caps the
        auto scan depth by a staging-memory budget with this (loop.py)."""
        per_sample = _per_sample_nbytes(self.dataset)
        return None if per_sample is None else self.batch_size * per_sample

    def set_epoch(self, epoch: int) -> None:
        self.epoch = epoch
        if self.sampler is not None:
            self.sampler.set_epoch(epoch)

    def _indices(self) -> np.ndarray:
        if self.sampler is not None:
            return np.fromiter(iter(self.sampler), dtype=np.int64)
        n = len(self.dataset)
        if self.shuffle:
            rng = np.random.Generator(np.random.PCG64(self.seed + self.epoch))
            return rng.permutation(n)
        return np.arange(n)

    def __len__(self) -> int:
        n = len(self.sampler) if self.sampler is not None else len(self.dataset)
        if self.drop_last:
            return n // self.batch_size
        return math.ceil(n / self.batch_size)

    def make_batch_plan(self):
        """Freeze this epoch's order and return ``(n_batches, fetch)`` where
        ``fetch(s)`` assembles batch ``s`` independently of any other batch —
        the random-access protocol PrefetchLoader's worker pool parallelizes
        over. One plan per epoch; ``__iter__`` is defined in terms of it so
        the two can never drift."""
        trace = self._trace
        tracer = trace[0]
        span = _open(trace, "loader_order")
        indices = self._indices()
        tracer.end_span(span)
        steps = len(self)
        batch_size = self.batch_size
        dataset = self.dataset

        def fetch(s: int):
            chunk = indices[s * batch_size : (s + 1) * batch_size]
            span = _open(trace, "loader_gather")
            x, y = _gather(dataset, chunk, batch_size)
            tracer.end_span(span)
            span = _open(trace, "loader_pad")
            batch = _pad(dataset, chunk, batch_size, x, y)
            tracer.end_span(span)
            return batch

        return steps, fetch

    def __iter__(self) -> Iterator[Tuple[np.ndarray, np.ndarray, np.ndarray]]:
        steps, fetch = self.make_batch_plan()
        for s in range(steps):
            yield fetch(s)


class _EpochMemoizedOrder:
    """Materializes a user sampler's order ONCE per epoch and serves the same
    array to every replica's :class:`DistributedSampler`. Required for
    correctness, not just speed: a non-deterministic sampler (e.g. a weighted
    random sampler that doesn't key off the epoch) iterated independently per
    replica — or drawn independently per PROCESS in a multi-host world —
    would give replicas DIFFERENT base orders and silently break shard
    disjointness. Locally the cache guarantees one materialization; across
    processes, process 0's order is broadcast so every host shards the same
    order. The cache invalidates on ``set_epoch`` (the per-epoch contract
    every tpuddp epoch driver honors)."""

    def __init__(self, sampler):
        self.sampler = sampler
        self._cache: Optional[np.ndarray] = None

    def set_epoch(self, epoch: int) -> None:
        set_ep = getattr(self.sampler, "set_epoch", None)
        if set_ep is not None:
            set_ep(epoch)
        self._cache = None

    def __len__(self) -> int:
        return len(self.sampler)

    def _materialize(self) -> np.ndarray:
        if self._cache is None:
            arr = np.fromiter(iter(self.sampler), dtype=np.int64)
            if jax.process_count() > 1:
                from tpuddp.parallel import collectives as col

                arr = np.asarray(col.broadcast_one_to_all(arr), dtype=np.int64)
            self._cache = arr
        return self._cache

    def __array__(self, dtype=None):
        # DistributedSampler._global_indices takes this fast path: the cached
        # ndarray is handed over directly instead of being re-iterated
        # element-by-element once per local replica
        arr = self._materialize()
        return arr.astype(dtype) if dtype is not None else arr

    def __iter__(self):
        return iter(self._materialize())


class ShardedDataLoader(_Traced):
    """Global-batch DP loader: one instance per process, one sampler per local
    replica. Yields the process-local ``(x, y, w)`` slice of the global batch
    (concat over local replicas in mesh order); pair with
    ``DistributedDataParallel.shard`` / ``mesh.shard_batch`` for placement.
    """

    def __init__(
        self,
        dataset,
        batch_size: int,
        mesh,
        shuffle: bool = True,
        seed: int = 0,
        drop_last: bool = False,
        sampler=None,
    ):
        self.dataset = dataset
        self.batch_size = batch_size  # per replica
        self.mesh = mesh
        self.drop_last = drop_last

        flat_devices = list(mesh.devices.flat)
        axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        model = int(axis_sizes.get("model", 1))
        if model > 1:
            # 2-D ("data", "model") mesh: the DATA-parallel replica set is
            # the data axis only — every device of one model group consumes
            # the SAME rows (the batch lays out P("data"), replicated over
            # "model"), so one sampler per data index, never per device.
            if jax.process_count() > 1:
                raise ValueError(
                    "ShardedDataLoader on a model-parallel mesh is "
                    "single-controller only (parallel.model > 1 is refused "
                    "multi-process at the DDP wrap too)"
                )
            self.world_size = len(flat_devices) // model
            self.local_ranks = list(range(self.world_size))
        else:
            self.world_size = len(flat_devices)
            proc = jax.process_index()
            # global ranks of this process's replicas, in mesh traversal
            # order — must match how NamedSharding lays the global batch
            # across devices.
            self.local_ranks = [
                rank for rank, d in enumerate(flat_devices)
                if d.process_index == proc
            ]
        # base_sampler: a user-supplied full-dataset order source (iter + len
        # + optional set_epoch). Its order is PRESERVED and sharded around:
        # it feeds the per-replica DistributedSamplers as their order_source,
        # so the pad-by-wrap/stride discipline stays the ONE authoritative
        # implementation (parallel/sampler.py) — HF prepare() semantics: a
        # custom sampler rides inside the sharded sampler, it is not replaced.
        self.base_sampler = sampler
        self._order = _EpochMemoizedOrder(sampler) if sampler is not None else None
        self.samplers = [
            DistributedSampler(
                len(dataset),
                num_replicas=self.world_size,
                rank=rank,
                shuffle=shuffle,
                seed=seed,
                order_source=self._order,
            )
            for rank in self.local_ranks
        ]

    def set_epoch(self, epoch: int) -> None:
        """Fan set_epoch to every local replica's sampler (reference
        multi-GPU-training-torch.py:175-178) — and to the user sampler, via
        the epoch memo, when one was supplied."""
        if self._order is not None:
            self._order.set_epoch(epoch)
        for s in self.samplers:
            s.set_epoch(epoch)

    @property
    def batch_nbytes(self):
        """Input bytes of one process-local host batch (x only, all local
        replicas) — the epoch driver caps the auto scan depth by a
        staging-memory budget with this (loop.py)."""
        per_sample = _per_sample_nbytes(self.dataset)
        if per_sample is None:
            return None
        return self.batch_size * len(self.local_ranks) * per_sample

    @property
    def num_samples_per_replica(self) -> int:
        return self.samplers[0].num_samples

    def __len__(self) -> int:
        n = self.num_samples_per_replica
        if self.drop_last:
            return n // self.batch_size
        return math.ceil(n / self.batch_size)

    def make_batch_plan(self):
        """Freeze this epoch's per-replica orders and return
        ``(n_batches, fetch)`` — the random-access protocol PrefetchLoader's
        worker pool parallelizes over (see :meth:`DataLoader.make_batch_plan`).
        """
        trace = self._trace
        tracer = trace[0]
        span = _open(trace, "loader_order")
        per_replica = [s.local_indices() for s in self.samplers]
        tracer.end_span(span)
        steps = len(self)
        batch_size = self.batch_size
        dataset = self.dataset

        def fetch(s: int):
            chunks = [shard[s * batch_size : (s + 1) * batch_size] for shard in per_replica]
            span = _open(trace, "loader_gather")
            rows = [_gather(dataset, chunk, batch_size) for chunk in chunks]
            tracer.end_span(span)
            span = _open(trace, "loader_pad")
            xs, ys, ws = zip(*(
                _pad(dataset, chunk, batch_size, x, y)
                for chunk, (x, y) in zip(chunks, rows)
            ))
            batch = np.concatenate(xs), np.concatenate(ys), np.concatenate(ws)
            tracer.end_span(span)
            return batch

        return steps, fetch

    def __iter__(self) -> Iterator[Tuple[np.ndarray, np.ndarray, np.ndarray]]:
        steps, fetch = self.make_batch_plan()
        for s in range(steps):
            yield fetch(s)

    def probe_fingerprint(self, x_local: np.ndarray) -> str:
        """Shard-disjointness probe string: a few raw input values per local
        replica (the reference's manual multi-GPU-training-torch.py:112-115
        probe, adapted to NHWC and any input size)."""
        parts = []
        for i, rank in enumerate(self.local_ranks):
            sample = x_local[i * self.batch_size]
            flat = np.asarray(sample).reshape(-1)
            mid = flat.size // 2
            parts.append(f"replica {rank}: {np.array2string(flat[mid : mid + 4], precision=4)}")
        return "; ".join(parts)


class PrefetchLoader:
    """Background-worker prefetch over any loader (the tpuddp analog of the
    reference's ``num_workers=2`` DataLoader workers,
    multi-GPU-training-torch.py:90-98): batch assembly (sampler slicing,
    native gather, padding) overlaps with device compute through a bounded
    queue. Semantics are unchanged — same batches, same order.

    Tracing: ``set_tracer`` reaches the inner loader (unknown attributes
    delegate), whose batch plan opens the assembly spans on the thread that
    runs it: here ``loader_gather`` and ``loader_pad`` come from the producer
    side (``produce``, or a ``work`` of the pool), never from the consumer,
    and ``loader_order`` from where the plan is made (the producer thread; the
    consumer's first ``next`` with a pool). A consumer that waits while no
    such span is open waited on an empty queue or on the hand-over, not on
    assembly.

    ``workers > 1`` parallelizes batch *assembly* across a thread pool when
    the inner loader exposes the random-access ``make_batch_plan`` protocol
    (both tpuddp loaders do); batches are re-emitted strictly in order, so
    the stream is bitwise-identical to the serial one. Loaders without the
    protocol fall back to one producer thread.

    Hardening contract (the async-pipeline satellite):

    - a worker exception propagates to the consumer with its ORIGINAL
      traceback attached (the producer frame is visible in the report);
    - every worker is reaped when iteration ends — normally, by an
      exception, or by the consumer abandoning the iterator mid-epoch (a
      preemption drain): the bounded queue can never wedge a producer and
      leak its thread;
    - the queue depth is byte-capped against the shared staging budget
      (``tpuddp/utils/batching.py``) via the loader's ``batch_nbytes``, so
      prefetch depth x batch bytes stays bounded host memory.
    """

    _SENTINEL = object()

    def __init__(self, loader, depth: int = 2, workers: int = 1):
        self.loader = loader
        self.depth = max(1, int(depth))
        self.workers = max(1, int(workers))

    # -- delegation so the epoch driver can't tell the difference --
    def set_epoch(self, epoch: int) -> None:
        self.loader.set_epoch(epoch)

    def __len__(self) -> int:
        return len(self.loader)

    def probe_fingerprint(self, x_local):
        probe = getattr(self.loader, "probe_fingerprint", None)
        return probe(x_local) if probe is not None else ""

    def __getattr__(self, name):
        return getattr(self.loader, name)

    def effective_depth(self) -> int:
        """The byte-capped queue depth: ``depth``, bounded by the staging
        budget over one batch's bytes when they are knowable (the shared
        depth policy, ``tpuddp/utils/batching.py::resolve_fuse``)."""
        return batching.resolve_fuse(
            getattr(self.loader, "batch_nbytes", None), cap=self.depth
        )

    def __iter__(self):
        depth = self.effective_depth()
        if self.workers > 1 and hasattr(self.loader, "make_batch_plan"):
            return self._iter_pool(depth)
        return self._iter_serial(depth)

    def _iter_serial(self, depth: int):
        """One producer thread driving the inner loader's own iterator."""
        q: "queue.Queue" = queue.Queue(maxsize=depth)
        stop = threading.Event()
        err = []

        def _put(item) -> bool:
            # bounded put that can always be cancelled: a consumer that
            # abandoned the iterator must be able to reap this thread even
            # with the queue full
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.05)
                    return True
                except queue.Full:
                    continue
            return False

        def produce():
            try:
                for batch in self.loader:
                    if not _put(batch):
                        return
            except BaseException as e:  # propagate into the consumer
                err.append(e)
            finally:
                _put(self._SENTINEL)

        thread = threading.Thread(
            target=produce, daemon=True, name="tpuddp-prefetch"
        )
        thread.start()
        try:
            while True:
                item = q.get()
                if item is self._SENTINEL:
                    break
                yield item
            if err:
                # the exception object still carries the producer-side
                # traceback; re-raising it surfaces the original frames
                raise err[0]
        finally:
            stop.set()
            try:  # unblock a producer stuck on a full queue
                while True:
                    q.get_nowait()
            except queue.Empty:
                pass
            thread.join(timeout=5)

    def _iter_pool(self, depth: int):
        """Worker pool over the inner loader's random-access batch plan;
        batches re-emit strictly in order."""
        steps, fetch = self.loader.make_batch_plan()
        lock = threading.Condition()
        results = {}  # batch index -> assembled batch (bounded by depth)
        cursor = {"claim": 0, "emit": 0}
        stop = threading.Event()
        err = []

        def work():
            while not stop.is_set():
                with lock:
                    # claim the next batch index, but never run more than
                    # `depth` batches ahead of the consumer (bounded memory)
                    while (
                        not stop.is_set()
                        and cursor["claim"] < steps
                        and cursor["claim"] - cursor["emit"] >= depth
                    ):
                        lock.wait(0.05)
                    if stop.is_set() or cursor["claim"] >= steps:
                        return
                    s = cursor["claim"]
                    cursor["claim"] += 1
                try:
                    batch = fetch(s)
                except BaseException as e:
                    with lock:
                        err.append(e)
                        stop.set()
                        lock.notify_all()
                    return
                with lock:
                    results[s] = batch
                    lock.notify_all()

        threads = [
            threading.Thread(
                target=work, daemon=True, name=f"tpuddp-prefetch-{i}"
            )
            for i in range(min(self.workers, max(1, steps)))
        ]
        for t in threads:
            t.start()
        try:
            for s in range(steps):
                with lock:
                    while s not in results and not err:
                        lock.wait(0.05)
                        if err:
                            break
                    if err:
                        raise err[0]
                    batch = results.pop(s)
                    cursor["emit"] = s + 1
                    lock.notify_all()
                yield batch
        finally:
            stop.set()
            with lock:
                lock.notify_all()
            for t in threads:
                t.join(timeout=5)
