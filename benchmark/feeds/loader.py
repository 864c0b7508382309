"""Loader feed: the job as ``train_native.py`` runs it, pass after pass. A
seeded in-memory uint8 set (made on the device, fetched to the host once),
the real ``ShardedDataLoader`` wrapped in ``PrefetchLoader``, and
``pipeline.run_pass`` with the shipped ``PipelineConfig`` and the K that
``resolve_scan_steps`` picks; each pass re-shuffled by its epoch number and
ended, as an epoch is, by fetching its accumulated metrics. The clock stops
the pass in progress through ``run_pass``'s own ``poll``: what it had
dispatched completes and counts, what it had only staged is dropped.

Traffic parameters: ``batch_per_chip``, ``dataset_samples``, ``scan_steps``
(``"auto"`` or a number), ``pipeline`` (the ``training.pipeline`` block;
null for the shipped defaults).
"""

from __future__ import annotations

import time

import jax
import numpy as np

from benchmark import cells
from benchmark.spans import RunnerTracer
from tpuddp.data import PrefetchLoader, ShardedDataLoader
from tpuddp.data.synthetic import SyntheticClassification
from tpuddp.training import pipeline as pipeline_lib
from tpuddp.training.loop import _param_bytes, resolve_scan_steps

_GENERATE_BY = 1024


class _Telemetry:
    """``run_pass``'s ``tel=`` interface: the runner's own count of steps,
    samples, the time it waited for the loader, and its queue depths."""

    def __init__(self):
        self.steps = 0
        self.dispatches = 0
        self.host_stall_s = 0.0
        self.staging_depth_max = 0
        self.inflight_depth_max = 0

    def offer_batch(self, batch):
        pass

    def pre_dispatch(self, n_steps):
        pass

    def post_dispatch(self, n_steps, n_samples, metrics=None, fence=None,
                      host_stall_s=0.0, staging_depth=0, inflight_depth=0, **_):
        self.steps += n_steps
        self.dispatches += 1
        self.host_stall_s += host_stall_s
        self.staging_depth_max = max(self.staging_depth_max, staging_depth)
        self.inflight_depth_max = max(self.inflight_depth_max, inflight_depth)


class _Annotated:
    """The loader with a ``loader_next`` span round every batch it hands
    over; everything else is the loader's own."""

    def __init__(self, loader, spans):
        self._loader, self._spans = loader, spans

    def __getattr__(self, name):
        return getattr(self._loader, name)

    def __len__(self):
        return len(self._loader)

    def __iter__(self):
        it = iter(self._loader)
        while True:
            with self._spans.span("loader_next"):
                try:
                    batch = next(it)
                except StopIteration:
                    return
            yield batch


class Feed:
    def __init__(self, cell, ddp, seed: int, spans):
        self.cell, self.ddp, self.seed, self.spans = cell, ddp, seed, spans
        self.batch_per_chip = int(cell.traffic["batch_per_chip"])
        self.n_samples = int(cell.traffic["dataset_samples"])
        self.pipeline = pipeline_lib.resolve_pipeline(cell.traffic.get("pipeline"))
        self.epoch = 0
        self.k = None

    def setup(self) -> None:
        n_gen = -(-self.n_samples // _GENERATE_BY)
        images, labels = jax.device_get(cells.load_system(self.cell).make_batches(
            self.cell.config, self.seed, n_gen, _GENERATE_BY
        ))
        images = images.reshape(-1, *images.shape[2:])[: self.n_samples]
        labels = labels.reshape(-1)[: self.n_samples]
        self.dataset = SyntheticClassification.from_arrays(
            np.ascontiguousarray(images), np.ascontiguousarray(labels)
        )
        loader = ShardedDataLoader(
            self.dataset, self.batch_per_chip, self.ddp.mesh, shuffle=True, seed=self.seed
        )
        if self.pipeline.host_workers > 0:
            loader = PrefetchLoader(loader, workers=self.pipeline.host_workers)
        self.loader = _Annotated(loader, self.spans)

    def sample_batches(self, n: int, batch: int):
        return [
            (self.dataset.images[i * batch:(i + 1) * batch],
             self.dataset.labels[i * batch:(i + 1) * batch])
            for i in range(n)
        ]

    def _pass(self, state, poll, tel):
        with self.spans.span("between_passes"):
            self.loader.set_epoch(self.epoch)
            self.epoch += 1
        state, acc, _ = pipeline_lib.run_pass(
            self.ddp, state, self.loader, self.k, self.ddp.train_step,
            self.ddp.train_step_many, cfg=self.pipeline, poll=poll, tel=tel,
            tracer=RunnerTracer(self.spans),
        )
        if acc is None:
            return state, None
        with self.spans.span("readback"):
            m = jax.device_get(acc)
        return state, (float(np.sum(m["loss_sum"])), float(np.sum(m["n"])))

    def warm(self, state):
        """One whole pass: every program a pass dispatches (the fused chunk,
        the single-step remainder where there is one, the metric adds of the
        readback drain) and the loader's native gather, used once."""
        self.k = resolve_scan_steps(
            self.cell.traffic["scan_steps"], len(self.loader),
            _param_bytes(state.params), self.loader.batch_nbytes,
        )
        state, _ = self._pass(state, lambda: False, _Telemetry())
        return state

    def measure(self, state, seconds: float):
        tel = _Telemetry()
        readbacks, steps_at = [], []
        t_open = t_close = time.perf_counter()
        expired = lambda: time.perf_counter() - t_open >= seconds
        while not expired():
            before = tel.steps
            state, read = self._pass(state, expired, tel)
            if read is not None:
                # the window closes on the last value fetch: a pass the
                # clock stopped before its first dispatch adds nothing
                t_close = time.perf_counter()
                readbacks.append(read)
                steps_at.append(tel.steps - before)
        return state, {
            "window_s": t_close - t_open,
            "steps": tel.steps,
            "samples": sum(n for _, n in readbacks),
            "readbacks": readbacks,
            "steps_per_readback": max(steps_at) if steps_at else 0,
            "counters": {
                "host_stall_s": tel.host_stall_s,
                "dispatches": tel.dispatches,
                "staging_depth_max": tel.staging_depth_max,
                "inflight_depth_max": tel.inflight_depth_max,
                "scan_steps": self.k,
                "passes": len(readbacks),
            },
        }
