"""Resident feed: seeded batches made on the device, laid out as the scan
step wants them (``shard_stacked``'s layout), and stepped K at a time through
``ddp.train_step_many`` with the host taken out: no loader, no staging. Two
dispatches are kept in flight, so the device never waits for the host, and
every dispatch's metrics are read back, so each completion is seen.

Traffic parameters: ``batch_per_chip``, ``resident_batches`` (a multiple of
``scan_steps``; the chunks are cycled), ``scan_steps`` (K).
"""

from __future__ import annotations

import collections
import time

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmark import cells
from tpuddp.parallel.mesh import data_axes


class Feed:
    def __init__(self, cell, ddp, seed: int, spans):
        traffic = cell.traffic
        self.cell, self.ddp, self.seed, self.spans = cell, ddp, seed, spans
        self.k = int(traffic["scan_steps"])
        self.n_batches = int(traffic["resident_batches"])
        if self.k < 1 or self.n_batches % self.k:
            raise ValueError(
                f"resident_batches {self.n_batches} is not a multiple of scan_steps {self.k}"
            )
        self.global_batch = int(traffic["batch_per_chip"]) * cell.chips
        self.chunks = None

    def setup(self) -> None:
        mesh, axis = self.ddp.mesh, data_axes(self.ddp.mesh)
        system, config = cells.load_system(self.cell), self.cell.config
        layout = lambda ndim: NamedSharding(mesh, P(None, axis, *([None] * (ndim - 2))))
        self.arrays = system.make_batches(
            config, self.seed, self.n_batches, self.global_batch, layout
        )
        weights = self.ddp.shard_stacked(
            system.unit_weights(config, self.k, self.global_batch)
        )
        if self.n_batches == self.k:
            self.chunks = [(*self.arrays, weights)]
        else:
            self.chunks = [
                self.ddp.shard_stacked(tuple(a[i:i + self.k] for a in self.arrays)) + (weights,)
                for i in range(0, self.n_batches, self.k)
            ]

    def sample_batches(self, n: int, batch: int):
        """``n`` host batches of ``batch`` samples from the seeded data, for
        the correctness check."""
        if n > self.n_batches or batch > self.global_batch:
            raise ValueError(f"the feed holds {self.n_batches} batches of {self.global_batch}")
        return [tuple(np.asarray(a[i, :batch]) for a in self.arrays) for i in range(n)]

    def warm(self, state):
        """One dispatch of each program the window uses, fenced."""
        state, metrics = self.ddp.train_step_many(state, self.chunks[0])
        jax.block_until_ready(metrics)
        return state

    def measure(self, state, seconds: float):
        spans, ddp = self.spans, self.ddp
        inflight = collections.deque()
        readbacks = []

        def read_oldest():
            with spans.span("readback"):
                m = jax.device_get(inflight.popleft())
            readbacks.append((float(np.sum(m["loss_sum"])), float(np.sum(m["n"]))))

        dispatched = 0
        t_open = time.perf_counter()
        while time.perf_counter() - t_open < seconds:
            with spans.span("dispatch"):
                state, metrics = ddp.train_step_many(
                    state, self.chunks[dispatched % len(self.chunks)]
                )
            inflight.append(metrics)
            dispatched += 1
            if len(inflight) > 1:
                read_oldest()
        while inflight:
            read_oldest()
        t_close = time.perf_counter()
        return state, {
            "window_s": t_close - t_open,
            "steps": dispatched * self.k,
            "samples": sum(n for _, n in readbacks),
            "readbacks": readbacks,
            "steps_per_readback": self.k,
            "counters": {},
        }
