"""The resident feed, with the program counters that a model's step carries
out beside its loss sums (``moe_*`` of ``tpuddp/nn/moe.py``) summed over the
window into ``window["counters"]``.

The window is the resident feed's own, to the letter: :meth:`measure` below
calls it unchanged. While it runs, each dispatch's metrics are remembered
(a list append); they are read back after the window has closed.
"""

from __future__ import annotations

import os

import jax
import numpy as np

from benchmark import cells

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
# the feed this one extends is the one it is named after, of the same checkout
_BASE = os.path.splitext(os.path.basename(__file__))[0].rsplit("_", 1)[0]
_resident = cells.load_module("feeds", _BASE, _ROOT)

_NOT_COUNTERS = ("loss_sum", "n")


class _Remembering:
    """``ddp.train_step_many``, remembering what each dispatch returned."""

    def __init__(self, ddp):
        self.ddp, self.metrics = ddp, []

    def train_step_many(self, state, chunk):
        state, metrics = self.ddp.train_step_many(state, chunk)
        self.metrics.append(metrics)
        return state, metrics


class Feed(_resident.Feed):
    def measure(self, state, seconds: float):
        ddp = self.ddp
        self.ddp = remembering = _Remembering(ddp)
        try:
            state, window = super().measure(state, seconds)
        finally:
            self.ddp = ddp
        totals = {}
        for metrics in jax.device_get(remembering.metrics):
            for name, value in metrics.items():
                if name not in _NOT_COUNTERS:
                    totals[name] = totals.get(name, 0.0) + float(np.sum(value))
        window["counters"] = {**window["counters"], **totals}
        return state, window
