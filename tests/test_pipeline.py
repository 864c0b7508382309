"""Async pipelined runner (ISSUE 8, tpuddp/training/pipeline.py): bitwise
parity pipelined-vs-synchronous at every depth, preemption/guard composition,
HLO identity, PrefetchLoader hardening, FusedEvaluator staging, and the
schema-v3 occupancy fields."""

import json
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpuddp import optim
from tpuddp.data import (
    DataLoader,
    PrefetchLoader,
    ShardedDataLoader,
    SyntheticClassification,
)
from tpuddp.models import ToyMLP
from tpuddp.nn import CrossEntropyLoss
from tpuddp.observability import schema as schema_mod
from tpuddp.observability import trace as trace_lib
from tpuddp.parallel import make_mesh
from tpuddp.parallel.ddp import DistributedDataParallel
from tpuddp.resilience import guard as guard_lib
from tpuddp.training import pipeline as pipe


def _np(leaf):
    """Comparable numpy view of any state leaf (typed PRNG keys included)."""
    try:
        if jnp.issubdtype(leaf.dtype, jax.dtypes.prng_key):
            return np.asarray(jax.random.key_data(leaf))
    except Exception:
        pass
    return np.asarray(leaf)


def assert_states_bitwise_equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        xa, ya = _np(x), _np(y)
        assert xa.dtype == ya.dtype
        np.testing.assert_array_equal(xa, ya)


def _make_ddp(mesh, **kw):
    ddp = DistributedDataParallel(
        ToyMLP(hidden=(16,)), optim.Adam(1e-2), CrossEntropyLoss(), mesh=mesh,
        **kw,
    )
    state = ddp.init_state(jax.random.key(0), jnp.zeros((1, 8, 8, 3)))
    return ddp, state


def _loader(mesh, n=640, seed=0, workers=0):
    ds = SyntheticClassification(n=n, shape=(8, 8, 3), seed=seed)
    loader = ShardedDataLoader(ds, 8, mesh, shuffle=True, seed=seed)
    if workers:
        loader = PrefetchLoader(loader, workers=workers)
    loader.set_epoch(0)
    return loader


def _train_epoch(mesh, cfg, scan_k=4, workers=0, inject_cb=None, **ddp_kw):
    ddp, state = _make_ddp(mesh, **ddp_kw)
    loader = _loader(mesh, workers=workers)
    state, acc, interrupted = pipe.run_pass(
        ddp, state, loader, scan_k, ddp.train_step, ddp.train_step_many,
        cfg=cfg, inject_cb=inject_cb,
    )
    assert not interrupted
    return ddp, jax.device_get(state), jax.device_get(acc)


# ------------------------------------------------------------- config knob --


def test_resolve_pipeline_contract():
    assert pipe.resolve_pipeline(None) == pipe.DEFAULT
    assert pipe.resolve_pipeline(True) == pipe.DEFAULT
    sync = pipe.resolve_pipeline(False)
    assert sync.depth == 1 and sync.host_workers == 0 and sync.sync_readback
    # device_augment must NOT differ between on and off: augment placement
    # changes the compiled program, and the A/B must stay HLO-identical
    assert sync.device_augment == pipe.DEFAULT.device_augment
    got = pipe.resolve_pipeline({"depth": 4, "host_workers": 0})
    assert got.depth == 4 and got.host_workers == 0
    with pytest.raises(ValueError, match="unknown training.pipeline"):
        pipe.resolve_pipeline({"dpeth": 4})
    with pytest.raises(ValueError, match="depth"):
        pipe.resolve_pipeline({"depth": 0})
    with pytest.raises(ValueError, match="host_workers"):
        pipe.resolve_pipeline({"host_workers": -1})
    with pytest.raises(ValueError, match="true/false or a mapping"):
        pipe.resolve_pipeline("deep")


def test_staging_depth_byte_capped():
    from tpuddp.utils.batching import STAGE_BYTES_BUDGET

    assert pipe.staging_depth_for(4, None) == 4
    assert pipe.staging_depth_for(4, 1024) == 4
    assert pipe.staging_depth_for(4, STAGE_BYTES_BUDGET // 2) == 2
    assert pipe.staging_depth_for(4, STAGE_BYTES_BUDGET * 2) == 1


# ----------------------------------------------------- bitwise parity core --


def test_pipelined_bitwise_parity_across_depths(mesh):
    """Depth ∈ {1, 2, 4} and the synchronous reference all land the exact
    same params/opt-state after an epoch with a scan remainder (10 batches,
    scan_k=4 -> 2 chunks + 2 single-step remainders)."""
    _, ref_state, ref_acc = _train_epoch(mesh, pipe.SYNCHRONOUS)
    for depth in (1, 2, 4):
        cfg = pipe.PipelineConfig(depth=depth, host_workers=0)
        _, state, acc = _train_epoch(mesh, cfg)
        assert_states_bitwise_equal(ref_state, state)
        assert_states_bitwise_equal(ref_acc, acc)


def test_pipelined_parity_with_prefetch_workers(mesh):
    """The worker-pool loader feeds the identical stream: pipelined run with
    host_workers=3 is bitwise-equal to the synchronous inline run."""
    _, ref_state, ref_acc = _train_epoch(mesh, pipe.SYNCHRONOUS)
    cfg = pipe.PipelineConfig(depth=2, host_workers=3)
    _, state, acc = _train_epoch(mesh, cfg, workers=3)
    assert_states_bitwise_equal(ref_state, state)
    assert_states_bitwise_equal(ref_acc, acc)


def test_pipelined_parity_wus_comm_state(mesh):
    """Weight-update sharding + bf16_ef comm hook (the richest TrainState:
    flat sharded moments + per-replica EF residual) stays bitwise across
    depths — comm_state included."""
    _, ref_state, _ = _train_epoch(
        mesh, pipe.SYNCHRONOUS,
        weight_update_sharding=True, comm_hook="bf16_ef",
    )
    for depth in (2, 4):
        cfg = pipe.PipelineConfig(depth=depth, host_workers=0)
        _, state, _ = _train_epoch(
            mesh, cfg, weight_update_sharding=True, comm_hook="bf16_ef",
        )
        assert_states_bitwise_equal(ref_state, state)


def test_pipelined_parity_managed(cpu_devices):
    """Managed (Accelerator) path: the pipelined loader stack (PrefetchLoader
    workers + StagedUploadLoader) plus the deferred readback drain produces
    bitwise-identical params/opt-state to plain inline loading."""
    from tpuddp.accelerate import Accelerator, StagedUploadLoader
    from tpuddp.nn import CrossEntropyLoss as CE
    from train_accelerate import train

    def run(pipelined):
        acc = Accelerator(
            mesh=make_mesh(cpu_devices[:4]), seed=0, fuse_steps=4
        )
        ds = SyntheticClassification(n=256, shape=(8, 8, 3), seed=1)
        model, opt, loader = acc.prepare(
            ToyMLP(hidden=(16,)),
            optim.Adam(1e-2),
            DataLoader(ds, batch_size=8, shuffle=True),
        )
        if pipelined:
            loader = StagedUploadLoader(PrefetchLoader(loader, workers=2))
        loader.set_epoch(0)
        loss, n = train(model, loader, CE(), opt, acc, augment=None)
        return model.params, opt.opt_state, loss, n

    p_ref, o_ref, loss_ref, n_ref = run(False)
    p_pipe, o_pipe, loss_pipe, n_pipe = run(True)
    assert (loss_ref, n_ref) == (loss_pipe, n_pipe)
    assert_states_bitwise_equal(
        jax.device_get((p_ref, o_ref)), jax.device_get((p_pipe, o_pipe))
    )


def test_pipelined_guard_skip_parity(mesh):
    """A nan-poisoned batch is firewalled identically at every depth: same
    skip counters, bitwise-identical state (the skipped update is a no-op on
    both paths)."""

    def make_inject():
        seen = {"i": 0}

        def inject(host_batch):
            i = seen["i"]
            seen["i"] += 1
            if i == 3:
                x, y, w = host_batch
                x = np.asarray(x, np.float32).copy()
                x[0, 0, 0, 0] = np.nan
                return x, y, w
            return host_batch

        return inject

    _, ref_state, _ = _train_epoch(
        mesh, pipe.SYNCHRONOUS, inject_cb=make_inject(), guard=True,
    )
    total_ref, consec_ref = guard_lib.read_skip_counters(ref_state)
    assert total_ref >= 1  # the poison was seen and firewalled
    for depth in (2, 4):
        cfg = pipe.PipelineConfig(depth=depth, host_workers=0)
        _, state, _ = _train_epoch(
            mesh, cfg, inject_cb=make_inject(), guard=True,
        )
        assert guard_lib.read_skip_counters(state) == (total_ref, consec_ref)
        assert_states_bitwise_equal(ref_state, state)


def test_midepoch_preempt_no_batch_lost_or_double_applied(mesh):
    """An interrupted pass returns the state of exactly the dispatches it
    issued: replaying the recorded dispatch sequence synchronously from the
    same init lands the identical state — nothing in flight was lost, nothing
    was applied twice."""
    for depth in (1, 3):
        ddp, state0 = _make_ddp(mesh)
        issued = []

        def rec_one(s, b):
            issued.append(("one", b))
            return ddp.train_step(s, b)

        def rec_many(s, b):
            issued.append(("many", b))
            return ddp.train_step_many(s, b)

        seen = {"n": 0}

        def probe(i, b):
            seen["n"] = i + 1

        loader = _loader(mesh)
        state, acc, interrupted = pipe.run_pass(
            ddp, state0, loader, 2, rec_one, rec_many,
            cfg=pipe.PipelineConfig(depth=depth, host_workers=0),
            probe_cb=probe, poll=lambda: seen["n"] >= 7,
        )
        assert interrupted
        # replay: fresh identical init, the same dispatches, synchronously
        ddp2, replay = _make_ddp(mesh)
        for kind, b in issued:
            step = ddp2.train_step if kind == "one" else ddp2.train_step_many
            replay, _ = step(replay, b)
        assert_states_bitwise_equal(
            jax.device_get(state), jax.device_get(replay)
        )


def test_hlo_identity_pipeline_on_off(mesh):
    """The pipeline never enters program construction: the lowered scan-step
    HLO after a pipelined pass is byte-identical to the synchronous run's,
    and both passes dispatched the identical shape sequence."""
    shapes = {}

    def run(key, cfg):
        ddp, state = _make_ddp(mesh)
        seq = []

        def rec_one(s, b):
            seq.append(("one", jax.tree_util.tree_map(np.shape, b)))
            return ddp.train_step(s, b)

        def rec_many(s, b):
            seq.append(("many", jax.tree_util.tree_map(np.shape, b)))
            return ddp.train_step_many(s, b)

        loader = _loader(mesh)
        state, _, _ = pipe.run_pass(
            ddp, state, loader, 4, rec_one, rec_many, cfg=cfg,
        )
        shapes[key] = seq
        # lower the exact program the pass used, against a real staged chunk
        state_struct = jax.tree_util.tree_map(
            lambda l: jax.ShapeDtypeStruct(np.shape(l), l.dtype), state
        )
        from tpuddp.training.step import stack_batches

        chunk = []
        for b in _loader(mesh):
            chunk.append(b)
            if len(chunk) == 4:
                break
        stacked = ddp.shard_stacked(stack_batches(chunk))
        lowered = jax.jit(
            lambda s, b: ddp.train_step_many(s, b)
        ).lower(state_struct, stacked)
        return lowered.as_text()

    on = run("on", pipe.PipelineConfig(depth=4, host_workers=0))
    off = run("off", pipe.SYNCHRONOUS)
    assert shapes["on"] == shapes["off"]
    assert on == off


# ------------------------------------ when a staged chunk is released (ISSUE 40) --


class _CountingLoader:
    """``loader``'s batches, counting how many it has handed over."""

    def __init__(self, loader):
        self.loader, self.drawn = loader, 0

    def __len__(self):
        return len(self.loader)

    def __iter__(self):
        for batch in self.loader:
            self.drawn += 1
            yield batch


@pytest.mark.parametrize("scan_k", [4, 1])
@pytest.mark.parametrize("depth", [1, 2, 4])
def test_first_chunk_is_dispatched_as_soon_as_it_is_staged(mesh, depth, scan_k):
    """The first dispatch of a pass is issued when exactly K batches (one on
    the per-batch cadence) were drawn from the loader, whatever ``depth``;
    dispatch i after it is released by the staging of chunk i + depth (or by
    the end of the loader), so at most ``depth`` chunks are ever left staged
    behind a dispatch; and the pass is still the synchronous one, bitwise."""
    ddp, state0 = _make_ddp(mesh)
    loader = _CountingLoader(_loader(mesh, n=8 * 8 * 21))
    n_batches = len(loader)
    assert n_batches == 21  # at scan_k 4: five chunks and one single step
    drawn_at, steps_of = [], []

    def rec_one(s, b):
        drawn_at.append(loader.drawn)
        steps_of.append(1)
        return ddp.train_step(s, b)

    def rec_many(s, b):
        drawn_at.append(loader.drawn)
        steps_of.append(scan_k)
        return ddp.train_step_many(s, b)

    tel = _Tel()
    state, acc, interrupted = pipe.run_pass(
        ddp, state0, loader, scan_k, rec_one, rec_many,
        cfg=pipe.PipelineConfig(depth=depth, host_workers=0), tel=tel,
    )
    assert not interrupted
    whole = n_batches // scan_k
    assert steps_of == [scan_k] * whole + [1] * (n_batches - whole * scan_k)
    assert len(steps_of) == pipe.dispatches_per_pass(n_batches, scan_k)
    assert drawn_at[0] == scan_k
    assert drawn_at[1:whole] == [
        min(scan_k * (i + depth + 1), n_batches) for i in range(1, whole)
    ]
    assert drawn_at[whole:] == [n_batches] * (n_batches - whole * scan_k)
    assert tel.staged_behind[0] == 0 and max(tel.staged_behind) <= depth
    # the queue does fill behind the first dispatch: a dispatch released by
    # a newly staged chunk leaves `depth` behind it, the end's flush fewer
    assert max(tel.staged_behind) == (depth if whole - 1 > depth else whole - 2)

    ddp_ref, ref0 = _make_ddp(mesh)
    ref, ref_acc, _ = pipe.run_pass(
        ddp_ref, ref0, _loader(mesh, n=8 * 8 * 21), scan_k,
        ddp_ref.train_step, ddp_ref.train_step_many, cfg=pipe.SYNCHRONOUS,
    )
    assert_states_bitwise_equal(jax.device_get(ref), jax.device_get(state))
    assert_states_bitwise_equal(jax.device_get(ref_acc), jax.device_get(acc))


@pytest.mark.parametrize(
    "n_batches, scan_k, accum, want",
    [(25, 5, 1, 5), (25, 25, 1, 1), (10, 4, 1, 4), (10, 4, 2, 3), (8, 4, 2, 2),
     (21, 1, 1, 21), (3, 4, 1, 3), (0, 4, 1, 0)],
)
def test_dispatches_per_pass_counts_chunks_and_the_tail(n_batches, scan_k, accum, want):
    assert pipe.dispatches_per_pass(n_batches, scan_k, accum) == want


# ------------------------------------------------------ deferred readback --


def test_readback_drain_order_and_inflight():
    drain = pipe._ReadbackDrain()

    class FakeLeaf:
        def __init__(self, ready):
            self._ready = ready
            self.shape, self.dtype = (), np.float32

        def is_ready(self):
            return self._ready

    # numpy metrics (no is_ready): folded eagerly, in order
    drain.offer({"loss_sum": np.asarray([1.0])})
    drain.offer({"loss_sum": np.asarray([2.0])})
    assert drain.inflight == 0
    out = drain.drain()
    np.testing.assert_array_equal(np.asarray(out["loss_sum"]), [3.0])
    # an in-flight leaf defers the fold and is visible as depth
    d2 = pipe._ReadbackDrain()
    d2.offer({"m": FakeLeaf(ready=False)})
    assert d2.inflight == 1


def test_stall_clock_take_semantics():
    c = pipe.StallClock()
    c.add(0.5)
    c.add(0.25)
    assert c.total == pytest.approx(0.75)
    assert c.take() == pytest.approx(0.75)
    assert c.take() == 0.0
    assert c.total == pytest.approx(0.75)


# ------------------------------------------------ PrefetchLoader hardening --


def test_prefetch_pool_identical_stream(cpu_devices):
    mesh4 = make_mesh(cpu_devices[:4])
    ds = SyntheticClassification(n=100, shape=(4, 4, 3), seed=3)
    base = ShardedDataLoader(ds, 4, mesh4, shuffle=True, seed=1)
    pool = PrefetchLoader(
        ShardedDataLoader(ds, 4, mesh4, shuffle=True, seed=1), workers=4
    )
    for epoch in range(2):
        base.set_epoch(epoch)
        pool.set_epoch(epoch)
        got = list(pool)
        want = list(base)
        assert len(got) == len(want)
        for (xa, ya, wa), (xb, yb, wb) in zip(want, got):
            np.testing.assert_array_equal(xa, xb)
            np.testing.assert_array_equal(ya, yb)
            np.testing.assert_array_equal(wa, wb)


class _ExplodingPlanLoader:
    """make_batch_plan protocol whose fetch dies at batch 2 — the worker-pool
    exception path."""

    def __len__(self):
        return 6

    def set_epoch(self, epoch):
        pass

    def make_batch_plan(self):
        def fetch(s):
            if s == 2:
                return self._boom()
            return (np.zeros((4, 2)), np.zeros(4, np.int32), np.ones(4, np.float32))

        return 6, fetch

    def _boom(self):
        raise RuntimeError("decode failed in worker")


def test_prefetch_pool_propagates_exception_with_traceback():
    pre = PrefetchLoader(_ExplodingPlanLoader(), workers=3)
    with pytest.raises(RuntimeError, match="decode failed in worker") as ei:
        list(pre)
    # the ORIGINAL producer-side frames survive the thread hop
    frames = []
    tb = ei.value.__traceback__
    while tb is not None:
        frames.append(tb.tb_frame.f_code.co_name)
        tb = tb.tb_next
    assert "_boom" in frames and "fetch" in frames


def test_prefetch_serial_propagates_exception_with_traceback():
    class Exploding:
        def __len__(self):
            return 3

        def __iter__(self):
            yield (np.zeros(1), np.zeros(1), np.ones(1))
            raise RuntimeError("loader blew up mid-epoch")

    pre = PrefetchLoader(Exploding(), workers=1)
    with pytest.raises(RuntimeError, match="blew up mid-epoch") as ei:
        list(pre)
    frames = []
    tb = ei.value.__traceback__
    while tb is not None:
        frames.append(tb.tb_frame.f_code.co_name)
        tb = tb.tb_next
    assert "__iter__" in frames  # the producer generator's frame


def _prefetch_threads():
    return [
        t for t in threading.enumerate() if t.name.startswith("tpuddp-prefetch")
    ]


@pytest.mark.parametrize("workers", [1, 3])
def test_prefetch_no_thread_leak_on_partial_iteration(workers):
    """Abandoning the iterator mid-epoch (the preemption-drain shape) must
    reap every worker — including one blocked on a full queue."""
    ds = SyntheticClassification(n=400, shape=(4, 4, 3), seed=0)
    pre = PrefetchLoader(DataLoader(ds, batch_size=4), depth=2, workers=workers)
    it = iter(pre)
    next(it)
    next(it)
    it.close()  # GeneratorExit -> the finally block reaps the pool
    deadline = time.monotonic() + 5
    while _prefetch_threads() and time.monotonic() < deadline:
        time.sleep(0.01)
    assert _prefetch_threads() == []


def test_prefetch_effective_depth_byte_capped():
    from tpuddp.utils.batching import STAGE_BYTES_BUDGET

    class Huge:
        batch_nbytes = STAGE_BYTES_BUDGET  # one batch fills the budget

        def __len__(self):
            return 1

    class Small:
        batch_nbytes = 1024

        def __len__(self):
            return 1

    class NoBytes:
        def __len__(self):
            return 1

    assert PrefetchLoader(Huge(), depth=8).effective_depth() == 1
    assert PrefetchLoader(Small(), depth=8).effective_depth() == 8
    # unknowable batch bytes -> the configured depth survives
    assert PrefetchLoader(NoBytes(), depth=3).effective_depth() == 3


# ------------------------------------------- the pass's own spans (ISSUE 39) --


class _Tel:
    """``run_pass``'s ``tel=`` interface, keeping what it is given."""

    def __init__(self):
        self.host_stall_s = 0.0
        self.staged_behind = []  # the staged queue's length after each dispatch left it

    def offer_batch(self, batch):
        pass

    def pre_dispatch(self, n_steps):
        pass

    def post_dispatch(self, n_steps, n_samples, metrics=None, host_stall_s=0.0,
                      staging_depth=0, **_):
        self.host_stall_s += host_stall_s
        self.staged_behind.append(staging_depth)


def _traced_pass(mesh, workers, scan_k, tracer, **kw):
    """One pass over 10 batches (8 a replica) under ``tracer`` (a Tracer,
    NULL or None), as the epoch driver runs it: the spans' parent is an
    epoch span."""
    ddp, state = _make_ddp(mesh)
    loader = _loader(mesh, workers=workers)
    live = isinstance(tracer, trace_lib.Tracer)
    epoch = tracer.start_span("epoch 0", trace_lib.KIND_EPOCH, tid="train") if live else None
    out = pipe.run_pass(
        ddp, state, loader, scan_k, ddp.train_step, ddp.train_step_many,
        cfg=pipe.PipelineConfig(depth=2, host_workers=workers),
        tracer=tracer, trace_parent=epoch, **kw,
    )
    if live:
        tracer.end_span(epoch)
    return loader, epoch, out


def _spans_by_name(tracer):
    by = {}
    for span in tracer.endpoint_payload(limit=None)["spans"]:
        by.setdefault(span["name"], []).append(span)
    return by


@pytest.mark.parametrize("scan_k", [4, 1])
@pytest.mark.parametrize("workers", [0, 1, 2])
def test_traced_pass_span_tree(mesh, workers, scan_k):
    """Names, kinds and parents of what one pass opens: everything under the
    epoch span but the two halves of a ``stage``; one ``input_wait`` a
    ``next``; one ``loader_gather`` and one ``loader_pad`` a batch and one
    ``loader_order`` a pass, opened by the thread that assembles."""
    tracer = trace_lib.Tracer("train", process_index=0)
    loader, epoch, (_, _, interrupted) = _traced_pass(mesh, workers, scan_k, tracer)
    assert not interrupted
    n_batches = len(loader)
    assert n_batches == 10  # at scan_k 4: two chunks and two single steps
    by = _spans_by_name(tracer)
    kinds = {name: {s["kind"] for s in spans} for name, spans in by.items()}
    assert kinds == {
        "epoch 0": {"epoch"}, "input_wait": {"queue_wait"}, "stage": {"stage"},
        "stage_put": {"load"}, "dispatch": {"dispatch"}, "readback": {"readback"},
        "loader_order": {"load"}, "loader_gather": {"load"}, "loader_pad": {"load"},
        **({"stage_stack": {"load"}} if scan_k > 1 else {}),
    }
    assert len({s["trace_id"] for spans in by.values() for s in spans}) == 1
    for name in ("input_wait", "stage", "dispatch", "readback",
                 "loader_order", "loader_gather", "loader_pad"):
        assert {s["parent_id"] for s in by[name]} == {epoch.span_id}, name
    # a stage's halves lie inside it
    stages = {s["span_id"]: s for s in by["stage"]}
    stacked = n_batches // scan_k if scan_k > 1 else 0
    assert len(stages) == len(by["stage_put"]) == stacked + n_batches - stacked * scan_k
    assert len(by.get("stage_stack", [])) == stacked
    halves = by["stage_put"] + by.get("stage_stack", [])
    for half in halves:
        outer = stages[half["parent_id"]]
        assert outer["t_start_ns"] <= half["t_start_ns"]
        assert half["duration_ms"] <= outer["duration_ms"]
    # one wait a next: every batch, and the next that found the loader empty
    waits = by["input_wait"]
    assert len(waits) == n_batches + 1
    assert [bool(w["attrs"].get("exhausted")) for w in waits] == [False] * n_batches + [True]
    # the loader's own: once a pass, once a batch, on the assembling thread
    assert len(by["loader_order"]) == 1
    assert len(by["loader_gather"]) == len(by["loader_pad"]) == n_batches
    rows = {s["tid"] for s in by["loader_gather"] + by["loader_pad"]}
    if workers == 0:
        assert rows == {"train"}  # inline: the pass's own thread, its parent's row
    else:
        assert rows and all(r.startswith("tpuddp-prefetch") for r in rows)
        assert len(rows) <= workers
    assert tracer.open_span_summaries() == [] and tracer.dropped == 0
    # every dispatch says which of its pass it is; the first says how long
    # the pass took to reach it, which lies inside the epoch span's head
    dispatches = sorted(by["dispatch"], key=lambda s: s["t_start_ns"])
    assert len(dispatches) == pipe.dispatches_per_pass(n_batches, scan_k)
    assert [d["attrs"]["index"] for d in dispatches] == list(range(len(dispatches)))
    assert ["head_s" in d["attrs"] for d in dispatches] == (
        [True] + [False] * (len(dispatches) - 1)
    )
    (opened,) = by["epoch 0"]
    reach_s = (dispatches[0]["t_start_ns"] - opened["t_start_ns"]) / 1e9
    assert 0 < dispatches[0]["attrs"]["head_s"] <= reach_s
    # and it was issued before the second chunk's batches were waited for
    first_waits = sorted(w["t_start_ns"] for w in by["input_wait"])
    assert first_waits[max(scan_k, 1)] > dispatches[0]["t_start_ns"]


@pytest.mark.parametrize("workers", [0, 2])
def test_traced_pass_is_bitwise_the_untraced_one(mesh, workers):
    """Same batches in the same order and the same final state under a live
    tracer, under NULL and under none."""
    seen, states = {}, {}
    for label, tracer in (
        ("none", None), ("null", trace_lib.NULL),
        ("live", trace_lib.Tracer("train", process_index=0)),
    ):
        batches = seen[label] = []
        _, _, (state, acc, _) = _traced_pass(
            mesh, workers, 4, tracer,
            probe_cb=lambda i, b: batches.append([np.array(a) for a in b]),
        )
        states[label] = jax.device_get((state, acc))
    for label in ("null", "live"):
        assert len(seen[label]) == len(seen["none"]) == 10
        for got, want in zip(seen[label], seen["none"]):
            for a, b in zip(got, want):
                np.testing.assert_array_equal(a, b)
        assert_states_bitwise_equal(states[label], states["none"])


def test_input_wait_spans_are_the_stall_clocks_intervals(mesh, monkeypatch):
    """The ``input_wait`` spans bracket exactly what ``StallClock.add`` is
    given: on a clock that ticks once a reading, each span that handed a
    batch over is its stall plus the two readings that bracket it, whatever
    the (inline, traced) loader read in between."""
    ticks = {"n": 0}

    def tick():
        ticks["n"] += 1
        return ticks["n"]

    tracer = trace_lib.Tracer("train", process_index=0)
    tel = _Tel()
    with monkeypatch.context() as patched:
        patched.setattr(time, "perf_counter", lambda: tick() * 1e-3)
        patched.setattr(time, "perf_counter_ns", lambda: tick() * 1_000_000)
        _traced_pass(mesh, 0, 4, tracer, tel=tel)
    waits = [w for w in _spans_by_name(tracer)["input_wait"] if not w["attrs"].get("exhausted")]
    assert len(waits) == 10
    total_ms = sum(w["duration_ms"] for w in waits)
    assert total_ms == pytest.approx(1e3 * tel.host_stall_s + 2 * len(waits))
    assert tel.host_stall_s >= 10 * 4e-3  # the loader's own readings lie inside


class _Boom(RuntimeError):
    pass


@pytest.mark.parametrize("ending", ["whole", "interrupted", "raised"])
@pytest.mark.parametrize("workers", [0, 1, 2])
def test_loader_holds_no_tracer_after_the_pass(mesh, workers, ending):
    """However a traced pass ends, the loader gives the tracer back
    (``finally``), its workers are reaped before ``run_pass`` returns, every
    span the pass and the loader opened is closed, and iterating the loader
    afterwards opens nothing."""
    from tpuddp.data import loader as loader_mod

    tracer = trace_lib.Tracer("train", process_index=0)
    seen = {"n": 0}
    handed = []
    real_set = loader_mod._Traced.set_tracer

    def probe(i, batch):
        seen["n"] = i + 1
        if ending == "raised" and i == 6:
            raise _Boom()

    kw = {"probe_cb": probe}
    if ending == "interrupted":
        kw["poll"] = lambda: seen["n"] >= 7
    with pytest.MonkeyPatch.context() as patched:
        patched.setattr(
            loader_mod._Traced, "set_tracer",
            lambda self, tracer, parent=None: (
                handed.append((self, tracer)), real_set(self, tracer, parent)
            ) and None,
        )
        if ending == "raised":
            with pytest.raises(_Boom):
                _traced_pass(mesh, workers, 2, tracer, **kw)
        else:
            _, _, (_, _, interrupted) = _traced_pass(mesh, workers, 2, tracer, **kw)
            assert interrupted == (ending == "interrupted")
    assert _prefetch_threads() == []
    # the driver's own span is open where the pass raised; nothing else is
    assert {s["name"] for s in tracer.open_span_summaries()} <= {"epoch 0"}
    (loader, first), (same, last) = handed
    assert loader is same and first is tracer and last is None
    assert loader._trace is loader_mod._UNTRACED
    before = tracer.completed
    assert len(list(loader)) == 10
    assert tracer.completed == before


@pytest.mark.parametrize("tracer", [None, trace_lib.NULL], ids=["none", "null"])
def test_untraced_pass_hands_the_loader_nothing(mesh, tracer, monkeypatch):
    """With no tracer the loader is never handed one, what its plan brackets
    its calls with is the NULL tracer's one shared span (nothing is made per
    batch), and neither the runner nor the loader opens a span on any live
    tracer."""
    from tpuddp.data import loader as loader_mod

    handed, opened, live = [], [], []
    monkeypatch.setattr(
        loader_mod._Traced, "set_tracer", lambda self, *a, **k: handed.append(a)
    )
    real_open = loader_mod._open

    def spy_open(trace, name):
        opened.append(real_open(trace, name))
        return opened[-1]

    monkeypatch.setattr(loader_mod, "_open", spy_open)
    real_start = trace_lib.Tracer.start_span
    monkeypatch.setattr(
        trace_lib.Tracer, "start_span",
        lambda self, *a, **k: live.append(a) or real_start(self, *a, **k),
    )
    loader, _, _ = _traced_pass(mesh, 0, 4, tracer)
    assert handed == [] and live == []
    assert len(opened) == 1 + 2 * len(loader)
    assert all(span is trace_lib.NULL_SPAN for span in opened)
    assert loader._trace is loader_mod._UNTRACED


# -------------------------------------------------- FusedEvaluator staging --


def test_fused_evaluator_staged_uploads_bitwise_on_ragged_stream(cpu_devices):
    """Eval staging (uploads issued at add-time) must not change metrics —
    ragged final buckets included."""
    from tpuddp.accelerate import Accelerator, FusedEvaluator
    from tpuddp.nn import CrossEntropyLoss as CE

    rng = np.random.RandomState(0)
    batches = [
        (rng.randn(n, 8, 8, 3).astype(np.float32),
         rng.randint(0, 10, n).astype(np.int32),
         np.ones(n, np.float32))
        for n in (8, 8, 8, 5)  # ragged tail
    ]

    def run(stage):
        acc = Accelerator(mesh=make_mesh(cpu_devices[:2]), seed=0)
        model = acc.prepare(ToyMLP(hidden=(16,)))
        model.eval()
        model(batches[0][0][:1])  # init
        ev = FusedEvaluator(model, CE(), fuse_steps=3, stage_uploads=stage)
        for x, y, w in batches:
            ev.add(x, y, w)
        return ev.finalize()

    loss_a, correct_a, n_a = run(False)
    loss_b, correct_b, n_b = run(True)
    assert (correct_a, n_a) == (correct_b, n_b)
    assert loss_a == loss_b  # bitwise: same program, same inputs


# ------------------------------------------------------- schema/telemetry --


def test_step_stats_v3_requires_occupancy_fields():
    base = {
        "epoch": 0, "step_start": 0, "steps": 4,
        "step_time_ms_p50": 1.0, "step_time_ms_p95": 1.0,
        "step_time_ms_p99": 1.0, "step_time_ms_max": 1.0,
        "samples_per_sec": 10.0,
    }
    occ = {"host_stall_ms": 0.1, "inflight_depth": 2, "staging_queue_depth": 1}
    good = schema_mod.stamp("step_stats", {**base, **occ})
    assert schema_mod.validate_record(good) == []
    missing = schema_mod.stamp("step_stats", base)
    errs = schema_mod.validate_record(missing)
    assert any("host_stall_ms" in e for e in errs)
    # a v2 record (pre-pipeline history) without them stays valid
    legacy = {**base, "type": "step_stats", "schema_version": 2}
    assert schema_mod.validate_record(legacy) == []


def test_history_carries_occupancy_fields(mesh, tmp_path):
    """End-to-end: a pipelined epoch-driver run writes step_stats windows
    carrying the occupancy fields and epoch rows carrying host_stall_ms, and
    the whole file validates at schema v3."""
    from tpuddp.observability import schema
    from tpuddp.training.loop import run_training_loop

    ds = SyntheticClassification(n=256, shape=(8, 8, 3), seed=0)
    loader = ShardedDataLoader(ds, 8, mesh, shuffle=True)
    test_loader = ShardedDataLoader(ds, 8, mesh, shuffle=True)
    ddp = DistributedDataParallel(
        ToyMLP(hidden=(16,)), optim.Adam(1e-2), CrossEntropyLoss(), mesh=mesh
    )
    state = ddp.init_state(jax.random.key(0), jnp.zeros((1, 8, 8, 3)))
    run_training_loop(
        ddp, state, loader, test_loader, str(tmp_path),
        num_epochs=1, checkpoint_epoch=1, step_stats_every=2, scan_steps=2,
        pipeline={"depth": 2, "host_workers": 0},
        log=lambda *_: None,
    )
    records = [
        json.loads(l)
        for l in (tmp_path / "history.jsonl").read_text().splitlines()
    ]
    assert schema.validate_history_records(records) == []
    meta = records[0]
    assert meta["pipeline"]["depth"] == 2
    windows = [r for r in records if r["type"] == "step_stats"]
    assert windows
    for w in windows:
        assert w["host_stall_ms"] >= 0
        assert w["staging_queue_depth"] >= 0
        assert w["inflight_depth"] >= 0
    epochs = [r for r in records if r["type"] == "epoch"]
    assert epochs and epochs[0]["host_stall_ms"] >= 0
