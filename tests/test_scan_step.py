"""Multi-step scan training: K fused steps must be semantically identical to
K sequential single steps (params, buffers, metrics, RNG schedule)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpuddp import optim
from tpuddp.data import SyntheticClassification
from tpuddp.models import ToyCNN, ToyMLP
from tpuddp.nn import CrossEntropyLoss
from tpuddp.parallel import make_mesh
from tpuddp.parallel.ddp import DistributedDataParallel
from tpuddp.training.step import stack_batches

KEY = jax.random.key(7)


MB = 1024 * 1024


@pytest.mark.parametrize(
    "scan_steps, n_batches, param_mb, batch_nbytes, want",
    [
        # a long epoch never meets the share-of-the-pass cap: as before PR 40
        pytest.param("auto", 1000, None, None, 32, id="unknown-batch-size-conservative"),
        pytest.param("auto", 1000, 100, None, 32, id="large-model-unknown-batch"),
        # known batch bytes: deep cap, bounded by the ~256MB staging budget
        pytest.param("auto", 1000, 100, MB, 64, id="1MB-batches-deep-cap"),
        pytest.param("auto", 1000, 100, 16 * MB, 16, id="16MB-batches-budget"),
        pytest.param("auto", 1000, 100, 10_000 * MB, 1, id="batch-over-budget"),
        # dispatch-bound small models get the deep cap (BASELINE.md K-sweep)
        pytest.param("auto", 1000, 2, None, 64, id="small-model-deep-cap"),
        # a short pass is cut into at least four dispatches so that the runner
        # has a next chunk to stage while one runs: 5 // 4 = 1, batch by batch
        # (was 5, the whole pass one dispatch, until PR 40)
        pytest.param("auto", 5, 2, None, 1, id="short-epoch-batch-by-batch"),
        pytest.param(16, 1000, 2, None, 16, id="explicit-wins"),
        pytest.param(25, 25, 2, 6_291_456, 25, id="explicit-wins-over-the-share"),
        # the benchmark's loader cell (2048 x 32x32x3 uint8 a batch, 25 a
        # pass): the share is 6, 5 divides 25, so five dispatches and no tail
        pytest.param("auto", 25, 228, 6_291_456, 5, id="loader-cell-25-batches"),
        pytest.param("auto", 24, 228, 6_291_456, 6, id="share-divides"),
        pytest.param("auto", 23, 228, 6_291_456, 5, id="prime-pass-keeps-the-share"),
        pytest.param("auto", 8, 228, 6_291_456, 2, id="eight-batches-four-chunks"),
        pytest.param("auto", 3, 228, 6_291_456, 1, id="fewer-than-four"),
        pytest.param("auto", 150, 228, 6_291_456, 30, id="divisor-in-the-upper-half"),
        pytest.param("auto", 160, 228, 6_291_456, 40, id="budget-binds-first"),
        pytest.param("auto", 200, 2, None, 50, id="small-model-200-batches"),
    ],
)
def test_resolve_scan_steps_table(scan_steps, n_batches, param_mb, batch_nbytes, want):
    from tpuddp.training.loop import resolve_scan_steps
    from tpuddp.training.pipeline import dispatches_per_pass

    k = resolve_scan_steps(
        scan_steps, n_batches,
        param_bytes=None if param_mb is None else param_mb * MB,
        batch_nbytes=batch_nbytes,
    )
    assert k == want
    if scan_steps == "auto" and n_batches >= 4:
        assert dispatches_per_pass(n_batches, k) >= 4


def test_auto_cuts_every_short_pass_into_four_and_never_deepens():
    """Whatever the pass's length: at least four dispatches once there are
    four batches, never a K over what the caps gave before the share, and the
    divisor preference gives up less than half of the share."""
    from tpuddp.training.loop import resolve_scan_steps
    from tpuddp.training.pipeline import dispatches_per_pass

    budget_cap = 256 * MB // 6_291_456  # 40
    for n_batches in range(1, 400):
        k = resolve_scan_steps("auto", n_batches, 228 * MB, 6_291_456)
        assert 1 <= k <= min(budget_cap, n_batches), n_batches
        assert dispatches_per_pass(n_batches, k) >= min(4, n_batches), n_batches
        share = max(1, n_batches // 4)
        if share >= budget_cap:
            assert k == budget_cap, n_batches
            continue
        assert share // 2 < k <= share, n_batches
        if n_batches % k:
            assert k == share, n_batches
            assert all(n_batches % j for j in range(share // 2 + 1, share)), n_batches


def make_batches(k, n=32, shape=(8, 8, 3), seed=0):
    ds = SyntheticClassification(n=n * k, shape=shape, seed=seed)
    return [
        (
            ds.images[i * n : (i + 1) * n],
            ds.labels[i * n : (i + 1) * n],
            np.ones(n, np.float32),
        )
        for i in range(k)
    ]


@pytest.mark.parametrize("mode", ["shard_map", "auto"])
@pytest.mark.parametrize("model_fn", [ToyMLP, lambda: ToyCNN(sync_bn=True)])
def test_scan_equals_sequential(cpu_devices, mode, model_fn):
    mesh = make_mesh(cpu_devices)
    batches = make_batches(4)

    def fresh():
        ddp = DistributedDataParallel(
            model_fn(), optim.Adam(1e-2), CrossEntropyLoss(), mesh=mesh, mode=mode
        )
        return ddp, ddp.init_state(KEY, jnp.zeros((1, 8, 8, 3)))

    # sequential
    ddp_a, state_a = fresh()
    total_a = None
    for b in batches:
        state_a, m = ddp_a.train_step(state_a, ddp_a.shard(b))
        total_a = m if total_a is None else jax.tree_util.tree_map(
            jnp.add, total_a, m
        )

    # fused scan
    ddp_b, state_b = fresh()
    stacked = ddp_b.shard_stacked(stack_batches(batches))
    state_b, total_b = ddp_b.train_step_many(state_b, stacked)

    assert int(state_b.step) == int(state_a.step) == 4
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-5
        ),
        state_a.params,
        state_b.params,
    )
    np.testing.assert_allclose(
        np.sum(np.asarray(total_a["loss_sum"])),
        np.sum(np.asarray(total_b["loss_sum"])),
        rtol=1e-4,
    )
    assert float(np.sum(np.asarray(total_b["n"]))) == 4 * 32


def test_stack_batches_shapes():
    batches = make_batches(3, n=8, shape=(4,))
    xs, ys, ws = stack_batches(batches)
    assert xs.shape == (3, 8, 4)
    assert ys.shape == (3, 8)
    assert ws.shape == (3, 8)


@pytest.mark.parametrize("mode", ["shard_map", "auto"])
def test_eval_scan_equals_sequential(cpu_devices, mode):
    """Fused eval (build_eval_scan_step) must produce exactly the summed
    metrics of per-batch eval_step calls, without touching state."""
    mesh = make_mesh(cpu_devices)
    batches = make_batches(4, seed=3)
    ddp = DistributedDataParallel(
        ToyCNN(sync_bn=True), optim.Adam(1e-2), CrossEntropyLoss(),
        mesh=mesh, mode=mode,
    )
    state = ddp.init_state(KEY, jnp.zeros((1, 8, 8, 3)))

    total_a = None
    for b in batches:
        m = ddp.eval_step(state, ddp.shard(b))
        total_a = m if total_a is None else jax.tree_util.tree_map(
            jnp.add, total_a, m
        )
    total_b = ddp.eval_step_many(state, ddp.shard_stacked(stack_batches(batches)))

    for k in ("loss_sum", "correct", "n"):
        np.testing.assert_allclose(
            np.sum(np.asarray(total_a[k])), np.sum(np.asarray(total_b[k])),
            rtol=1e-5,
        )


def test_sync_buffers_validated_at_wrap_time(cpu_devices):
    """Divergent BN buffers must not be publishable as replicated state: an
    unsynced stateful BatchNorm + sync_buffers='none' is refused at DDP
    construction, and misspelled modes are refused everywhere."""
    mesh = make_mesh(cpu_devices)

    with pytest.raises(ValueError, match="sync_buffers"):
        DistributedDataParallel(
            ToyCNN(sync_bn=False), optim.Adam(1e-2), CrossEntropyLoss(),
            mesh=mesh, mode="shard_map", sync_buffers="none",
        )
    with pytest.raises(ValueError, match="sync_buffers"):
        DistributedDataParallel(
            ToyMLP(), optim.Adam(1e-2), CrossEntropyLoss(),
            mesh=mesh, sync_buffers="brodcast",
        )
    # no divergent buffers (synced BN) -> 'none' is fine; 'pmean' always fine
    for model, sb in [
        (ToyCNN(sync_bn=True), "none"),
        (ToyMLP(), "none"),
        (ToyCNN(sync_bn=False), "pmean"),
    ]:
        ddp = DistributedDataParallel(
            model, optim.Adam(1e-2), CrossEntropyLoss(),
            mesh=mesh, mode="shard_map", sync_buffers=sb,
        )
        state = ddp.init_state(KEY, jnp.zeros((1, 8, 8, 3)))
        (b,) = make_batches(1)
        state, m = ddp.train_step(state, ddp.shard(b))
        assert np.isfinite(np.sum(np.asarray(m["loss_sum"])))


def test_pmean_buffer_sync_averages_divergent_stats(cpu_devices):
    """sync_buffers='pmean' reconciles per-replica BN stats by averaging:
    after one step the published running mean equals the mean over replicas'
    local batch stats (not rank 0's)."""
    mesh = make_mesh(cpu_devices)
    ddp = DistributedDataParallel(
        ToyCNN(sync_bn=False, widths=(4,)), optim.Adam(1e-3),
        CrossEntropyLoss(), mesh=mesh, mode="shard_map", sync_buffers="pmean",
    )
    state = ddp.init_state(KEY, jnp.zeros((1, 8, 8, 3)))
    (b,) = make_batches(1)
    state, _ = ddp.train_step(state, ddp.shard(b))
    # published state is replicated and finite
    bn_state = jax.tree_util.tree_leaves(state.model_state)
    assert all(np.all(np.isfinite(np.asarray(leaf))) for leaf in bn_state)


def test_undeclared_stateful_module_refused_at_wrap_time(cpu_devices):
    """A future custom stateful layer that never declares divergent_state()
    must be refused under sync_buffers='none' — the by-construction guarantee
    that replaced the old isinstance(BatchNorm) check."""
    from tpuddp import nn
    from tpuddp.nn.core import Module

    mesh = make_mesh(cpu_devices)

    class EmaTracker(Module):
        """Stateful, unsynced, and NOT special-cased anywhere."""

        def init(self, key, x):
            return (), {"ema": jnp.zeros(x.shape[-1])}

        def apply(self, params, state, x, ctx):
            new = {"ema": 0.9 * state["ema"] + 0.1 * x.mean(axis=tuple(range(x.ndim - 1)))}
            return x, new

    model = nn.Sequential(nn.Flatten(), EmaTracker(), nn.Linear(10))
    with pytest.raises(ValueError, match="divergent_state"):
        DistributedDataParallel(
            model, optim.Adam(1e-2), CrossEntropyLoss(),
            mesh=mesh, mode="shard_map", sync_buffers="none",
        )

    class VouchedEmaTracker(EmaTracker):
        def divergent_state(self):
            return False  # (for the test; a real EMA would sync instead)

    model2 = nn.Sequential(nn.Flatten(), VouchedEmaTracker(), nn.Linear(10))
    DistributedDataParallel(
        model2, optim.Adam(1e-2), CrossEntropyLoss(),
        mesh=mesh, mode="shard_map", sync_buffers="none",
    )
