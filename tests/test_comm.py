"""Gradient-communication hooks (tpuddp/parallel/comm.py) — the tpuddp
rebuild of torch DDP's bucketed allreduce + comm hooks (SURVEY.md §2b,
``default_hooks.bf16_compress_hook`` et al.).

Pinned contracts:

- bucket assembly: deterministic whole-leaf packing, cap respected, oversized
  leaves isolated, padding absorbed by the tail, exact cover of the padded
  flat vector;
- the wire really carries bf16: the compiled HLO of the explicit step holds a
  bf16 all-reduce (or bf16 reduce-scatter under weight_update_sharding);
- numerics: bf16_ef training tracks the fp32 path's loss within tolerance
  over N steps on the 8-device virtual world, in every mode the knob reaches
  (explicit shard_map / auto, scan-fused, grad accumulation, managed);
- the comm-bytes counter shows the >= 45% gradient-byte reduction the ISSUE
  acceptance demands;
- the bf16_ef error-feedback residual is training state: it must be nonzero
  once training has run, and must checkpoint-round-trip losslessly on both
  the native (training/checkpoint.py) and managed (save_state/load_state)
  paths.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpuddp import nn, optim
from tpuddp.data import SyntheticClassification
from tpuddp.models import ToyMLP
from tpuddp.parallel import comm as comm_lib
from tpuddp.parallel import make_mesh
from tpuddp.parallel.ddp import DistributedDataParallel
from tpuddp.resilience import guard as guard_lib
from tpuddp.training import checkpoint as ckpt
from tpuddp.training.step import stack_batches

KEY = jax.random.key(0)
MB = 1024 * 1024


def cap_mb(elems: int) -> float:
    """bucket_cap_mb holding exactly ``elems`` f32 elements."""
    return elems * 4 / MB


def make_batch(n=64, seed=5, shape=(8, 8, 3)):
    ds = SyntheticClassification(n=n, shape=shape, seed=seed)
    x, y = ds.get_batch(np.arange(n))
    return x, y, np.ones(n, np.float32)


def build(mesh, hook, mode="shard_map", wus=False, accum=1, cap=None, **kw):
    return DistributedDataParallel(
        ToyMLP(hidden=(16,)),
        optim.Adam(1e-2),
        nn.CrossEntropyLoss(),
        mesh=mesh,
        mode=mode,
        comm_hook=hook,
        weight_update_sharding=wus,
        grad_accumulation=accum,
        **({"bucket_cap_mb": cap} if cap is not None else {}),
        **kw,
    )


# ---------------------------------------------------------------- buckets --


def test_buckets_cover_padded_vector_exactly():
    # 18 raw elements padded to a world multiple (24): the tail bucket
    # absorbs the padding so the buckets tile [0, total) with no gap
    b = comm_lib.make_buckets((6, 6, 6), total=24, bucket_cap_mb=cap_mb(16))
    assert b == ((0, 12), (12, 24))
    assert b[0][0] == 0 and b[-1][1] == 24
    for (s0, e0), (s1, _) in zip(b, b[1:]):
        assert e0 == s1 and s0 < e0


def test_bucket_cap_respected_on_whole_leaf_boundaries():
    sizes = (4, 4, 4, 4, 4)
    b = comm_lib.make_buckets(sizes, total=24, bucket_cap_mb=cap_mb(10))
    # greedy whole-leaf packing: 4+4 <= 10 < 4+4+4 -> buckets of two leaves
    assert b == ((0, 8), (8, 16), (16, 24))
    boundaries = set(np.cumsum((0,) + sizes)) | {24}
    for s, e in b:
        assert s in boundaries  # never splits a leaf


def test_oversized_leaf_gets_its_own_bucket():
    # torch DDP's rule: a tensor larger than the cap is never split
    b = comm_lib.make_buckets((100, 4), total=104, bucket_cap_mb=cap_mb(16))
    assert b == ((0, 100), (100, 104))


def test_buckets_deterministic_and_odd_remainders():
    sizes = (7, 3, 11, 1, 5)  # ragged odd sizes, total padded to 32
    a = comm_lib.make_buckets(sizes, 32, bucket_cap_mb=cap_mb(12))
    assert a == comm_lib.make_buckets(sizes, 32, bucket_cap_mb=cap_mb(12))
    assert a[0][0] == 0 and a[-1][1] == 32
    covered = sum(e - s for s, e in a)
    assert covered == 32
    # every bucket holds at least one whole leaf and respects the cap unless
    # it is a single oversized leaf or the padding-absorbing tail
    edges = list(np.cumsum(sizes))
    for s, e in a[:-1]:
        n_leaves = sum(1 for c in edges if s < c <= e)
        assert n_leaves >= 1
        assert (e - s) <= 12 or n_leaves == 1


def test_bucket_cap_validation(cpu_devices):
    with pytest.raises(ValueError, match="bucket_cap_mb"):
        comm_lib.make_buckets((4,), 8, bucket_cap_mb=0)
    with pytest.raises(ValueError, match="comm_hook"):
        comm_lib.validate_hook("fp8")
    mesh = make_mesh(cpu_devices)
    with pytest.raises(ValueError, match="comm_hook"):
        build(mesh, "int8")
    with pytest.raises(ValueError, match="bucket_cap_mb"):
        build(mesh, "bf16", cap=-1.0)
    # both API levels share the knob contract
    from tpuddp.accelerate import Accelerator

    with pytest.raises(ValueError, match="bucket_cap_mb"):
        Accelerator(mesh=mesh, bucket_cap_mb=0)
    with pytest.raises(ValueError, match="comm_hook"):
        Accelerator(mesh=mesh, comm_hook="int8")


def test_make_grad_comm_plan():
    params = {"w": jnp.zeros((13, 7)), "b": jnp.zeros((7,))}
    assert comm_lib.make_grad_comm(params, 8, "none") is None
    plan = comm_lib.make_grad_comm(params, 8, "bf16_ef", bucket_cap_mb=cap_mb(64))
    assert plan.compressed and plan.needs_residual
    assert plan.buckets[0][0] == 0 and plan.buckets[-1][1] == plan.spec.total
    # residual layouts: per-replica (world * total) vs replicated (total)
    assert plan.init_residual(per_replica=True).shape == (8 * plan.spec.total,)
    assert plan.init_residual(per_replica=False).shape == (plan.spec.total,)
    bf16 = comm_lib.make_grad_comm(params, 8, "bf16")
    assert bf16.compressed and not bf16.needs_residual
    assert bf16.init_residual(per_replica=True) is None


# ----------------------------------------------------------- wire accounting


def test_comm_bytes_reduction_at_least_45_percent():
    # any realistic f32 parameter pytree works; sizes chosen so the
    # world-multiple padding is negligible against the leaf sum
    p = {"w1": jnp.zeros((192, 64)), "b1": jnp.zeros((64,)),
         "w2": jnp.zeros((64, 10)), "b2": jnp.zeros((10,))}
    base = comm_lib.comm_bytes_for_hook(p, 8, "none")
    for hook in ("bf16", "bf16_ef"):
        comp = comm_lib.comm_bytes_for_hook(p, 8, hook)
        assert 1 - comp / base >= 0.45, (hook, comp, base)
    wbase = comm_lib.comm_bytes_for_hook(p, 8, "none", wus=True)
    wcomp = comm_lib.comm_bytes_for_hook(p, 8, "bf16_ef", wus=True)
    assert 1 - wcomp / wbase >= 0.45


def test_ddp_counter_property(cpu_devices):
    mesh = make_mesh(cpu_devices)
    ddp = build(mesh, "bf16_ef")
    assert ddp.grad_comm_bytes_per_step is None  # pre-init: no plan yet
    ddp.init_state(KEY, jnp.zeros((1, 8, 8, 3)))
    comp = ddp.grad_comm_bytes_per_step
    base = build(mesh, "none")
    base.init_state(KEY, jnp.zeros((1, 8, 8, 3)))
    assert 1 - comp / base.grad_comm_bytes_per_step >= 0.45


def test_auto_mode_counter_reports_f32_wire(cpu_devices):
    """mode="auto": XLA inserts the psum over f32 values and the hook only
    emulates the quantization — the counter must report the f32 payload, not
    a byte cut that never reached the wire."""
    mesh = make_mesh(cpu_devices)
    comp = build(mesh, "bf16_ef", mode="auto")
    comp.init_state(KEY, jnp.zeros((1, 8, 8, 3)))
    base = build(mesh, "none", mode="auto")
    base.init_state(KEY, jnp.zeros((1, 8, 8, 3)))
    assert comp.grad_comm_bytes_per_step == base.grad_comm_bytes_per_step


def test_comm_bytes_formula_per_hook():
    """Satellite (ISSUE 9): the per-hook wire-byte formula, pinned exactly.
    Sparse/quantized payloads must count EVERY wire part — int8 values, the
    int32 top-k indices, and the per-bucket f32 scale scalars — and
    ``wire=False`` (auto/managed, where the collective stays f32) must keep
    reporting the f32 payload for every hook."""
    p = {"w": jnp.zeros((40, 10)), "b": jnp.zeros((10,))}  # 410 raw elems
    world, cap = 8, cap_mb(4096)  # one bucket: 410 -> padded 416
    spec_total = 416
    base = comm_lib.comm_bytes_for_hook(p, world, "none")
    assert base == 410 * 4  # tree pmean reduces the raw elements
    assert comm_lib.comm_bytes_for_hook(
        p, world, "bf16", bucket_cap_mb=cap
    ) == spec_total * 2
    assert comm_lib.comm_bytes_for_hook(
        p, world, "int8_ef", bucket_cap_mb=cap
    ) == spec_total * 1 + 4  # int8 values + ONE f32 scale (one bucket)
    k = comm_lib.bucket_topk(spec_total, 0.1)
    assert comm_lib.comm_bytes_for_hook(
        p, world, "topk_ef", bucket_cap_mb=cap, density=0.1
    ) == k * (1 + 4) + 4  # int8 values + int32 indices + scale
    # multi-bucket: scales are per bucket — 2 buckets => 2 scale scalars
    from tpuddp.training.step import make_flat_param_spec

    spec = make_flat_param_spec(p, world)
    assert spec.total == spec_total
    two = comm_lib.make_buckets(spec.sizes, spec.total, bucket_cap_mb=cap_mb(401))
    assert len(two) == 2
    sizes = [e - s for s, e in two]
    assert comm_lib.comm_bytes_for_hook(
        p, world, "int8_ef", bucket_cap_mb=cap_mb(401)
    ) == sum(sizes) * 1 + 2 * 4
    assert comm_lib.comm_bytes_for_hook(
        p, world, "topk_ef", bucket_cap_mb=cap_mb(401), density=0.1
    ) == sum(comm_lib.bucket_topk(b, 0.1) for b in sizes) * 5 + 2 * 4
    # wus degenerates to ONE whole-vector bucket for every hook
    assert comm_lib.comm_bytes_for_hook(
        p, world, "int8_ef", wus=True
    ) == spec_total * 1 + 4
    # wire=False: auto/managed reduces f32 whatever the hook emulates
    for hook in ("bf16_ef", "int8_ef", "topk_ef"):
        assert comm_lib.comm_bytes_for_hook(
            p, world, hook, wire=False
        ) == base, hook


def test_comm_bytes_acceptance_cuts():
    """The acceptance floors as counter facts: int8_ef >= 70%, topk_ef at
    density 0.1 >= 85% below the f32 payload on a realistic layout."""
    p = {"w1": jnp.zeros((192, 64)), "b1": jnp.zeros((64,)),
         "w2": jnp.zeros((64, 10)), "b2": jnp.zeros((10,))}
    base = comm_lib.comm_bytes_for_hook(p, 8, "none")
    for hook, floor in (("int8_ef", 0.70), ("topk_ef", 0.85)):
        comp = comm_lib.comm_bytes_for_hook(p, 8, hook, density=0.1)
        assert 1 - comp / base >= floor, (hook, comp, base)


def test_comm_bytes_breakdown_hierarchical():
    """Hierarchical accounting: intra-host = the f32 scatter + gather
    operands, inter-host = the compressed shard payload — and the inter-host
    share must sit below the flat topology's total for every hook."""
    p = {"w": jnp.zeros((100, 10))}
    world, local = 8, 4
    from tpuddp.training.step import make_flat_param_spec

    total = make_flat_param_spec(p, world).total
    shard = total // local
    for hook in ("none", "bf16_ef", "int8_ef", "topk_ef"):
        flat = comm_lib.comm_bytes_breakdown(p, world, hook, topology="flat")
        assert flat["intra_host"] == 0
        assert flat["inter_host"] == flat["total"]
        hier = comm_lib.comm_bytes_breakdown(
            p, world, hook, topology="hierarchical", local_size=local
        )
        assert hier["intra_host"] == total * 4 + shard * 4
        assert hier["inter_host"] < flat["total"], hook
        assert hier["total"] == hier["intra_host"] + hier["inter_host"]
    hier = comm_lib.comm_bytes_breakdown(
        p, world, "int8_ef", topology="hierarchical", local_size=local
    )
    assert hier["inter_host"] == shard * 1 + 4
    with pytest.raises(ValueError, match="local_size"):
        comm_lib.comm_bytes_breakdown(p, world, "int8_ef", topology="hierarchical")
    with pytest.raises(ValueError, match="comm_topology"):
        comm_lib.comm_bytes_breakdown(p, world, "int8_ef", topology="ring")


def test_topk_density_validation(cpu_devices):
    with pytest.raises(ValueError, match="density"):
        comm_lib.bucket_topk(100, 0.0)
    with pytest.raises(ValueError, match="density"):
        comm_lib.bucket_topk(100, 1.5)
    assert comm_lib.bucket_topk(100, 0.1) == 10
    assert comm_lib.bucket_topk(3, 0.1) == 1  # never an empty send
    mesh = make_mesh(cpu_devices)
    with pytest.raises(ValueError, match="density"):
        build(mesh, "topk_ef", topk_density=2.0)
    from tpuddp.accelerate import Accelerator

    with pytest.raises(ValueError, match="density"):
        Accelerator(mesh=mesh, topk_density=0.0)


def test_comm_bytes_counter_class():
    from tpuddp.observability import CommBytesCounter

    c = CommBytesCounter(1000)
    c.add_updates(3)
    c.add_updates(2)
    assert c.total_bytes == 5000
    snap = c.snapshot(epoch_updates=2)
    assert snap["grad_comm_bytes_per_update"] == 1000
    assert snap["grad_comm_bytes_total"] == 5000
    assert snap["grad_comm_bytes_epoch"] == 2000
    # inert counter (pre-init ddp / facade without the attribute): epoch
    # records must stay unchanged
    inert = CommBytesCounter(None)
    inert.add_updates(7)
    assert inert.total_bytes is None and inert.snapshot(7) == {}


# ------------------------------------------------------------- wire dtype --


def _collective_window(ddp, st, batch, op):
    """The text window of the first ``op`` in the LOWERED step program.

    Lowered (StableHLO), not backend-compiled: the byte-reduction contract is
    "the program tpuddp emits requests the gradient collective in the wire
    dtype". Whether the wire then honors it is the backend's legalization —
    TPU ICI carries bf16 collectives natively, while this CPU test world
    upcasts them to f32 at compile time (the quantization numerics survive
    either way; that is what the loss-parity tests pin)."""
    fn = lambda s, b: ddp.train_step(s, b)  # noqa: E731
    txt = jax.jit(fn).lower(st, batch).as_text()
    i = txt.find(op)
    assert i >= 0, f"no {op} in the lowered step program"
    return txt[i : i + 900]


def test_lowered_step_requests_bf16_allreduce(cpu_devices):
    mesh = make_mesh(cpu_devices)
    x, y, w = make_batch()
    for hook, want in (("bf16", True), ("none", False)):
        ddp = build(mesh, hook)
        st = ddp.init_state(KEY, jnp.zeros((1, 8, 8, 3)))
        window = _collective_window(
            ddp, st, ddp.shard((x, y, w)), "stablehlo.all_reduce"
        )
        assert ("xbf16>" in window) == want, (hook, window[:200])


def test_lowered_wus_step_requests_bf16_reduce_scatter(cpu_devices):
    mesh = make_mesh(cpu_devices)
    x, y, w = make_batch()
    ddp = build(mesh, "bf16_ef", wus=True)
    st = ddp.init_state(KEY, jnp.zeros((1, 8, 8, 3)))
    window = _collective_window(
        ddp, st, ddp.shard((x, y, w)), "stablehlo.reduce_scatter"
    )
    assert "xbf16>" in window


# --------------------------------------------------------------- numerics --


def _run_steps(ddp, steps=8, seed=5):
    x, y, w = make_batch(seed=seed)
    st = ddp.init_state(KEY, jnp.zeros((1, 8, 8, 3)))
    m = None
    for _ in range(steps):
        st, m = ddp.train_step(st, ddp.shard((x, y, w)))
    loss = float(np.sum(np.asarray(m["loss_sum"]))) / float(
        np.sum(np.asarray(m["n"]))
    )
    return st, loss


@pytest.mark.parametrize("mode", ["shard_map", "auto"])
@pytest.mark.parametrize("hook", ["bf16", "bf16_ef"])
def test_compressed_training_tracks_f32_loss(cpu_devices, mode, hook):
    mesh = make_mesh(cpu_devices)
    _, base = _run_steps(build(mesh, "none", mode=mode))
    st, comp = _run_steps(build(mesh, hook, mode=mode))
    assert np.isfinite(comp)
    assert abs(comp - base) <= max(0.05, 0.02 * abs(base)), (hook, mode)
    if hook == "bf16_ef":
        res = np.asarray(st.comm_state)
        assert res.dtype == np.float32 and np.any(res != 0)
    else:
        assert st.comm_state is None


def test_bf16_ef_composes_with_wus(cpu_devices):
    mesh = make_mesh(cpu_devices)
    _, base = _run_steps(build(mesh, "none", wus=True))
    st, comp = _run_steps(build(mesh, "bf16_ef", wus=True))
    assert abs(comp - base) <= max(0.05, 0.02 * abs(base))
    assert np.any(np.asarray(st.comm_state) != 0)


def test_bf16_ef_scan_fused_and_accumulation(cpu_devices):
    """The residual threads through the lax.scan carry: K fused steps with
    grad_accumulation=2 stay on the fp32 trajectory and update the
    residual."""
    mesh = make_mesh(cpu_devices)
    x, y, w = make_batch()
    k = 4  # 2 optimizer updates per dispatch at accum=2

    def run(hook):
        ddp = build(mesh, hook, accum=2)
        st = ddp.init_state(KEY, jnp.zeros((1, 8, 8, 3)))
        stacked = ddp.shard_stacked(stack_batches([(x, y, w)] * k))
        m = None
        for _ in range(4):
            st, m = ddp.train_step_many(st, stacked)
        loss = float(np.sum(np.asarray(m["loss_sum"]))) / float(
            np.sum(np.asarray(m["n"]))
        )
        return st, loss

    _, base = run("none")
    st, comp = run("bf16_ef")
    assert np.isfinite(comp)
    assert abs(comp - base) <= max(0.05, 0.02 * abs(base))
    assert np.any(np.asarray(st.comm_state) != 0)


@pytest.mark.parametrize("mode", ["shard_map", "auto"])
@pytest.mark.parametrize("hook", ["int8_ef", "topk_ef"])
def test_quantized_sparse_training_tracks_f32_loss(cpu_devices, mode, hook):
    """Comm compression v2: int8_ef/topk_ef stay within their documented
    parity bound of the uncompressed trajectory (topk_ef compared past its
    ~1/density-update error-feedback warmup) and carry a live residual."""
    steps = 24 if hook == "topk_ef" else 8
    mesh = make_mesh(cpu_devices)
    _, base = _run_steps(build(mesh, "none", mode=mode), steps=steps)
    st, comp = _run_steps(build(mesh, hook, mode=mode), steps=steps)
    assert np.isfinite(comp)
    assert abs(comp - base) <= comm_lib.loss_parity_tol(hook, base), (
        hook, mode, comp, base,
    )
    leaves = jax.tree_util.tree_leaves(st.comm_state)
    assert leaves and any(np.any(np.asarray(l) != 0) for l in leaves)


@pytest.mark.parametrize("hook", ["int8_ef", "topk_ef"])
def test_quantized_sparse_composes_with_wus(cpu_devices, hook):
    steps = 24 if hook == "topk_ef" else 8
    mesh = make_mesh(cpu_devices)
    _, base = _run_steps(build(mesh, "none", wus=True), steps=steps)
    st, comp = _run_steps(build(mesh, hook, wus=True), steps=steps)
    assert abs(comp - base) <= comm_lib.loss_parity_tol(hook, base)
    assert np.any(np.asarray(st.comm_state) != 0)


def test_int8_scan_fused_and_accumulation(cpu_devices):
    """The int8 residual threads through the lax.scan carry exactly like
    bf16_ef's: K fused steps at accum=2 stay on the f32 trajectory."""
    mesh = make_mesh(cpu_devices)
    x, y, w = make_batch()
    k = 4

    def run(hook):
        ddp = build(mesh, hook, accum=2)
        st = ddp.init_state(KEY, jnp.zeros((1, 8, 8, 3)))
        stacked = ddp.shard_stacked(stack_batches([(x, y, w)] * k))
        m = None
        for _ in range(4):
            st, m = ddp.train_step_many(st, stacked)
        loss = float(np.sum(np.asarray(m["loss_sum"]))) / float(
            np.sum(np.asarray(m["n"]))
        )
        return st, loss

    _, base = run("none")
    st, comp = run("int8_ef")
    assert np.isfinite(comp)
    assert abs(comp - base) <= comm_lib.loss_parity_tol("int8_ef", base)
    assert np.any(np.asarray(st.comm_state) != 0)


# ------------------------------- the one step program against a plain worker --

HOOKS = ("none", "bf16_ef", "int8_ef", "topk_ef")
DP = 4  # replicas of the data-parallel side
SPLIT_CAP = cap_mb(600)  # ToyMLP(hidden=(16,))'s two Linears land in buckets of their own


def _plain_worker(params, model_state, dispatches):
    """One worker, no mesh, no wrap, no exchange: every cycle of micro-batches
    is ONE Adam update on the mean gradient of their concatenation, which is
    what data parallelism and accumulation both promise to equal. A dispatch
    is a list of cycles, a cycle a list of ``(x, y, w)`` micro-batches; ``None``
    stands for a dispatch the firewall skips (no update, no loss). Returns the
    mean loss of every dispatch and the final parameters."""
    model, criterion, optimizer = ToyMLP(hidden=(16,)), nn.CrossEntropyLoss(), optim.Adam(1e-2)

    def loss_fn(p, x, y, w):
        logits, _ = model.apply(p, model_state, x, nn.Context(train=True))
        return criterion(logits, y, w)

    @jax.jit
    def update(p, opt_state, x, y, w):
        loss, grads = jax.value_and_grad(loss_fn)(p, x, y, w)
        p, opt_state = optimizer.update(grads, opt_state, p)
        return p, opt_state, loss

    opt_state = optimizer.init(params)
    losses = []
    for cycles in dispatches:
        if cycles is None:
            losses.append(None)
            continue
        total = 0.0
        for micros in cycles:
            x, y, w = (np.concatenate(a) for a in zip(*micros))
            params, opt_state, loss = update(params, opt_state, x, y, w)
            total += float(loss) * len(x)
        losses.append(total / sum(len(m[0]) for c in cycles for m in c))
    return losses, params


@pytest.mark.parametrize("guard", [False, True], ids=["noguard", "guard"])
@pytest.mark.parametrize("accum", [1, 2], ids=["a1", "a2"])
@pytest.mark.parametrize("fused", [1, 4], ids=["k1", "k4"])
@pytest.mark.parametrize("hook", HOOKS)
def test_dp4_step_tracks_single_worker(cpu_devices, hook, fused, accum, guard):
    """The data-parallel step over four replicas, ``fused`` updates a dispatch
    and ``accum`` micro-batches an update, against the plain single worker:
    the uncompressed exchange to float32 rounding at every dispatch and in
    the final parameters, a compressed hook within the bound this file holds
    it to. Under the guard a poisoned dispatch in the middle changes nothing
    (parameters and the error-feedback residual are the ones before it, bit
    for bit) and training goes on from there along the worker's trajectory."""
    ddp = build(make_mesh(cpu_devices[:DP]), hook, accum=accum, cap=SPLIT_CAP, guard=guard)
    state = ddp.init_state(KEY, jnp.zeros((1, 8, 8, 3)))
    params0 = jax.device_get(state.params)
    model_state0 = jax.device_get(state.model_state)

    n_dispatches = 12 if hook == "topk_ef" else 5
    seeds = iter(range(100, 100 + n_dispatches * fused * accum))
    dispatches = [
        [[make_batch(seed=next(seeds)) for _ in range(accum)] for _ in range(fused)]
        for _ in range(n_dispatches)
    ]
    poisoned_at = 2 if guard else None

    def dispatch(state, cycles):
        micros = [m for cycle in cycles for m in cycle]
        if len(micros) == 1:
            state, m = ddp.train_step(state, ddp.shard(micros[0]))
        else:
            state, m = ddp.train_step_many(state, ddp.shard_stacked(stack_batches(micros)))
        m = jax.device_get(m)
        return state, float(np.sum(m["loss_sum"]) / np.sum(m["n"]))

    losses, plan = [], []
    for i, cycles in enumerate(dispatches):
        if i == poisoned_at:
            before = jax.device_get((state.params, state.opt_state, state.comm_state))
            bad = [[(np.full_like(x, np.nan), y, w) for x, y, w in c] for c in cycles]
            state, _ = dispatch(state, bad)
            after = jax.device_get((state.params, state.opt_state, state.comm_state))
            for a, b in zip(jax.tree_util.tree_leaves(after), jax.tree_util.tree_leaves(before)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            assert guard_lib.read_skip_counters(state) == (fused, fused)
            plan.append(None)
            losses.append(None)
        state, loss = dispatch(state, cycles)
        plan.append(cycles)
        losses.append(loss)

    want, want_params = _plain_worker(params0, model_state0, plan)
    residual = state.comm_state
    if hook == "none":
        assert residual is None
        for got, ref in zip(losses, want):
            assert (got is None) == (ref is None)
            if ref is not None:
                assert got == pytest.approx(ref, rel=2e-5)
        for a, b in zip(
            jax.tree_util.tree_leaves(jax.device_get(state.params)),
            jax.tree_util.tree_leaves(want_params),
        ):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-6)
    else:
        assert np.isfinite(losses[-1])
        assert abs(losses[-1] - want[-1]) <= comm_lib.loss_parity_tol(hook, want[-1]), (
            losses, want,
        )
        assert np.any(np.asarray(residual) != 0)  # error feedback is live


_ITEMSIZE = {"f32": 4, "bf16": 2, "i8": 1, "i32": 4}


def _collective_operand_bytes(text):
    """Bytes entering every cross-replica collective of a lowered (StableHLO)
    program, from each operation's own operand types."""
    total = 0
    for op in re.finditer(
        r"stablehlo\.(all_reduce|all_gather|reduce_scatter|all_to_all|collective_permute)", text
    ):
        operands = re.search(r":\s*\(([^)]*)\)\s*->", text[op.end():]).group(1)
        for shape in re.findall(r"tensor<([^>]*)>", operands):
            *dims, dtype = shape.split("x")
            total += int(np.prod([int(d) for d in dims], dtype=np.int64)) * _ITEMSIZE[dtype]
    return total


@pytest.mark.parametrize("hook", HOOKS)
def test_wire_bytes_match_lowered_program(cpu_devices, hook):
    """``grad_comm_bytes_per_step`` (what ``grad_wire_mb_per_step`` and the
    history's byte counters report) is the operand bytes of the collectives
    in the step program itself: values, scales and indices, buckets and
    padding included; the gradient exchange is the step's only collective."""
    for cap in (SPLIT_CAP, 25):
        ddp = build(make_mesh(cpu_devices[:DP]), hook, cap=cap)
        state = ddp.init_state(KEY, jnp.zeros((1, 8, 8, 3)))
        text = jax.jit(ddp.train_step).lower(state, ddp.shard(make_batch())).as_text()
        assert _collective_operand_bytes(text) == ddp.grad_comm_bytes_per_step, (hook, cap)


# ------------------------------------------------- hierarchical topology --


def hier_build(cpu_devices, hook, **kw):
    from tpuddp.parallel.mesh import hierarchical_mesh

    mesh = hierarchical_mesh(devices=cpu_devices)
    return build(mesh, hook, comm_topology="hierarchical", **kw)


def test_hierarchical_none_matches_flat_pmean(cpu_devices):
    """hook "none" under the hierarchical topology is pure re-bracketing
    (f32 scatter -> f32 psum -> gather): same trajectory as the flat pmean
    up to summation order."""
    mesh = make_mesh(cpu_devices)
    _, base = _run_steps(build(mesh, "none"))
    _, hier = _run_steps(hier_build(cpu_devices, "none"))
    np.testing.assert_allclose(hier, base, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("hook", ["bf16_ef", "int8_ef", "topk_ef"])
def test_hierarchical_compressed_tracks_f32(cpu_devices, hook):
    steps = 24 if hook == "topk_ef" else 8
    mesh = make_mesh(cpu_devices)
    _, base = _run_steps(build(mesh, "none"), steps=steps)
    st, comp = _run_steps(hier_build(cpu_devices, hook), steps=steps)
    assert np.isfinite(comp)
    assert abs(comp - base) <= comm_lib.loss_parity_tol(hook, base), (
        hook, comp, base,
    )
    # the residual is per-replica sharded state, live after training
    assert np.any(np.asarray(st.comm_state) != 0)


def test_hierarchical_inter_host_bytes_below_flat(cpu_devices):
    """The topology's acceptance contract: for every hook, the compressed
    inter-host payload is strictly below the flat topology's total."""
    mesh = make_mesh(cpu_devices)
    for hook in ("none", "bf16_ef", "int8_ef", "topk_ef"):
        flat = build(mesh, hook)
        flat.init_state(KEY, jnp.zeros((1, 8, 8, 3)))
        hier = hier_build(cpu_devices, hook)
        hier.init_state(KEY, jnp.zeros((1, 8, 8, 3)))
        assert hier.grad_comm_bytes_inter_host < flat.grad_comm_bytes_per_step
        assert hier.grad_comm_bytes_intra_host > 0
        assert flat.grad_comm_bytes_intra_host == 0


def test_hierarchical_refuses_bad_compositions(cpu_devices):
    mesh = make_mesh(cpu_devices)
    with pytest.raises(ValueError, match="hierarchical"):
        build(mesh, "int8_ef", comm_topology="hierarchical")  # 1-D mesh
    with pytest.raises(ValueError, match="mutually exclusive"):
        hier_build(cpu_devices, "int8_ef", wus=True)
    with pytest.raises(ValueError, match="shard_map"):
        hier_build(cpu_devices, "int8_ef", mode="auto")
    with pytest.raises(ValueError, match="comm_topology"):
        build(mesh, "int8_ef", comm_topology="ring")
    from tpuddp.accelerate import Accelerator

    with pytest.raises(ValueError, match="explicit"):
        Accelerator(mesh=mesh, comm_topology="hierarchical")
    from tpuddp.parallel.mesh import hierarchical_mesh

    with pytest.raises(ValueError, match="factorable"):
        hierarchical_mesh(devices=cpu_devices[:3])


def test_lowered_step_requests_int8_allgather(cpu_devices):
    """The explicit int8 step's lowered program carries the compressed
    payload as the collective operand: an i8-element all-gather."""
    mesh = make_mesh(cpu_devices)
    x, y, w = make_batch()
    ddp = build(mesh, "int8_ef")
    st = ddp.init_state(KEY, jnp.zeros((1, 8, 8, 3)))
    window = _collective_window(
        ddp, st, ddp.shard((x, y, w)), "stablehlo.all_gather"
    )
    assert "xi8>" in window, window[:300]


def test_local_quantize_error_feedback_conserves():
    """The managed emulation's invariant: quantized + new_residual == grads +
    old_residual exactly (both sides are the same f32 subtraction)."""
    g = {"w": jnp.asarray(np.random.RandomState(0).randn(32).astype(np.float32))}
    r = comm_lib.init_residual_tree(g)
    q, r1 = comm_lib.local_quantize(g, r, "bf16_ef")
    np.testing.assert_array_equal(
        np.asarray(q["w"] + r1["w"]), np.asarray(g["w"] + r["w"])
    )
    # and the quantized value really is bf16-representable
    qw = np.asarray(q["w"])
    np.testing.assert_array_equal(
        qw, qw.astype(jnp.bfloat16).astype(np.float32)
    )
    # hook "none" is the identity; "bf16" carries no residual
    g2, r2 = comm_lib.local_quantize(g, None, "none")
    assert g2 is g and r2 is None
    q3, r3 = comm_lib.local_quantize(g, None, "bf16")
    assert r3 is None and np.any(np.asarray(q3["w"]) != np.asarray(g["w"]))


@pytest.mark.parametrize("hook", ["int8_ef", "topk_ef"])
def test_local_quantize_int8_topk_conserves(hook):
    """The managed emulation of the quantized/sparse hooks keeps the EF
    invariant exactly (quantized + residual == send, both sides the same
    f32 subtraction), produces genuinely int8-representable values, and —
    for topk — keeps at most ceil(density * n) nonzeros per leaf."""
    vals = np.random.RandomState(0).randn(64).astype(np.float32)
    g = {"w": jnp.asarray(vals)}
    r = comm_lib.init_residual_tree(g)
    q, r1 = comm_lib.local_quantize(g, r, hook, density=0.25)
    np.testing.assert_array_equal(
        np.asarray(q["w"] + r1["w"]), np.asarray(g["w"] + r["w"])
    )
    qw = np.asarray(q["w"])
    scale = np.abs(vals).max() / 127.0
    codes = qw / scale
    np.testing.assert_allclose(codes, np.round(codes), atol=1e-4)
    assert np.abs(codes).max() <= 127.5
    if hook == "topk_ef":
        k = comm_lib.bucket_topk(64, 0.25)
        assert np.count_nonzero(qw) <= k
        # what it kept really is the top-|.| slice of the send
        kept_idx = np.nonzero(qw)[0]
        thresh = np.sort(np.abs(vals))[-k]
        assert np.all(np.abs(vals[kept_idx]) >= thresh - 1e-6)


# ------------------------------------------------------------ checkpoints --


@pytest.mark.parametrize("hook", ["bf16_ef", "int8_ef", "topk_ef"])
def test_native_residual_checkpoint_roundtrip(cpu_devices, tmp_path, hook):
    """Every EF hook's residual is training state: nonzero after steps,
    lossless across the native checkpoint, trains on after restore (scales
    are recomputed per step — never checkpointed, so nothing else rides)."""
    mesh = make_mesh(cpu_devices)
    x, y, w = make_batch()
    ddp = build(mesh, hook)
    st = ddp.init_state(KEY, jnp.zeros((1, 8, 8, 3)))
    for _ in range(3):
        st, _ = ddp.train_step(st, ddp.shard((x, y, w)))
    res = np.asarray(st.comm_state)
    assert np.any(res != 0)
    path = ckpt.save(str(tmp_path / "ckpt_1.npz"), st)
    # a fresh same-shape state is the load template (the loop's resume path)
    ddp2 = build(mesh, hook)
    st2 = ddp2.init_state(KEY, jnp.zeros((1, 8, 8, 3)))
    restored = ckpt.load(path, st2)
    np.testing.assert_array_equal(np.asarray(restored.comm_state), res)
    # and the restored state trains on (placement re-established by the jit)
    st3, m = ddp2.train_step(restored, ddp2.shard((x, y, w)))
    assert np.isfinite(float(np.sum(np.asarray(m["loss_sum"]))))
    assert np.any(np.asarray(st3.comm_state) != res)


def test_hookless_checkpoint_structure_unchanged(cpu_devices, tmp_path):
    """comm_state=None must not appear as a checkpoint leaf: hook-less
    checkpoints keep their historical structure (old checkpoints stay
    loadable, new hook-less ones stay loadable by old code)."""
    mesh = make_mesh(cpu_devices)
    ddp = build(mesh, "none")
    st = ddp.init_state(KEY, jnp.zeros((1, 8, 8, 3)))
    path = ckpt.save(str(tmp_path / "ckpt_1.npz"), st)
    with np.load(path) as data:
        assert not any("comm_state" in k for k in data.files)


def test_pre_hook_checkpoint_loads_into_ef_template(cpu_devices, tmp_path):
    """Turning comm_hook="bf16_ef" ON over checkpoints from a hook-less run
    must resume, not crash: the missing residual leaf keeps the template's
    zero initialization (exactly a fresh compressed run's starting state)."""
    mesh = make_mesh(cpu_devices)
    ddp = build(mesh, "none")
    st = ddp.init_state(KEY, jnp.zeros((1, 8, 8, 3)))
    path = ckpt.save(str(tmp_path / "ckpt_1.npz"), st)  # no comm_state leaf
    ef = build(mesh, "bf16_ef")
    st2 = ef.init_state(KEY, jnp.zeros((1, 8, 8, 3)))
    restored = ckpt.load(path, st2)
    assert not np.any(np.asarray(restored.comm_state))
    x, y, w = make_batch()
    st3, m = ef.train_step(restored, ef.shard((x, y, w)))
    assert np.isfinite(float(np.sum(np.asarray(m["loss_sum"]))))
    assert np.any(np.asarray(st3.comm_state) != 0)


def test_managed_residual_roundtrip(cpu_devices, tmp_path):
    from tpuddp.accelerate import Accelerator

    mesh = make_mesh(cpu_devices)
    x, y, w = make_batch(n=32)
    criterion = nn.CrossEntropyLoss()

    def steps(acc, model, opt, n):
        last = None
        for _ in range(n):
            opt.zero_grad()
            loss = criterion(model(x), y, w)
            acc.backward(loss)
            opt.step()
            last = loss.item()
        return last

    acc = Accelerator(mesh=mesh, seed=3, comm_hook="bf16_ef")
    model, opt = acc.prepare(ToyMLP(hidden=(16,)), optim.Adam(1e-2))
    steps(acc, model, opt, 3)
    assert opt._comm_state is not None
    res = jax.tree_util.tree_map(np.asarray, opt._comm_state)
    assert any(np.any(l != 0) for l in jax.tree_util.tree_leaves(res))
    assert opt.grad_comm_bytes_per_step is not None
    acc.save_state(model, opt, str(tmp_path), epoch=1)
    cont = steps(acc, model, opt, 2)  # the run we must be able to reproduce

    acc2 = Accelerator(mesh=mesh, seed=3, comm_hook="bf16_ef")
    model2, opt2 = acc2.prepare(ToyMLP(hidden=(16,)), optim.Adam(1e-2))
    model2(x[:1])  # materialize structure to load into
    assert acc2.load_state(model2, opt2, str(tmp_path)) == 2
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), b),
        opt2._comm_state, res,
    )
    resumed = steps(acc2, model2, opt2, 2)
    np.testing.assert_allclose(resumed, cont, rtol=0, atol=1e-6)
