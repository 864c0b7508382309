"""The DeltaNet scan's fused lowering (nn/deltanet_kernels.py for what a chunk
computes alone, nn/deltanet_carry_kernels.py for the carry over chunks)
against the plain path of ``nn/deltanet.py`` at chunk 64 and widths of 128, on
the CPU in Pallas's interpreter (the tests pass ``interpret=True``
themselves), and the rule that chooses between the two. The kernels compiled
for the chip, alone and inside the token cell's whole step:
``tests/test_hybrid_moe.py``."""

import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from benchmark import cells
from tpuddp.models import load_model
from tpuddp import nn as nn_package
from tpuddp.nn import deltanet, deltanet_carry_kernels, deltanet_kernels
from tpuddp.nn.deltanet import chunk_gated_delta_rule

_SCAN_T, _SCAN_HK, _SCAN_HV, _SCAN_D = 1024, 2, 4, 128  # two grid steps a head, two value heads a key head
_NAMES = ["out", "dq", "dk", "dv", "dg", "dbeta"]  # what a lowering is compared on: the output and five gradients


def _scan_inputs(
    dtype=jnp.float32, t=_SCAN_T, identical_keys=False, seed=0, b=1, hk=_SCAN_HK, hv=_SCAN_HV, log_decay=(-7, 1.5)
):
    """Unit keys and queries, decays from nearly none (-1e-3 a token) to
    nearly all (-4.5), ``beta`` across (0, 1). At those decays a chunk of 64
    tokens leaves e^-34 of the state it found: the carry's tests draw theirs
    from ``log_decay`` (-9, -3), where a chunk leaves about 0.6 of it."""
    rng = np.random.RandomState(seed)
    unit = lambda a: a / np.linalg.norm(a, axis=-1, keepdims=True)
    q = unit(rng.randn(b, t, hk, _SCAN_D)) * _SCAN_D ** -0.5
    k = unit(rng.randn(b, t, hk, _SCAN_D))
    g = -np.exp(rng.uniform(*log_decay, (b, t, hv)))
    beta = rng.uniform(0.02, 0.98, (b, t, hv))
    if identical_keys:  # every entry below the inverse's diagonal is 1
        k, g, beta = np.broadcast_to(k[:, :1], k.shape), 0 * g, 0 * beta + 1
    v, probe = rng.randn(2, b, t, hv, _SCAN_D)
    as_ = lambda a, dt: jnp.asarray(a, dt)
    return (as_(q, dtype), as_(k, dtype), as_(v, dtype), as_(g, jnp.float32), as_(beta, jnp.float32)), as_(probe, jnp.float32)


def _scan(fused, compute_dtype):
    return lambda *a: deltanet._chunked_rule(
        *a, chunk=64, compute_dtype=compute_dtype, fused=fused, interpret=True
    )


@pytest.fixture(scope="module")
def scan_pairs():
    """Forward and the five gradients of both lowerings, once a case."""
    done = {}

    def pair(dtype, identical_keys=False):
        key = (str(dtype), identical_keys)
        if key not in done:
            args, probe = _scan_inputs(jnp.dtype(dtype), identical_keys=identical_keys)
            done[key] = {}
            for fused in (True, False):
                f = _scan(fused, jnp.dtype(dtype))
                loss = lambda *a: jnp.sum(f(*a).astype(jnp.float32) * probe)
                done[key][fused] = (f(*args), *jax.jit(jax.grad(loss, argnums=range(5)))(*args))
        return done[key]

    return pair


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-4), ("bfloat16", 2 ** -7)])
@pytest.mark.parametrize("what", _NAMES)
def test_fused_scan_agrees_with_the_plain_path(scan_pairs, dtype, tol, what):
    """The kernel pair against the plain path at chunk 64 and widths of 128,
    each key head serving two value heads: float32 to float32's tolerance;
    with bfloat16 product inputs to bfloat16's rounding (the two round the
    forward's products at the same points; the backward kernel also rounds
    the cotangents it multiplies, as a TPU's default precision does)."""
    got = scan_pairs(dtype)
    index = _NAMES.index(what)
    a, b = (np.asarray(got[fused][index], np.float32) for fused in (True, False))
    assert a.shape == b.shape and got[True][index].dtype == got[False][index].dtype
    assert np.linalg.norm(a - b) <= tol * np.linalg.norm(b) and np.linalg.norm(b) > 0


@pytest.mark.parametrize("what", ["out", "dk", "dv"])
def test_fused_scan_with_identical_keys(scan_pairs, what):
    """The inverse's worst case (``beta`` 1, no decay, one key): the kernel's
    substitution and joins stay as exact as the plain path's."""
    got = scan_pairs("float32", identical_keys=True)
    index = _NAMES.index(what)
    a, b = (np.asarray(got[fused][index]) for fused in (True, False))
    assert np.isfinite(a).all() and np.linalg.norm(a - b) <= 2e-4 * np.linalg.norm(b)


def test_shared_key_heads_are_the_repeated_ones():
    """Value head ``h`` reads key head ``h // 2``: both lowerings give what
    keys and queries repeated in memory give, and the shared head's gradient
    is the sum of its value heads'."""
    args, probe = _scan_inputs(t=512)
    repeated = tuple(jnp.repeat(a, _SCAN_HV // _SCAN_HK, axis=2) for a in args[:2]) + args[2:]
    for fused in (True, False):
        loss = lambda *a: jnp.sum(_scan(fused, jnp.float32)(*a) * probe)
        both = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))
        (value, shared), (value_apart, apart) = both(*args), both(*repeated)
        np.testing.assert_allclose(value, value_apart, rtol=1e-5)
        for a, b in zip(shared, apart):
            summed = b.reshape(*a.shape[:3], -1, a.shape[-1]).sum(axis=3)
            np.testing.assert_allclose(a, summed, rtol=1e-4, atol=1e-6)


_CARRY_CASES = {  # b, t, hk, hv, products' input type, tolerance
    # 16 key / 32 value heads of 128, one grid step of the chunk-local kernels
    "the_cells_heads": (1, 512, 16, 32, "float32", 2e-4),
    "a_padded_tail": (1, 1000, 2, 4, "float32", 2e-4),  # 24 tokens that must leave the state as it is
    "two_sequences": (2, 512, 2, 4, "float32", 2e-4),
    "a_refused_length": (1, 500, 2, 4, "float32", 2e-4),  # no whole grid steps: the rule's answer is the loop
    # both round the state and V' where they enter a product; the kernel rounds a cotangent there too
    "bfloat16_products": (1, 1024, 2, 4, "bfloat16", 2 ** -7),
}


def _carry_pair(case, monkeypatch):
    """Output and the five input gradients, the kernels' and the loop's. A
    shape the rule refuses goes through the rule itself, on a TPU that is
    claimed and with the carry's module out of reach: it has to take the loop
    without importing the kernels."""
    b, t, hk, hv, dtype, _ = _CARRY_CASES[case]
    args, probe = _scan_inputs(jnp.dtype(dtype), t=t, b=b, hk=hk, hv=hv, seed=3, log_decay=(-9, -3))
    plain = _scan(False, jnp.dtype(dtype))
    if case == "a_refused_length":
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(deltanet, "traced_per_replica", lambda: True)
        monkeypatch.delattr(nn_package, "deltanet_carry_kernels")
        monkeypatch.setitem(sys.modules, "tpuddp.nn.deltanet_carry_kernels", None)  # an import now raises
        fused = lambda *a: chunk_gated_delta_rule(*a, chunk=64)
    else:
        fused = _scan(True, jnp.dtype(dtype))
    both = []
    for f in (fused, plain):
        loss = lambda *a, f=f: jnp.sum(f(*a).astype(jnp.float32) * probe)
        both.append((f(*args), *jax.grad(loss, argnums=range(5))(*args)))
    return both


def _assert_carry_agrees(got, want, tol=2e-4, but=()):
    for name, a, b in zip(_NAMES, got, want):
        if name in but:
            continue
        assert a.shape == b.shape and a.dtype == b.dtype, name
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.linalg.norm(a - b) <= tol * np.linalg.norm(b) and np.linalg.norm(b) > 0, name


@pytest.mark.parametrize("case", list(_CARRY_CASES))
def test_the_carry_kernels_agree_with_the_loop(case, monkeypatch):
    """``deltanet_carry_fwd`` / ``deltanet_carry_bwd`` (with the chunk-local
    pair before them, as ``fused=True`` runs them) against the ``lax.scan``
    carry and its two batched products: the output rows and all five input
    gradients, to the tolerances the chunk-local kernels' tests hold
    (float32's, and with bfloat16 product inputs bfloat16's rounding). The
    state crosses grid steps, sequences and the heads a grid step takes side
    by side; a padded tail leaves it alone."""
    _assert_carry_agrees(*_carry_pair(case, monkeypatch), tol=_CARRY_CASES[case][-1])


def test_a_dropped_decay_cotangent_fails_that_comparison(monkeypatch):
    """The planted fault: the backward kernel leaves the decay's cotangent
    out (it reaches ``A_log`` and ``dt_bias`` through ``g``). The comparison
    above then fails, on ``dg`` and nothing else."""
    real = deltanet_carry_kernels._backward_kernel

    def faulty(*refs, **static):
        real(*refs, **static)
        refs[13][...] = jnp.zeros_like(refs[13])  # dg_ref

    monkeypatch.setattr(deltanet_carry_kernels, "_backward_kernel", faulty)
    got, want = _carry_pair("a_padded_tail", monkeypatch)
    with pytest.raises(AssertionError, match="dg"):
        _assert_carry_agrees(got, want)
    _assert_carry_agrees(got, want, but=("dg",))


@pytest.mark.parametrize("chunk,dk,dv,hv,block,itemsize,want", [
    (64, 128, 128, 32, 8, 2, (4, 4)),    # the token cell's: 16 chunk-heads a grid step
    (64, 128, 128, 32, 8, 4, (2, 4)),    # float32 products: half as many fit
    (64, 128, 128, 2, 8, 2, (4, 2)),     # no more heads than there are
    (64, 128, 128, 6, 8, 2, (4, 3)),     # a divisor of the heads
    (64, 256, 256, 32, 8, 2, (2, 4)),
    (64, 512, 512, 32, 8, 4, (1, 2)),
    (64, 1024, 1024, 32, 8, 4, None),    # one chunk of one head beside its state does not fit
])
def test_the_carrys_grid_step_fits_vmem(chunk, dk, dv, hv, block, itemsize, want):
    assert deltanet_carry_kernels.tile(chunk, dk, dv, hv, block, itemsize, itemsize) == want


@pytest.mark.parametrize("backend,chunk,dk,dv,t,per_replica,want", [
    ("tpu", 64, 128, 128, 8192, True, "fused"),   # the published widths at the cell's length
    ("tpu", 64, 128, 256, 512, True, "fused"),    # one grid step; a wider value head
    ("tpu", 64, 128, 128, 8192, False, "plain"),  # mode="auto": GSPMD cannot partition a custom call
    ("cpu", 64, 128, 128, 8192, True, "plain"),
    ("gpu", 64, 128, 128, 8192, True, "plain"),
    ("tpu", 16, 16, 16, 24, True, "plain"),       # the tiny preset
    ("tpu", 16, 128, 128, 8192, True, "plain"),   # another chunk
    ("tpu", 128, 128, 128, 8192, True, "plain"),
    ("tpu", 64, 64, 128, 8192, True, "plain"),    # half a lane register of keys
    ("tpu", 64, 128, 192, 8192, True, "plain"),
    ("tpu", 64, 128, 128, 8000, True, "plain"),   # ragged lengths
    ("tpu", 64, 128, 128, 8192 + 64, True, "plain"),
    ("tpu", 64, 128, 128, 0, True, "plain"),
    ("tpu", 64, 512, 512, 8192, True, "fused"),   # the carry's state beside one chunk's blocks fits VMEM
    ("tpu", 64, 1024, 1024, 8192, True, "plain"),  # and here it does not
])
def test_scan_lowering_rule(backend, chunk, dk, dv, t, per_replica, want):
    assert deltanet.scan_lowering(backend, chunk, dk, dv, t, per_replica=per_replica) == want


def test_several_devices_under_jit_take_the_plain_scan(monkeypatch):
    """``mode="auto"``: a TPU process of eight devices, traced outside
    ``shard_map``, does not reach the kernels."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(deltanet, "_chunked_rule", lambda *a, fused, **k: fused)
    args, _ = _scan_inputs(t=512)
    assert len(jax.devices()) > 1 and chunk_gated_delta_rule(*args, chunk=64) is False
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    inside = []
    jax.jit(jax.shard_map(
        lambda *a: inside.append(chunk_gated_delta_rule(*a, chunk=64)) or a[2],
        mesh=mesh, in_specs=P("data"), out_specs=P("data"), check_vma=False,
    ))(*args)
    assert inside == [True]


def test_the_tiny_preset_and_the_cpu_stay_on_the_plain_scan(monkeypatch):
    """Whatever the model and the widths, a CPU run never reaches the kernels."""
    monkeypatch.setattr(deltanet_kernels, "chunk_local", lambda *a, **k: pytest.fail("the kernels on the CPU"))
    monkeypatch.setattr(deltanet_carry_kernels, "carry", lambda *a, **k: pytest.fail("the carry's kernels on the CPU"))
    system = cells.load_module("systems", "token_moe_lm")
    tiny = system.shrunk(cells.load_cell("qwen3next_ep16_t8k_fused").config)
    model = load_model(
        tiny["model"]["registry_name"], tiny["vocab_size"], **{**system.model_kwargs(tiny), "compute_dtype": "float32"}
    )
    params, _ = model.init(jax.random.key(0), jnp.zeros((1, 48), jnp.int32))
    mix = jax.jit(model._deltanet)
    assert mix(params["layers"][0]["mixer"], jnp.ones((1, 48, tiny["hidden_size"]))).shape == (1, 48, tiny["hidden_size"])
    args, _ = _scan_inputs(t=512)  # and at the published widths
    assert jax.jit(lambda *a: chunk_gated_delta_rule(*a, chunk=64))(*args).shape == args[2].shape


def test_the_scan_kernels_run_inside_the_wraps_shard_map():
    """The pair inside ``shard_map`` over the data axis and inside ``lax.map``
    over a device's sequences, rematerialised, as the step traces a DeltaNet
    layer: each device scans its own sequences."""
    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
    (q, k, v, g, beta), probe = _scan_inputs(t=512)
    args = tuple(jnp.concatenate([a, a[:, ::-1], 2 * a, a[:, ::-1] / 2]) for a in (q, k, v, g, beta))
    one = jax.checkpoint(lambda *a: _scan(True, jnp.float32)(*(x[None] for x in a))[0])

    def loss(*a):
        return jnp.sum(jnp.sin(jax.lax.map(lambda row: one(*row), a)))

    grad = jax.grad(loss, argnums=range(5))
    wrapped = jax.jit(jax.shard_map(grad, mesh=mesh, in_specs=P("data"), out_specs=P("data"), check_vma=False))
    for got, want in zip(wrapped(*args), grad(*args)):
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
