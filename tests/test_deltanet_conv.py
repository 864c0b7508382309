"""The DeltaNet mixer's short convolution (``nn/deltanet.py:short_conv``): the
fused kernel pair (``nn/deltanet_conv_kernels.py``) against the plain path and
against the plain path in float32, on the CPU in Pallas's interpreter (the
tests pass ``interpret=True`` themselves), and the rule that chooses between
the two. The kernels compiled for the chip, alone and inside the token cell's
whole step: ``tests/test_hybrid_moe.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from benchmark import cells
from tpuddp.models import load_model
from tpuddp.nn import deltanet, deltanet_conv_kernels
from tpuddp.nn import sequence as seq

_T, _HK, _HV, _D, _TAPS = 1024, 4, 8, 128, 4  # two row tiles, three channel tiles (queries, keys, two of values)
_KW = _HK * _D
_C = 2 * _KW + _HV * _D
_SCALE, _EPS = _D ** -0.5, 1e-6
_OUTS = ("q", "k", "v")


def _inputs(dtype=jnp.bfloat16, t=_T, taps=_TAPS, seed=0):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(1, t, _C), dtype)
    w = jnp.asarray(rng.uniform(-1, 1, (taps, _C)) / np.sqrt(taps), jnp.float32)  # as the model draws them
    probes = [jnp.asarray(rng.randn(1, t, n), jnp.float32) for n in (_KW, _KW, _C - 2 * _KW)]
    return x, w, probes


def _fused(x, w):
    return deltanet_conv_kernels.short_conv(x, w, _KW, _D, _SCALE, _EPS, True)


def _plain(x, w):
    return deltanet._plain_short_conv(x, w, _KW, _D, _SCALE, _EPS)


def _exact(x, w):
    """The plain path on float32 rows: nothing is rounded."""
    return _plain(x.astype(jnp.float32), w)


def _loss(f, probes):
    return lambda x, w: sum(jnp.sum(o.astype(jnp.float32) * p) for o, p in zip(f(x, w), probes))


@pytest.fixture(scope="module")
def results():
    """Outputs and the two gradients of the three, once a row type."""
    done = {}

    def of(dtype):
        key = str(jnp.dtype(dtype))
        if key not in done:
            x, w, probes = _inputs(dtype)
            done[key] = {}
            for name, f in (("fused", _fused), ("plain", _plain), ("exact", _exact)):
                rows = x.astype(jnp.float32) if name == "exact" else x
                done[key][name] = (jax.jit(f)(x, w), jax.jit(jax.grad(_loss(f, probes), argnums=(0, 1)))(rows, w))
        return done[key]

    return of


def _f32(a):
    return np.asarray(a, np.float32)


def _err(a, b):
    return float(np.linalg.norm(_f32(a) - _f32(b)) / np.linalg.norm(_f32(b)))


@pytest.mark.parametrize("out", _OUTS)
def test_forward_is_within_two_ulps_of_the_plain_path(results, out):
    """The plain path rounds after the convolution, twice in the activation,
    after the norm and after the scale; the kernel once. Half an ulp each:
    two ulps hold all but a few elements in a thousand (measured: 99.6% of
    ``q``, 99.9% of ``k`` and ``v``) and every element is within four (an ulp
    halves where a value crosses a power of two). Types and shapes are the
    plain path's."""
    got = results(jnp.bfloat16)
    a, b = (got[name][0][_OUTS.index(out)] for name in ("fused", "plain"))
    assert a.shape == b.shape and a.dtype == b.dtype == jnp.bfloat16
    a, b = _f32(a), _f32(b)
    ulp = 2.0 ** (np.floor(np.log2(np.maximum(np.maximum(np.abs(a), np.abs(b)), 1e-30))) - 7)
    assert np.mean(np.abs(a - b) <= 2 * ulp) >= 0.995 and np.all(np.abs(a - b) <= 4 * ulp) and np.any(a != 0)


@pytest.mark.parametrize("out", _OUTS)
def test_forward_is_no_further_from_float32_than_the_plain_path(results, out):
    got = results(jnp.bfloat16)
    fused, plain, exact = (got[name][0][_OUTS.index(out)] for name in ("fused", "plain", "exact"))
    assert _err(fused, exact) <= _err(plain, exact)
    assert np.abs(_f32(fused) - _f32(exact)).max() <= np.abs(_f32(plain) - _f32(exact)).max()
    assert _err(fused, exact) <= 2.0 ** -8  # one rounding to bfloat16


@pytest.mark.parametrize("what", ["rows", "taps"])
def test_gradients_agree_with_float32(results, what):
    """The rows' cotangent is summed over the taps in float32 and rounded
    once, where the plain path rounds each tap's term: closer to float32 than
    the plain path. The taps' gradient is float32 in both."""
    got = results(jnp.bfloat16)
    index = ["rows", "taps"].index(what)
    fused, plain, exact = (got[name][1][index] for name in ("fused", "plain", "exact"))
    assert fused.shape == plain.shape and fused.dtype == plain.dtype
    assert _err(fused, exact) <= (2.0 ** -8 if what == "rows" else 4e-3)
    if what == "rows":
        assert _err(fused, exact) < 0.75 * _err(plain, exact)
    else:
        assert _err(fused, exact) <= 1.1 * _err(plain, exact)


@pytest.mark.parametrize("what", ["q", "k", "v", "rows", "taps"])
def test_on_float32_rows_the_kernels_are_the_plain_path(results, what):
    """Nothing to round: the two differ by the order of float32 sums and by
    how SiLU is written."""
    got = results(jnp.float32)
    pick = (lambda r: r[0][_OUTS.index(what)]) if what in _OUTS else (lambda r: r[1][["rows", "taps"].index(what)])
    a, b = pick(got["fused"]), pick(got["plain"])
    assert a.dtype == b.dtype == jnp.float32
    np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)


def test_the_first_rows_see_zeros_before_the_sequence():
    """Row 0 is the last tap times its own row; rows before the sequence add
    nothing, whatever the block before the first tile would hold."""
    x, w, _ = _inputs(jnp.float32)
    q, k, v = _fused(x, w)
    a = jax.nn.silu(x[:, 0] * w[_TAPS - 1])
    np.testing.assert_allclose(v[:, 0], a[:, 2 * _KW:], rtol=1e-5, atol=1e-6)
    k0 = a[:, _KW: 2 * _KW].reshape(-1, _D)
    np.testing.assert_allclose(
        k[:, 0].reshape(-1, _D), k0 / np.sqrt(np.sum(np.square(k0), -1, keepdims=True) + _EPS), rtol=1e-5, atol=1e-6
    )
    moved = _fused(x.at[:, _TAPS - 1:].add(1.0), w)  # rows 3 and later cannot reach rows 0 to 2 ...
    for got, want in zip(moved, (q, k, v)):
        np.testing.assert_array_equal(got[:, :_TAPS - 1], want[:, :_TAPS - 1])
    assert not np.allclose(moved[2][:, _TAPS - 1], v[:, _TAPS - 1])  # ... and do reach row 3


@pytest.mark.parametrize("t", [512, 1536])
def test_a_tile_boundary_is_no_seam(t):
    """One, and three row tiles against one call of the plain path, forward
    and both gradients: the rows on either side of a boundary read and feed
    each other, and the sequence's last rows get no cotangent from past its
    end."""
    x, w, probes = _inputs(jnp.float32, t=t, seed=t)
    for a, b in zip(jax.jit(_fused)(x, w), jax.jit(_plain)(x, w)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)
    fused, plain = (jax.jit(jax.grad(_loss(f, probes), argnums=(0, 1)))(x, w) for f in (_fused, _plain))
    for a, b in zip(fused, plain):
        np.testing.assert_allclose(a, b, rtol=3e-4, atol=3e-4 * float(jnp.abs(b).max()))
    edge = slice(512 - 8, 512 + 8) if t > 512 else slice(t - 8, t)
    np.testing.assert_allclose(fused[0][:, edge], plain[0][:, edge], rtol=3e-4, atol=1e-5)


@pytest.mark.parametrize("taps", [1, 2, 8])
def test_other_tap_counts(taps):
    x, w, probes = _inputs(jnp.float32, t=512, taps=taps, seed=taps)
    fused, plain = (jax.jit(jax.value_and_grad(_loss(f, probes), argnums=(0, 1)))(x, w) for f in (_fused, _plain))
    np.testing.assert_allclose(fused[0], plain[0], rtol=1e-4)
    for a, b in zip(fused[1], plain[1]):
        np.testing.assert_allclose(a, b, rtol=3e-4, atol=3e-4 * float(jnp.abs(b).max()))


def test_several_sequences_add_their_taps_gradients():
    """The taps' gradient is one resident block over every sequence and row
    tile of a channel tile."""
    x, w, probes = _inputs(jnp.float32, t=512)
    both = jnp.concatenate([x, x[:, ::-1]])
    twice = [jnp.concatenate([p, 0.5 * p]) for p in probes]
    fused, plain = (jax.jit(jax.grad(_loss(f, twice), argnums=(0, 1)))(both, w) for f in (_fused, _plain))
    for a, b in zip(fused, plain):
        np.testing.assert_allclose(a, b, rtol=3e-4, atol=3e-4 * float(jnp.abs(b).max()))


_CELL = dict(backend="tpu", channels=8192, key_width=2048, head_dim=128, taps=4, t=8192, per_replica=True)


@pytest.mark.parametrize("change,want", [
    ({}, "fused"),                                    # the published widths at the cell's length
    ({"t": 512}, "fused"),                            # one row tile
    ({"channels": 1024, "key_width": 256, "head_dim": 256}, "fused"),  # heads of two lane registers
    ({"channels": 768, "key_width": 128}, "fused"),   # one head a tile: 128 divides 128 and 512
    ({"taps": 8}, "fused"),
    ({"backend": "cpu"}, "plain"),
    ({"backend": "gpu"}, "plain"),
    ({"per_replica": False}, "plain"),                # mode="auto": GSPMD cannot partition a custom call
    ({"channels": 64, "key_width": 32, "head_dim": 16, "t": 24}, "plain"),  # the tiny preset
    ({"head_dim": 16}, "plain"),
    ({"head_dim": 192}, "plain"),
    ({"t": 8000}, "plain"),                           # no whole row tile
    ({"t": 8192 + 64}, "plain"),
    ({"t": 0}, "plain"),
    ({"taps": 9}, "plain"),                           # reaches past the 8 rows a tile brings of its neighbour
    ({"channels": 4096}, "plain"),                    # no value columns
    ({"channels": 8192 + 64}, "plain"),               # value columns that are no whole heads
])
def test_conv_lowering_rule(change, want):
    args = {**_CELL, **change}
    per_replica = args.pop("per_replica")
    assert deltanet.conv_lowering(*args.values(), per_replica=per_replica) == want


@pytest.mark.parametrize("channels,key_width,head_dim,tile", [
    (8192, 2048, 128, 512), (1024, 256, 256, 256), (768, 128, 128, 128), (6144, 1536, 128, 512),
    (2304, 768, 128, 384), (8192, 2048, 1024, None), (8192, 2048, 64, None),
])
def test_channel_tiles_are_whole_heads_that_divide_keys_and_values(channels, key_width, head_dim, tile):
    assert deltanet_conv_kernels.channel_tile(channels, key_width, head_dim) == tile


def test_several_devices_under_jit_take_the_plain_path(monkeypatch):
    """``mode="auto"``: a TPU process of eight devices, traced outside
    ``shard_map``, does not reach the kernels; inside it does."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(deltanet_conv_kernels, "short_conv", lambda x, *a: "fused")
    monkeypatch.setattr(deltanet, "_plain_short_conv", lambda x, *a: "plain")
    x, w, _ = _inputs(jnp.float32, t=512)
    call = lambda x, w: deltanet.short_conv(x, w, key_width=_KW, head_dim=_D, q_scale=_SCALE)
    assert len(jax.devices()) > 1 and call(x, w) == "plain"
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    inside = []
    jax.jit(jax.shard_map(
        lambda x, w: inside.append(call(x, w)) or x, mesh=mesh, in_specs=(P("data"), P()), out_specs=P("data"),
        check_vma=False,
    ))(x, w)
    assert inside == ["fused"]


def test_the_tiny_preset_and_the_cpu_stay_on_the_plain_path(monkeypatch):
    """Whatever the model and the widths, a CPU run never reaches the kernels."""
    monkeypatch.setattr(deltanet_conv_kernels, "short_conv", lambda *a, **k: pytest.fail("the kernels on the CPU"))
    system = cells.load_module("systems", "token_moe_lm")
    tiny = system.shrunk(cells.load_cell("qwen3next_ep16_t8k_fused").config)
    model = load_model(
        tiny["model"]["registry_name"], tiny["vocab_size"], **{**system.model_kwargs(tiny), "compute_dtype": "float32"}
    )
    params, _ = model.init(jax.random.key(0), jnp.zeros((1, 48), jnp.int32))
    mix = jax.jit(model._deltanet)
    assert mix(params["layers"][0]["mixer"], jnp.ones((1, 48, tiny["hidden_size"]))).shape == (1, 48, tiny["hidden_size"])
    x, w, _ = _inputs(t=512)  # and at the published widths
    q, k, v = jax.jit(lambda x, w: deltanet.short_conv(x, w, key_width=_KW, head_dim=_D, q_scale=_SCALE))(x, w)
    assert q.shape == k.shape == (1, 512, _KW) and v.shape == (1, 512, _C - 2 * _KW)


def test_the_plain_path_is_the_mixers_old_expression():
    """What ``_deltanet`` wrote out before the function existed, to the bit,
    forward and gradients, in both row types."""
    def old(qkv, taps):
        b, t = qkv.shape[:2]
        qkv = jax.nn.silu(seq.causal_conv1d(qkv, taps))
        q = qkv[..., :_KW].reshape(b, t, _HK, _D)
        k = qkv[..., _KW: 2 * _KW].reshape(b, t, _HK, _D)
        v = qkv[..., 2 * _KW:].reshape(b, t, _HV, _D)
        q = seq.l2_normalise(q) * (_D ** -0.5)
        k = seq.l2_normalise(k)
        return q.reshape(b, t, -1), k.reshape(b, t, -1), v.reshape(b, t, -1)

    for dtype in (jnp.bfloat16, jnp.float32):
        x, w, probes = _inputs(dtype, t=96)
        for a, b in zip(jax.jit(_plain)(x, w), jax.jit(old)(x, w)):
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(_f32(a), _f32(b))
        for a, b in zip(*(jax.jit(jax.grad(_loss(f, probes), argnums=(0, 1)))(x, w) for f in (_plain, old))):
            np.testing.assert_array_equal(_f32(a), _f32(b))


def test_the_conv_kernels_run_inside_the_wraps_shard_map():
    """The pair inside ``shard_map`` over the data axis and inside ``lax.map``
    over a device's sequences, rematerialised, as the step traces a DeltaNet
    layer: each device convolves its own sequences and the taps' gradients
    of all of them add up."""
    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
    x, w, probes = _inputs(jnp.float32, t=512)
    rows = jnp.concatenate([x, x[:, ::-1], 2 * x, x[:, ::-1] / 2])
    one = jax.checkpoint(lambda row, w: _fused(row[None], w))

    def loss(rows, w):
        outs = jax.lax.map(lambda row: one(row, w), rows)
        return sum(jnp.sum(jnp.sin(o) * p) for o, p in zip(outs, probes))

    grad = jax.grad(loss, argnums=(0, 1))
    wrapped = jax.jit(jax.shard_map(
        lambda rows, w: (lambda g: (g[0], jax.lax.psum(g[1], "data")))(grad(rows, w)),
        mesh=mesh, in_specs=(P("data"), P()), out_specs=(P("data"), P()), check_vma=False,
    ))
    for got, want in zip(wrapped(rows, w), grad(rows, w)):
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
