"""The looped cell at its shrunk size on the CPU, the planted faults of its
comparison with the reference, and its seven readers on hand-made events that
carry the scope names only a looped stack opens."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cells, check, run, trace_reduce
from benchmark.tests import tiny
from benchmark.tests.test_trace_reduce import _host, _meta, _op
from tpuddp.parallel import make_mesh

WORKLOAD, CONFIG = "ouro_loop4_t16k_fused", "ouro_2_6b_loop4"
SCOPE_READERS = (
    "looped_attention_ms_per_step", "looped_attention_roofline_pct", "looped_ffn_ms_per_step",
    "exit_head_ms_per_step", "exit_head_roofline_pct", "loop_recompute_ms_per_step",
)
COUNTER_READERS = ("loop_mean_exit_step",)
PRE = "jit(multi)/while/body/closed_call/"
FWD = PRE + "jvp(tpuddp.forward)/passes/while/body/checkpoint/"
BWD = PRE + "transpose(jvp(tpuddp.forward))/passes/while/body/checkpoint/"
REMAT = BWD + "rematted_computation/"
LOSS, LOSS_BWD = PRE + "jvp(tpuddp.loss)/exits/", PRE + "transpose(jvp(tpuddp.loss))/exits/"
SPLASH = "vmap(jit(_splash_attention))/splash_mha_fwd_residuals/splash_mha_fwd_residuals/pallas_call:"


@pytest.fixture
def root(tmp_path, monkeypatch):
    tiny.fake_devices(monkeypatch, run)
    from tpuddp.utils import compile_cache

    monkeypatch.setattr(compile_cache, "enable", lambda: "")
    return tiny.make_root(tmp_path)


def _said(capfd):
    return [json.loads(l) for l in capfd.readouterr().err.splitlines() if l.startswith('{"workload"')][-1]


@pytest.mark.parametrize("seed", [2600000501, 2147483659])
def test_the_shrunk_cell_is_correct_and_counts_its_exits(root, capfd, seed):
    """Untraced, on seeds above 2**31 as the driver's are: ``correct``, no
    program lowered in the window, tokens counted, and the exits' counters
    summed over the window by the cell's feed: the masses add up to the
    window's tokens."""
    cell = cells.load_cell(WORKLOAD, root)
    assert cell.config["sample_unit"] == "token" and cell.traffic["feed"] == "resident_counted"
    assert cell.config["system"] == "token_looped_lm"
    result = run.run_cell(WORKLOAD, seed=seed, seconds=0.5, trace=False, root=root)
    assert result["correct"] is True and result["failed"] == 0
    said = _said(capfd)
    tokens = cell.traffic["batch_per_chip"] * cell.config["tokens"]["seq_len"]
    assert said["samples"] == result["attempted"] * tokens
    assert said["compiles_in_window"] == 0
    assert said["reference"]["loss_rel_err"] < 2e-3 and said["reference"]["update_norm_rel_err"] < 2e-2
    counters, passes = said["counters"], cell.config["total_ut_steps"]
    masses = [counters[f"loop_exit_mass_{t}"] for t in range(1, passes + 1)]
    assert sum(masses) == pytest.approx(said["samples"], rel=1e-5) and min(masses) > 0
    assert all(counters[f"loop_exit_loss_{t}"] > 0 for t in range(1, passes + 1))


def test_a_program_without_the_model_fails_the_cell_at_once(root, monkeypatch):
    """The parent commit under this benchmark: its registry has no such model,
    so the cell ends in a ``BenchmarkError`` (``run.py``: exit 2, no result
    line) before a state is made or a program compiled."""
    import tpuddp.models as zoo

    monkeypatch.setattr(zoo, "_REGISTRY", {k: v for k, v in zoo._REGISTRY.items() if not k.startswith("ouro")})
    with pytest.raises(cells.BenchmarkError, match="cannot build 'ouro_tiny'"):
        run.run_cell(WORKLOAD, seed=1, seconds=0.1, trace=False, root=root)


def test_a_trunk_without_the_new_arguments_fails_the_cell_at_once(root, monkeypatch):
    """A registry that knows the name but whose trunk takes none of this
    model's arguments (a ``TypeError`` at construction) ends the same way."""
    import tpuddp.models as zoo

    monkeypatch.setitem(zoo._REGISTRY, "ouro_tiny", lambda num_classes, hidden_size: None)
    with pytest.raises(cells.BenchmarkError, match="cannot build 'ouro_tiny'"):
        run.run_cell(WORKLOAD, seed=1, seconds=0.1, trace=False, root=root)


def test_the_traced_line_reports_the_counter_reader(root, monkeypatch):
    """A CPU capture has no device plane, so the recorded AlexNet capture
    stands in: the scope readers find no looped layer in it and report nothing
    (as they do on a program without the scopes); the counter's reader reads
    the window's own counters, and the readers without a list of cells report
    as they do everywhere."""
    from benchmark.tests.test_harness import RECORDED

    monkeypatch.setattr(
        trace_reduce, "capture_events", lambda trace_dir: trace_reduce.load_events(RECORDED)
    )
    result = run.run_cell(WORKLOAD, seed=3000000011, seconds=5, trace=True, root=root)
    assert result["correct"] is True
    assert {"compile_s", "device_ms_per_step", "device_mfu_pct", *COUNTER_READERS} <= set(result["metrics"])
    assert 1.0 < result["metrics"]["loop_mean_exit_step"]["value"] < 4.0
    assert not set(SCOPE_READERS) & set(result["metrics"])
    assert not {"moe_load_imbalance", "full_attention_ms_per_step", "dense_ffn_ms_per_step"} & set(result["metrics"])


# -- the planted faults, through the harness's own comparison -------------------------------

class _SeededBatches:
    """What ``check.against_reference`` asks of a feed."""

    def __init__(self, system, config, seed):
        self.system, self.config, self.seed = system, config, seed

    def sample_batches(self, n, batch):
        tokens, targets = self.system.make_batches(self.config, self.seed, n, batch)
        return [(np.asarray(tokens[i]), np.asarray(targets[i])) for i in range(n)]


def _nearest_e4m3(a):
    """``float8_e4m3fn``'s value nearest to ``a`` (ties to even) in float32
    arithmetic, for a device that compiles no 8-bit type: 3 bits of mantissa,
    least normal 2^-6, below it steps of 2^-9, largest 448 (tests/
    test_window_moe_training.py holds this expression to the type's own)."""
    _, exponent = jnp.frexp(a)  # |a| in [2^(exponent - 1), 2^exponent)
    step = jax.lax.bitcast_convert_type(((jnp.maximum(exponent - 1, -6) - 3 + 127) << 23).astype(jnp.int32), jnp.float32)
    return jnp.clip(jnp.round(a / step) * step, -448.0, 448.0)


@jax.custom_vjp
def e4m3(a):
    """A product's operand rounded to 8 bits, and its cotangent too."""
    return _nearest_e4m3(a)


e4m3.defvjp(lambda a: (_nearest_e4m3(a), None), lambda _, d: (_nearest_e4m3(d),))


def plant(fault: str, monkeypatch) -> None:
    """Plant one of the program's four faults (``benchmark/configs``: the
    configuration's ``check.reason`` has what each reads at the cell's size)."""
    from tpuddp.models.hybrid_moe import HybridMoELM
    from tpuddp.nn import sequence as seq

    if fault == "three_passes":  # (a) three passes for four
        real = HybridMoELM.__init__
        monkeypatch.setattr(HybridMoELM, "__init__", lambda self, *a, **kw: real(self, *a, **{**kw, "loop_steps": 3}))
    elif fault == "unnormed_carry":  # (b) the un-normed state fed to the next pass
        def _pass(self, params, state, h, ctx):
            def walk(params, h):
                s = self._layers(params, state, h, ctx)[0]
                return s, self._norm(s, params["final_norm"])
            return (jax.checkpoint(walk) if ctx.train else walk)(params, h)
        monkeypatch.setattr(HybridMoELM, "_pass", _pass)
    elif fault == "uniform_exits":  # (c) the gate out of the loss
        def uniform(gate_logits):
            log_p = jnp.full(gate_logits.shape, -jnp.log(gate_logits.shape[0]), jnp.float32)
            return log_p, jnp.exp(log_p)
        monkeypatch.setattr(seq, "exit_distribution", uniform)
    elif fault == "mass_lost":  # (d) the last exit takes lambda_R of what is left, not all of it
        real = seq.exit_distribution
        def lossy(gate_logits):
            log_p = real(gate_logits)[0].at[-1].add(jax.nn.log_sigmoid(gate_logits[-1]))
            return log_p, jnp.exp(log_p)
        monkeypatch.setattr(seq, "exit_distribution", lossy)
    else:
        raise ValueError(fault)


@pytest.fixture(scope="module")
def published():
    return cells.load_cell(WORKLOAD).config


@pytest.fixture(scope="module")
def system():
    return cells.load_module("systems", "token_looped_lm")


def _held_to_the_files_limits(system, published, seed, **over):
    shrunk = system.shrunk(published)
    limits = {k: published["check"][k] for k in ("loss_rtol", "update_norm_rtol")}
    config = {**shrunk, **over, "check": {**shrunk["check"], "batch": 4, **limits}}
    cell = cells.Cell(
        name="t", chips=1, config_name=CONFIG, config=config, traffic_name="t",
        traffic={"ddp": {}}, end_to_end=(), per_layer=(), root=cells.ROOT,
    )
    mesh = make_mesh(jax.devices()[:1], {"data": 1})
    return check.against_reference(cell, mesh, seed, _SeededBatches(system, config, seed)), limits


@pytest.mark.parametrize("seed", [11, 12, 2600000501])
def test_the_sound_program_passes_the_configurations_limits(system, published, seed):
    got, limits = _held_to_the_files_limits(system, published, seed)
    assert got["ok"] is True
    assert got["loss_rel_err"] < limits["loss_rtol"] / 2 and got["update_norm_rel_err"] < limits["update_norm_rtol"] / 3


@pytest.mark.parametrize("fault", ["three_passes", "unnormed_carry", "uniform_exits", "mass_lost"])
@pytest.mark.parametrize("seed", [11, 2600000501])
def test_a_planted_fault_fails_the_configurations_limits(system, published, fault, seed, monkeypatch):
    """Each of the program's four planted faults is not ``correct`` under the
    limits the configuration's file states, with room. At the cell's size
    both limits read every one of them from the second step on (the
    configuration's ``check.reason`` has the readings). At this size a fourth
    pass over three 64-wide layers of random weights moves the loss by 5e-4
    (8.7e-3 at the cell's size) and the update's norm by 1.6e-3 to 2.7e-3, at
    the limits and not beyond them: three passes for four is held to ten
    times (five asserted) the sound program's own loss error at the seed."""
    sound = _held_to_the_files_limits(system, published, seed)[0] if fault == "three_passes" else None
    plant(fault, monkeypatch)
    got, limits = _held_to_the_files_limits(system, published, seed)
    if sound is not None:
        assert sound["ok"] is True and got["loss_rel_err"] > 5 * sound["loss_rel_err"], (got, sound)
        return
    assert got["ok"] is False
    assert max(got["loss_rel_err"] / limits["loss_rtol"],
               got["update_norm_rel_err"] / limits["update_norm_rtol"]) > 1.5, got


@pytest.mark.parametrize("seed", [11, 12])
def test_the_reference_in_8_bit_operands_fails_the_configurations_limits(system, published, seed, monkeypatch):
    """(e) The next precision down, read through the reference side (no 8-bit
    type, no program): the reference with every product's operands and their
    cotangents rounded to ``float8_e4m3fn``, put in the program's place and
    held against the reference as it is by the comparison's own measure."""
    from tpuddp.models import load_model

    reference = cells.load_module("reference", CONFIG)
    config = system.shrunk(published)
    model = load_model(config["model"]["registry_name"], config["vocab_size"], **system.model_kwargs(config))
    init = jax.device_get(system.init_variables(model, config, seed))
    batches = _SeededBatches(system, config, seed).sample_batches(3, 4)
    plain = reference.train_steps(config, *init, batches)
    monkeypatch.setattr(reference, "_operand", e4m3)
    rounded = reference.train_steps(config, *init, batches)
    worst = lambda ours, theirs: max(abs(a - b) / abs(b) for a, b in zip(ours, theirs))
    limits = published["check"]
    assert max(worst(rounded[0], plain[0]) / limits["loss_rtol"],
               worst(rounded[1], plain[1]) / limits["update_norm_rtol"]) > 1.5


# -- the readers ---------------------------------------------------------------------------

def _run_with(events, steps=2, tokens=32768, counters=None, workload=WORKLOAD):
    cell = cells.load_cell(workload)
    return {
        "cell": cell, "trace": {"planes": []}, "events": events, "setup": {},
        "window": {"steps": steps, "samples": tokens, "counters": counters or {}},
        "spans": {"seconds": {}, "counts": {}}, "counters": {},
        "flops_per_sample": 1.0, "peaks": cells.load_peaks("TPU v5e"),
    }


def _events():
    us = 1000.0  # one millisecond
    ops = [
        ("f1", FWD + "0_FullAttention/while/body/checkpoint/qkv/dot_general:", 4),
        ("f2", FWD + "0_FullAttention/while/body/checkpoint/attention/" + SPLASH, 7),
        ("f3", FWD + "5_FullAttention/while/body/checkpoint/o_proj/dot_general:", 2),
        ("f4", FWD + "5_FullAttention/while/body/checkpoint/mul:", 1),  # the mixer's norms: no part's scope
        ("f5", FWD + "3_FullAttention/while/body/checkpoint/mlp/dot_general:", 9),
        ("f6", REMAT + "3_FullAttention/while/body/checkpoint/mlp/dot_general:", 9),  # the pass walked again
        ("f7", REMAT + "3_FullAttention/while/body/checkpoint/rematted_computation/mlp/dot_general:", 9),
        ("f8", REMAT + "1_FullAttention/while/body/checkpoint/rematted_computation/attention/" + SPLASH, 7),
        ("f9", REMAT + "1_FullAttention/while/body/checkpoint/attention/transpose:", 15),  # rematted: the walk's
        ("f10", BWD + "2_FullAttention/while/body/qkv/dot_general:", 6),
        ("f11", BWD + "2_FullAttention/while/body/mlp/dot_general:", 12),
        ("f12", FWD + "mul:", 3),  # the pass's own norm: no layer's
        ("e1", LOSS + "while/body/checkpoint/dot_general:", 20),
        ("e2", LOSS + "gate/mul:", 1),
        ("e3", LOSS_BWD + "while/body/checkpoint/rematted_computation/dot_general:", 20),
        ("e4", LOSS_BWD + "while/body/checkpoint/dot_general:", 40),
        ("o1", PRE + "tpuddp.optimizer/mul:", 10),
        ("o2", PRE + "jvp(tpuddp.loss)/while/body/checkpoint/dot_general:", 50),  # a loss with no exits
        ("o3", PRE + "jvp(tpuddp.forward)/3_FullAttention/while/body/checkpoint/attention/dot_general:", 50),  # no loop
        ("o4", PRE + "jvp(tpuddp.forward)/0_ShortConv/while/body/checkpoint/mlp/dot_general:", 50),
    ]
    events, ts = _meta() + [_host("bench:window", 0, 400 * us)], 0.0
    for name, tf_op, ms in ops:
        events.append(_op(name, ts, ms * us, tf_op=tf_op))
        ts += ms * us
    return events


def test_the_readers_on_the_looped_stacks_names():
    """Device time by part under ``passes``, forward, backward and
    recomputation together, and under the loss phase's ``exits``; a roofline
    share is the need's least time over that; another model's layers of the
    same name, under no ``passes``, are not read."""
    counters = {"loop_exit_mass_1": 16384.0, "loop_exit_mass_2": 8192.0, "loop_exit_mass_3": 4096.0,
                "loop_exit_mass_4": 4096.0, "loop_exit_loss_1": 1.0}
    run_ = _run_with(_events(), counters=counters)
    read = lambda name: cells.load_module("layer_metrics", name).read(run_)
    assert read("looped_attention_ms_per_step") == pytest.approx((4 + 7 + 2 + 7 + 15 + 6) / 2)
    assert read("looped_ffn_ms_per_step") == pytest.approx((9 + 9 + 9 + 12) / 2)
    assert read("exit_head_ms_per_step") == pytest.approx((20 + 1 + 20 + 40) / 2)
    assert read("loop_recompute_ms_per_step") == pytest.approx((9 + 9 + 7 + 15) / 2)
    assert read("loop_mean_exit_step") == pytest.approx(1.875)
    cfg, peaks = run_["cell"].config, run_["peaks"]
    flops = cells.load_module("flops", CONFIG)
    ops, nbytes = flops.attention_cost(cfg, 32768)
    assert ops / peaks["bf16_flops_per_s"] > nbytes / peaks["hbm_bytes_per_s"]  # a compute bound
    assert read("looped_attention_roofline_pct") == pytest.approx(100 * 24 * ops / peaks["bf16_flops_per_s"] / 29e-3)
    ops, nbytes = flops.exit_head_cost(cfg, 32768)
    assert ops / peaks["bf16_flops_per_s"] > nbytes / peaks["hbm_bytes_per_s"]
    assert read("exit_head_roofline_pct") == pytest.approx(100 * ops / peaks["bf16_flops_per_s"] / 81e-3)


def test_the_readers_find_nothing_in_a_program_without_the_scopes():
    """The parent's program, another token cell's or an image cell's: scopes,
    but no ``passes`` and no ``exits``. Every scope reader returns nothing and
    none raises; the counter's reader returns nothing where the step carries
    no such counters out. On an empty capture the same."""
    events = _meta() + [
        _host("bench:window", 0, 1000),
        _op("f1", 0, 500, tf_op=PRE + "jvp(tpuddp.forward)/3_Conv2d/conv_general_dilated:"),
        _op("f2", 500, 200, tf_op=PRE + "jvp(tpuddp.forward)/3_FullAttention/while/body/checkpoint/attention/dot_general:"),
        _op("f3", 700, 100, tf_op=PRE + "jvp(tpuddp.loss)/while/body/checkpoint/dot_general:"),
        _op("f4", 800, 100, tf_op=PRE + "tpuddp.optimizer/mul:"),
    ]
    for run_ in (_run_with(events, counters={"moe_expert_tokens_held": 100.0}), {**_run_with([]), "trace": None}):
        for name in SCOPE_READERS + COUNTER_READERS:
            assert cells.load_module("layer_metrics", name).read(run_) is None, name


def test_analytic_counts_of_the_published_cut():
    """2,441,134,080 multiply-accumulates a token: four passes of six layer
    applications of 84,936,704 (the projections 16,777,216, scores and values
    33,556,480 over 8,192.5 keys a query at 16,384 tokens, the SwiGLU
    34,603,008) and of the head's 100,663,296; 509,661,185 parameters."""
    cfg = cells.load_cell(WORKLOAD).config
    flops = cells.load_module("flops", CONFIG)
    per_product = [m for m, _ in flops.products(cfg)]
    assert len(per_product) == 4 * (6 * 6 + 1) and flops.layer_applications(cfg) == 24
    assert per_product[:6] == [2048 * 2048] * 3 + [4096 * 16385 // 2, 2048 * 2048, 3 * 2048 * 5632]
    assert sum(per_product[:6]) == 84_936_704 and per_product[36] == 2048 * 49152 == 100_663_296
    assert sum(per_product) == 2_441_134_080
    assert flops.train_flops_per_sample(cfg) == 6.0 * 2_441_134_080
    ops, nbytes = flops.attention_cost(cfg, 16384)
    assert ops == 6.0 * 33_556_480 * 16384 and nbytes == 3 * 16384 * 128 * 64 * 2
    ops, nbytes = flops.exit_head_cost(cfg, 16384)
    assert ops == 6.0 * 4 * 16384 * 100_663_296
    assert nbytes == 100_663_296 * 10 + 4 * 16384 * 2048 * 6
    assert cfg["parameters"] == 509_661_185 == 6 * 51_388_416 + 2 * 100_663_296 + 2048 + 2049
    assert cfg["reduced"] == ["num_hidden_layers"] and cfg["published"] == {"num_hidden_layers": 48}
