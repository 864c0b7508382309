"""Benchmark: samples/sec/chip on the reference workload (BASELINE.json metric).

Configs measured (BASELINE.md targets):
- toy MLP, per-chip batch 128, scan-fused (the BASELINE.json headline) -> stdout
- toy MLP per-step dispatch (quantifies the per-dispatch penalty)
- AlexNet-class 224x224: f32 per-step, f32 + bf16 scan-fused
- ResNet-18 @ native 32x32 with sync-BN, bf16 scan-fused (plus the same row
  under the bf16_ef compressed comm hook — the grad_comm_bytes_per_step pair
  records the gradient-byte reduction as a measured artifact)
- the Bottleneck/VGG halves of the zoo: VGG-11 and ResNet-50 @ 224 (bf16,
  scan-fused, device-MFU recorded like every row); ResNet-101 @ 224 only
  under ``--slow`` / ``$TPUDDP_BENCH_SLOW=1``
- managed (Accelerator) toy MLP: eager per-batch sync (reference-parity mode)
  and deferred-metrics mode

All runs are the FULL DP train step (device-side uint8 augmentation for the
CNNs, forward, backward, grad pmean, Adam update, on-device metrics), matching
the reference hot loop (multi-GPU-training-torch.py:109-132) with per-chip
batch 128 / Adam lr=1e-3 / cross-entropy.

Per config the JSON reports measured MFU: FLOPs are taken from XLA's compiled
cost analysis of the exact program being timed (so fwd+bwd+optimizer+augment,
not a hand model), divided by wall time and the chip's bf16 peak.

Timing methodology: steps are dispatched as an async dependency chain and the
clock stops on a *value fetch* from the final step's metrics, which fences
the device on every runtime. Single-step configs measure dispatch rate, NOT
chip compute — that is exactly what the scan-fused variants exist to show
(see BASELINE.md). Configs compared against each other (native per-step vs
managed) time the SAME number of steps per fetch — otherwise the comparison
measures fence amortization, not the paths.

``vs_baseline``: the reference publishes no numbers (BASELINE.md), so the
baseline is measured here: the same toy-MLP workload through the reference's
stack (torch + Adam + per-batch loss.item(), its quirk Q5 sync included) on
this host's available torch device (CPU — the reference's CUDA path needs
NVIDIA hardware that does not exist on a TPU host).

Output contract (driver-parseable): the FULL results dict is written to
``bench_results.json`` next to this script, and the LAST stdout line is one
compact machine-readable JSON summary (headline metric/value/unit,
vs_baseline, device, config count, results path). Everything else —
per-config lines, warnings, failures — goes to stderr. The big-model tail
(ResNet-101 @ 224) runs only under ``--slow`` / ``$TPUDDP_BENCH_SLOW=1``.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

# Peak bf16 MXU FLOP/s per chip by device kind — ONE table for the bench and
# the training-loop telemetry (tpuddp/observability/recorder.py). MFU is
# always reported against the bf16 peak: on TPU, f32 matmuls execute on the
# MXU with bf16 multiplies by default, so bf16 peak is the one ceiling.
from tpuddp.observability import PEAK_FLOPS  # noqa: E402

RESULTS = {}  # name -> {samples_per_sec_per_chip, ms_per_step, mfu}


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def _program_flops(jitted, *args):
    """FLOPs of one execution of ``jitted(*args)`` from XLA cost analysis
    (compiled if available, HLO estimate otherwise); None when unsupported."""
    try:
        lowered = jitted.lower(*args)
        try:
            cost = lowered.compile().cost_analysis()
        except Exception:
            cost = lowered.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        flops = float(cost.get("flops", 0.0))
        return flops if flops > 0 else None
    except Exception as e:
        log(f"  cost_analysis unavailable ({type(e).__name__}: {e})")
        return None


def _peak_flops():
    import jax

    kind = jax.devices()[0].device_kind
    return PEAK_FLOPS.get(kind), kind


def _record(name, sps_per_chip, ms_per_step, flops_per_chip_step, extra=None):
    peak, _ = _peak_flops()
    mfu = None
    if flops_per_chip_step and peak:
        mfu = flops_per_chip_step / (ms_per_step / 1e3) / peak
    RESULTS[name] = {
        "samples_per_sec_per_chip": round(sps_per_chip, 1),
        "ms_per_step": round(ms_per_step, 3),
        "mfu": round(mfu, 4) if mfu is not None else None,
    }
    if extra:
        RESULTS[name].update(extra)
    # async-pipeline columns on EVERY row (tpuddp/training/pipeline.py):
    # wall/device ratio and host-stall percentiles. Rows that pre-stage their
    # buffers have no host loader, so their stall is a structural 0; rows
    # without a device-time estimate carry null rather than a guess.
    for k in ("wall_to_device_ratio", "host_stall_ms_p50", "host_stall_ms_p95"):
        RESULTS[name].setdefault(k, None)
    mfu_s = f", MFU {mfu * 100:.1f}%" if mfu is not None else ""
    w2d = RESULTS[name]["wall_to_device_ratio"]
    w2d_s = f", wall/device {w2d:.2f}" if w2d is not None else ""
    log(f"{name}: {sps_per_chip:,.0f} samples/s/chip, {ms_per_step:.2f} ms/step{mfu_s}{w2d_s}")


def _make_runner(ddp, state_box, batch, scan, laps=None):
    """Build run(n_steps) over pre-staged device buffers. Warmup calls must
    reuse the SAME buffers that are timed later, so that no buffer's
    upload lands inside the timed region.

    ``laps`` (a list) collects one wall-clock lap per dispatch — the raw
    material for the per-row step-time percentiles. The laps are taken
    WITHOUT per-dispatch fences (the timing-honesty contract above forbids
    extra fences inside the timed region), so they measure dispatch
    resolution; under device backpressure they converge to execution time,
    and the row's mean (fenced once, at the fetch) remains the headline."""
    from tpuddp.training.step import stack_batches

    if scan > 1:
        stacked = ddp.shard_stacked(
            stack_batches([tuple(np.asarray(b) for b in batch)] * scan)
        )

        def run(steps):
            outer = max(1, steps // scan)
            metrics = None
            t_prev = time.perf_counter()
            for _ in range(outer):
                state_box[0], metrics = ddp.train_step_many(state_box[0], stacked)
                if laps is not None:
                    t_now = time.perf_counter()
                    laps.append((t_now - t_prev) / scan)
                    t_prev = t_now
            loss_sum = float(np.sum(np.asarray(metrics["loss_sum"])))  # fence
            assert np.isfinite(loss_sum)
            return outer * scan

    else:

        def run(steps):
            metrics = None
            t_prev = time.perf_counter()
            for _ in range(steps):
                state_box[0], metrics = ddp.train_step(state_box[0], batch)
                if laps is not None:
                    t_now = time.perf_counter()
                    laps.append(t_now - t_prev)
                    t_prev = t_now
            loss_sum = float(np.sum(np.asarray(metrics["loss_sum"])))
            assert np.isfinite(loss_sum)
            return steps

    return run


def bench_config(
    name, model, in_shape, batch_per_chip, steps, augment=None,
    x_dtype=np.float32, scan=1, opt=None, comm_hook="none",
):
    import jax
    import jax.numpy as jnp

    from tpuddp import nn, optim
    from tpuddp.parallel import make_mesh
    from tpuddp.parallel.ddp import DistributedDataParallel
    from tpuddp.training.step import stack_batches

    opt = opt or (lambda: optim.Adam(1e-3))
    devices = jax.devices()
    mesh = make_mesh(devices)
    n_chips = len(devices)
    global_batch = batch_per_chip * n_chips

    ddp = DistributedDataParallel(
        model, opt(), nn.CrossEntropyLoss(), mesh=mesh,
        mode="shard_map", augment=augment, comm_hook=comm_hook,
    )
    model_in = in_shape if augment is None else augment(
        jax.random.key(0), jnp.zeros((1,) + in_shape, x_dtype)
    ).shape[1:]
    state = ddp.init_state(jax.random.key(0), jnp.zeros((1,) + tuple(model_in)))

    rng = np.random.RandomState(0)
    if np.issubdtype(x_dtype, np.integer):
        x = rng.randint(0, 256, (global_batch,) + in_shape).astype(x_dtype)
    else:
        x = rng.randn(global_batch, *in_shape).astype(x_dtype)
    y = rng.randint(0, 10, global_batch).astype(np.int32)
    w = np.ones(global_batch, np.float32)
    batch = ddp.shard((x, y, w))

    state_box = [state]
    laps = []
    run = _make_runner(ddp, state_box, batch, scan, laps=laps)
    run(max(3, scan))  # compile + stage all buffers (lazy-upload warm)
    run(max(3, scan))  # second warm pass: steady-state dispatch path
    laps.clear()  # percentiles cover the timed region only
    t0 = time.perf_counter()
    steps = run(steps)
    dt = time.perf_counter() - t0

    # FLOPs of the step actually timed, cross-checked at runtime rather than
    # assumed (two backend/version-dependent conventions could each skew the
    # published MFU by Kx or Nx):
    #  1. scan counting: XLA's cost analysis counts a while/scan body once in
    #     most versions (scan-program flops ~= single-step program flops); if
    #     this backend instead counts the body K times, the ratio test below
    #     detects it and divides by K. Anything else -> MFU suppressed.
    #  2. chip counting: the figure may be whole-program or per-device. With
    #     n_chips > 1 a 1-device probe of the same per-chip workload
    #     disambiguates; an unresolvable ratio -> MFU suppressed.
    flops_note = None
    flops_per_chip = None
    try:
        bx, by, bw = batch
        f_single = _program_flops(
            jax.jit(lambda s, a, b, c: ddp.train_step(s, (a, b, c))),
            state_box[0], bx, by, bw,
        )
        f_step = f_single
        if scan > 1 and f_single:
            stacked = ddp.shard_stacked(
                stack_batches([tuple(np.asarray(b) for b in batch)] * scan)
            )
            xs, ys, ws = stacked
            f_scan = _program_flops(
                jax.jit(lambda s, a, b, c: ddp.train_step_many(s, (a, b, c))),
                state_box[0], xs, ys, ws,
            )
            ratio = (f_scan or 0.0) / f_single
            if 0.75 <= ratio <= 1.33:
                f_step = f_scan  # body counted once (the usual convention)
            elif abs(ratio - scan) / scan <= 0.33:
                f_step = f_scan / scan  # body counted per trip
            else:
                f_step = None
                flops_note = f"scan/single flops ratio {ratio:.2f} unresolvable"
                log(f"  MFU suppressed: {flops_note}")
        if f_step and n_chips > 1:
            # Disambiguate whole-program vs per-device module flops.
            from tpuddp.parallel import make_mesh as _mk
            ddp1 = DistributedDataParallel(
                model, opt(), nn.CrossEntropyLoss(),
                mesh=_mk(devices[:1]), mode="shard_map", augment=augment,
            )
            b1 = ddp1.shard((x[:batch_per_chip], y[:batch_per_chip], w[:batch_per_chip]))
            f_1dev = _program_flops(
                jax.jit(lambda s, a, b, c: ddp1.train_step(s, (a, b, c))),
                state_box[0], *b1,
            )
            if f_1dev:
                r = f_step / f_1dev
                if abs(r - n_chips) / n_chips <= 0.25:
                    flops_per_chip = f_step / n_chips  # whole-program figure
                elif 0.75 <= r <= 1.33:
                    flops_per_chip = f_step  # per-device figure
                else:
                    flops_note = f"{n_chips}-chip/1-chip flops ratio {r:.2f} unresolvable"
                    log(f"  MFU suppressed: {flops_note}")
        elif f_step:
            flops_per_chip = f_step
    except Exception as e:
        log(f"  flops probe failed ({type(e).__name__}: {e})")

    # Model-only MFU: subtract the augment pipeline's FLOPs (resize/flip/
    # normalize) from the whole-program numerator so model-compute utilization
    # isn't flattered by input-pipeline FLOPs (measured ~0.3% on AlexNet@224 —
    # reported so the distinction is auditable, not because it moves much).
    extra = {}
    if flops_per_chip and augment is not None:
        try:
            k0 = jax.random.key(0)
            xp = x[:batch_per_chip]
            aug_flops = _program_flops(jax.jit(lambda r, v: augment(r, v)), k0, xp)
            if aug_flops and aug_flops < flops_per_chip:
                peak, _ = _peak_flops()
                if peak:
                    extra["mfu_model"] = round(
                        (flops_per_chip - aug_flops) / (dt / steps) / peak, 4
                    )
        except Exception as e:
            log(f"  augment flops probe failed ({type(e).__name__}: {e})")
    if flops_note:
        extra["mfu_note"] = flops_note
    # step-time percentiles over the timed region's per-dispatch laps (the
    # observability recorder's percentile code — one definition for bench
    # rows and history.jsonl): a straggling dispatch or a mid-run slowdown
    # shows up as a p95/p99 >> p50, invisible in the mean
    if laps:
        from tpuddp.observability import percentiles as _pct

        pct = _pct(laps)
        extra.update({
            f"ms_per_step_{k}": round(v * 1e3, 3)
            for k, v in pct.items() if v is not None
        })
        extra["timed_dispatches"] = len(laps)
        # wall/device estimator for pre-staged rows: mean timed step (the
        # headline, fence-amortized) over the p50 dispatch lap — under device
        # backpressure the laps converge to execution time, so the ratio
        # isolates the fence/host share. Host stall is a structural 0 here:
        # these rows reuse one pre-staged buffer, no host loader runs (the
        # --pipeline A/B rows measure the real loader-fed ratio).
        if pct.get("p50"):
            extra["wall_to_device_ratio"] = round(
                (dt / steps) / pct["p50"], 3
            )
        extra["host_stall_ms_p50"] = 0.0
        extra["host_stall_ms_p95"] = 0.0
    # per-step gradient-comm wire bytes (parallel/comm.py accounting): the
    # compressed hooks' byte reduction as a recorded bench artifact
    if ddp.grad_comm_bytes_per_step is not None:
        extra["grad_comm_bytes_per_step"] = int(ddp.grad_comm_bytes_per_step)
        if comm_hook != "none":
            extra["comm_hook"] = comm_hook

    sps = steps * global_batch / dt
    _record(name, sps / n_chips, dt / steps * 1e3, flops_per_chip, extra or None)
    return sps / n_chips, n_chips


def bench_managed(batch_per_chip=128, steps=60, deferred=False, fuse=1):
    """The managed (Accelerator) path on the toy MLP — BASELINE.json
    configs[2]. Eager mode keeps the reference's per-batch loss.item() sync
    (quirk Q3/Q5 parity); deferred mode syncs once at the end; fuse > 1 adds
    K-step scan fusion behind the Accelerator (the managed analog of the
    native scan-fused path)."""
    import jax
    import jax.numpy as jnp

    from tpuddp import nn, optim
    from tpuddp.accelerate import Accelerator
    from tpuddp.models import ToyMLP
    from tpuddp.parallel import make_mesh

    mesh = make_mesh(jax.devices())
    n_chips = mesh.devices.size
    global_batch = batch_per_chip * n_chips
    acc = Accelerator(mesh=mesh, seed=0, fuse_steps=fuse)
    model, opt = acc.prepare(ToyMLP(num_classes=10), optim.Adam(1e-3))
    criterion = nn.CrossEntropyLoss()

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(global_batch, 32, 32, 3).astype(np.float32))
    y = jnp.asarray(rng.randint(0, 10, global_batch).astype(np.int32))

    def run(n):
        losses = []
        total = 0.0
        for _ in range(n):
            opt.zero_grad()
            loss = criterion(model(x), y)
            acc.backward(loss)
            opt.step()
            if deferred:
                losses.append(loss)  # values land when the queue flushes
            else:
                total += loss.item()
        if deferred:
            # sum on device array-at-a-time over fused flushes; one fetch
            from tpuddp.accelerate import sum_losses

            total = float(sum_losses(losses))
        assert np.isfinite(total)

    # warm twice with >= 2 flushes each so every program the timed run uses is
    # compiled: the fused-scan (both pre- and post-donation operand layouts)
    # AND sum_losses' scalar add between flush arrays
    run(2 * max(3, fuse))
    run(2 * max(3, fuse))
    t0 = time.perf_counter()
    run(steps)
    dt = time.perf_counter() - t0
    sps = steps * global_batch / dt
    mode = "deferred" if deferred else "eager per-batch sync"
    if fuse > 1:
        mode += f", {fuse}-step fused"
    _record(f"managed toy_mlp ({mode})", sps / n_chips, dt / steps * 1e3, None)
    return sps / n_chips


def bench_managed_alexnet(batch_per_chip=128, steps=96, fuse=32):
    """The managed (Accelerator) path on the compute-bound flagship config —
    AlexNet s2d bf16 @224, bf16 Adam moments, deferred metrics, fuse_steps
    scan — so the 'native and managed compile to the same step program' claim
    is a measured fact on a real CNN, not an inference from the toy model
    (reference managed entrypoint: multi-GPU-training-accelerate.py:39-56).
    Compare against the native 'alexnet bf16 224 bf16-opt s2d (scan-fused)'
    row: same model, batch, optimizer, augment, and fusion depth."""
    import jax
    import jax.numpy as jnp

    from tpuddp import nn, optim
    from tpuddp.accelerate import Accelerator
    from tpuddp.data.transforms import make_train_augment
    from tpuddp.models import AlexNet
    from tpuddp.parallel import make_mesh

    mesh = make_mesh(jax.devices())
    n_chips = mesh.devices.size
    global_batch = batch_per_chip * n_chips
    acc = Accelerator(mesh=mesh, seed=0, fuse_steps=fuse)
    model, opt = acc.prepare(
        AlexNet(10, space_to_depth=True),
        optim.Adam(1e-3, state_dtype="bfloat16"),
    )
    criterion = nn.CrossEntropyLoss()
    _aug = make_train_augment(size=224, compute_dtype=jnp.bfloat16)
    augment = jax.jit(lambda rng, i, x: _aug(jax.random.fold_in(rng, i), x))

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randint(0, 256, (global_batch, 32, 32, 3)).astype(np.uint8))
    y = jnp.asarray(rng.randint(0, 10, global_batch).astype(np.int32))
    aug_base = acc.next_rng_key()
    # stage ONE augmented batch and reuse it, exactly like the native row
    # reuses its pre-staged stacked batch — the timed region then measures
    # the managed STEP path, not per-step augment dispatch/upload overhead
    # (w=None hits the prepared model's cached all-ones weights)
    xb = augment(aug_base, 0, x)

    def run(n):
        from tpuddp.accelerate import sum_losses

        losses = []
        for _ in range(n):
            opt.zero_grad()
            loss = criterion(model(xb), y)
            acc.backward(loss)
            opt.step()
            losses.append(loss)
        total = float(sum_losses(losses))  # one fetch; fences the chain
        assert np.isfinite(total)

    run(2 * fuse)
    run(2 * fuse)
    t0 = time.perf_counter()
    run(steps)
    dt = time.perf_counter() - t0
    sps = steps * global_batch / dt
    _record(
        f"managed alexnet bf16 224 bf16-opt s2d (deferred, {fuse}-step fused)",
        sps / n_chips, dt / steps * 1e3, None,
    )
    return sps / n_chips


def bench_managed_eval(batch_per_chip=128, batches=256, fused=True, fuse_k=None):
    """The managed eval pass on the toy MLP: the facade loop (2+ dispatches
    per test batch: transform, forward, plus per-batch metric ops) vs the
    FusedEvaluator (ONE scan dispatch per K batches + one final fetch — the
    managed analog of the native eval scan). ``fuse_k=None`` measures the
    product default (size-resolved K)."""
    import jax
    import jax.numpy as jnp

    from tpuddp import nn
    from tpuddp.accelerate import Accelerator, FusedEvaluator
    from tpuddp.data.transforms import make_eval_transform
    from tpuddp.models import ToyMLP
    from tpuddp.parallel import make_mesh

    mesh = make_mesh(jax.devices())
    n_chips = mesh.devices.size
    acc = Accelerator(mesh=mesh, seed=0)
    model = acc.prepare(ToyMLP(num_classes=10))
    model.eval()
    criterion = nn.CrossEntropyLoss()
    transform = jax.jit(make_eval_transform(size=None))

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(batch_per_chip, 32, 32, 3).astype(np.float32))
    y = np.ascontiguousarray(rng.randint(0, 10, batch_per_chip).astype(np.int32))
    w = np.ones(batch_per_chip, np.float32)
    model(np.asarray(x[:1]))  # init params

    if fused:
        ev = FusedEvaluator(model, criterion, transform=transform, fuse_steps=fuse_k)
        # the product default (flat 32; toy batches are far under the
        # staging budget so the probe matches the in-run resolution)
        fuse_k = ev._resolve_fuse()

        def run(n):
            for _ in range(n):
                ev.add(x, y, w)
            loss_sum, _, total = ev.finalize()
            assert np.isfinite(loss_sum) and total == n * batch_per_chip
    else:
        fuse_k = fuse_k or 8  # warmup count only; the facade has no fusion

        def run(n):
            loss_sum = 0.0
            for _ in range(n):
                outputs = model(transform(x))
                loss_sum += criterion(outputs, y, w).item()
            assert np.isfinite(loss_sum)

    run(2 * fuse_k)
    run(2 * fuse_k)
    t0 = time.perf_counter()
    run(batches)
    dt = time.perf_counter() - t0
    sps = batches * batch_per_chip / dt  # full batch on every chip (quirk Q3)
    mode = f"scan-fused K={fuse_k}" if fused else "per-batch facade"
    _record(f"managed eval toy_mlp ({mode})", sps, dt / batches * 1e3, None)
    return sps


def _device_ms_denominator(ddp, state, stacked, scan):
    """Per-step device time of ONE wrap's compiled scan step, measured over a
    pre-staged chunk and fenced once — the denominator of a row's
    ``wall_to_device_ratio``.

    The denominator is only honest for rows dispatching the SAME compiled
    program it was measured under. ``--pipeline`` shares one wrap across its
    on/off rows (the pipeline's HLO-identity contract), so one derivation
    covers both."""
    metrics = None
    for _ in range(2):  # compile + warm
        state, metrics = ddp.train_step_many(state, stacked)
    float(np.sum(np.asarray(metrics["loss_sum"])))
    n_dev = max(4, 32 // scan)
    t0 = time.perf_counter()
    for _ in range(n_dev):
        state, metrics = ddp.train_step_many(state, stacked)
    float(np.sum(np.asarray(metrics["loss_sum"])))  # fence
    return (time.perf_counter() - t0) / (n_dev * scan) * 1e3


def bench_pipeline_pair(batch_per_chip=64, n_train=4096, repeats=2, scan=8):
    """The async-pipeline A/B (``--pipeline``): one epoch of the REAL
    loader-fed training pass (ShardedDataLoader -> staged chunks -> K-fused
    dispatch) on a CNN, measured twice through the actual pipelined runner
    (tpuddp/training/pipeline.py):

    - ``pipeline off``: the synchronous reference — no loader workers, no
      staged lookahead, one blocking readback per dispatch (the serial
      cadence whose cost BASELINE.md's dispatch-RTT section documents);
    - ``pipeline on``: the product default shape (host workers + deep staged
      queue + deferred readback drain).

    Both rows share one device-time denominator — the same step program
    dispatched over a pre-staged chunk, fenced once — so
    ``wall_to_device_ratio`` is comparable: the pipeline's whole claim is
    that the ON row's ratio sits closer to 1.0. Bitwise parity of the two
    passes is asserted in-run (same seed, same data order -> identical final
    loss sums), not just in the test suite."""
    import jax
    import jax.numpy as jnp

    from tpuddp import nn, optim
    from tpuddp.data import PrefetchLoader, ShardedDataLoader
    from tpuddp.data.synthetic import synthetic_uint8_datasets
    from tpuddp.data.transforms import make_train_augment
    from tpuddp.models import ToyCNN
    from tpuddp.parallel import make_mesh
    from tpuddp.parallel.ddp import DistributedDataParallel
    from tpuddp.training import pipeline as pipe
    from tpuddp.training.step import stack_batches

    mesh = make_mesh(jax.devices())
    n_chips = mesh.devices.size
    train_ds, _ = synthetic_uint8_datasets(n_train, 64, seed=0)
    augment = make_train_augment(size=None)  # on-device normalize (in-step)

    class _Cap:
        """Telemetry stub capturing per-dispatch host-stall laps."""

        def __init__(self):
            self.stalls = []

        def offer_batch(self, b):
            pass

        def pre_dispatch(self, n):
            pass

        def post_dispatch(self, n, s, fence=None, host_stall_s=0.0, **occ):
            self.stalls.append(host_stall_s)

    # ONE wrap for both rows: the compiled step programs are shared (the
    # pipeline never enters program construction — its HLO-identity
    # contract), and each row re-inits the state from the same key, so the
    # A/B isolates the host pipeline and nothing else. widths=(8, 16): a
    # real conv net sized so the pair stays O(minutes) on the CPU rung too.
    ddp = DistributedDataParallel(
        ToyCNN(10, widths=(8, 16)), optim.Adam(1e-3), nn.CrossEntropyLoss(),
        mesh=mesh, mode="shard_map", augment=augment,
    )

    def fresh_state():
        return ddp.init_state(jax.random.key(0), jnp.zeros((1, 32, 32, 3)))

    def one_pass(state, loader, cfg, cap=None):
        state, acc, _ = pipe.run_pass(
            ddp, state, loader, scan, ddp.train_step, ddp.train_step_many,
            cfg=cfg, tel=cap,
        )
        # the fence: one value fetch from the accumulated metrics
        loss_sum = float(np.sum(np.asarray(acc["loss_sum"])))
        assert np.isfinite(loss_sum)
        return state, loss_sum

    # shared device-time denominator: the same scan program over ONE
    # pre-staged chunk, fenced once — what the chip does with zero host work
    base_loader = ShardedDataLoader(
        train_ds, batch_per_chip, mesh, shuffle=True, seed=0
    )
    base_loader.set_epoch(0)
    first_chunk = []
    for b in base_loader:
        first_chunk.append(b)
        if len(first_chunk) == scan:
            break
    stacked = ddp.shard_stacked(stack_batches(first_chunk))
    device_ms = _device_ms_denominator(ddp, fresh_state(), stacked, scan)
    # one derivation for both rows is correct HERE because both rows
    # dispatch this one wrap's program (see _device_ms_denominator)

    rows = {}
    for on in (False, True):
        if on:
            cfg = pipe.PipelineConfig(depth=4, host_workers=2)
        else:
            cfg = pipe.SYNCHRONOUS
        state = fresh_state()
        loader = ShardedDataLoader(
            train_ds, batch_per_chip, mesh, shuffle=True, seed=0
        )
        if on and cfg.host_workers:
            loader = PrefetchLoader(loader, workers=cfg.host_workers)
        loader.set_epoch(0)
        state, _ = one_pass(state, loader, cfg)  # warm/compile epoch
        cap = _Cap()
        n_steps = len(loader) * repeats
        samples = 0
        t0 = time.perf_counter()
        loss_sums = []
        for ep in range(1, repeats + 1):
            loader.set_epoch(ep)
            state, loss_sum = one_pass(state, loader, cfg, cap=cap)
            loss_sums.append(loss_sum)
            samples += len(train_ds)
        dt = time.perf_counter() - t0
        wall_ms = dt / n_steps * 1e3
        from tpuddp.observability import percentiles as _pct

        pct = _pct(cap.stalls)
        name = (
            f"toy_cnn b{batch_per_chip} loader-fed "
            + ("(pipeline on, depth 4)" if on else "(pipeline off, synchronous)")
        )
        extra = {
            "wall_to_device_ratio": round(wall_ms / device_ms, 3),
            "device_ms_per_step": round(device_ms, 3),
            "host_stall_ms_p50": round((pct["p50"] or 0.0) * 1e3, 3),
            "host_stall_ms_p95": round((pct["p95"] or 0.0) * 1e3, 3),
            "pipeline": cfg.as_dict(),
        }
        _record(name, samples / dt / n_chips, wall_ms, None, extra)
        rows[on] = {"sps": samples / dt / n_chips, "loss_sums": loss_sums}
    # bitwise parity of the A/B itself: same seed + same data order must give
    # the same trajectory whichever way the host pipeline ran
    assert rows[True]["loss_sums"] == rows[False]["loss_sums"], (
        "pipeline on/off trajectories diverged: "
        f"{rows[True]['loss_sums']} vs {rows[False]['loss_sums']}"
    )
    return rows[True]["sps"], rows[False]["sps"]


def bench_comm_matrix(batch_per_chip=64, steps=96, density=0.1):
    """The comm-compression-v2 A/B matrix (``--comm``): every hook
    (none/bf16_ef/int8_ef/topk_ef) x topology (flat/hierarchical) pair on
    the same fixed toy-MLP workload over all local devices — the ISSUE 9
    acceptance artifact (BENCH_r07.json). Per row: throughput, per-step
    gradient wire bytes (total + the inter-/intra-host hop split), the
    hook's density, and the final mean loss. In-run assertions make the
    artifact self-verifying rather than a claim:

    - ``int8_ef`` cuts >= 70% and ``topk_ef`` (density 0.1) >= 85% of the
      f32 gradient wire bytes on the explicit flat path;
    - every compressed run's final loss tracks the uncompressed flat run
      within the documented per-hook bound
      (:func:`tpuddp.parallel.comm.loss_parity_tol` — topk_ef's error
      feedback warms up over ~1/density updates, hence ``steps=96``: the
      matrix compares trajectories past the warmup, where the bound is
      meaningful);
    - hierarchical topology's inter-host bytes are strictly below the flat
      topology's total for the same hook (the reason the topology exists).

    Returns ``(int8_flat_sps, none_flat_sps)`` for the summary line."""
    import jax
    import jax.numpy as jnp

    from tpuddp import nn, optim
    from tpuddp.parallel import comm as comm_lib
    from tpuddp.parallel import make_mesh
    from tpuddp.parallel.ddp import DistributedDataParallel
    from tpuddp.parallel.mesh import hierarchical_mesh
    from tpuddp.models import ToyMLP

    devices = jax.devices()
    n_chips = len(devices)
    global_batch = batch_per_chip * n_chips
    rng = np.random.RandomState(7)
    x = rng.randn(global_batch, 8, 8, 3).astype(np.float32)
    y = rng.randint(0, 10, global_batch).astype(np.int32)
    w = np.ones(global_batch, np.float32)

    topologies = ["flat"]
    if n_chips % 2 == 0 and n_chips >= 2:
        topologies.append("hierarchical")
    else:
        log(f"comm matrix: hierarchical rows skipped ({n_chips} devices "
            "do not factor into a (host, local) split)")

    stats = {}
    for topology in topologies:
        mesh = (
            hierarchical_mesh(devices=devices)
            if topology == "hierarchical"
            else make_mesh(devices)
        )
        for hook in ("none", "bf16_ef", "int8_ef", "topk_ef"):
            ddp = DistributedDataParallel(
                ToyMLP(hidden=(16,)), optim.Adam(1e-2),
                nn.CrossEntropyLoss(), mesh=mesh, mode="shard_map",
                comm_hook=hook, comm_topology=topology, topk_density=density,
            )
            state = ddp.init_state(
                jax.random.key(0), jnp.zeros((1, 8, 8, 3))
            )
            batch = ddp.shard((x, y, w))
            metrics = None
            for _ in range(3):  # compile + warm
                state, metrics = ddp.train_step(state, batch)
            float(np.sum(np.asarray(metrics["loss_sum"])))
            t0 = time.perf_counter()
            for _ in range(steps):
                state, metrics = ddp.train_step(state, batch)
            loss_sum = float(np.sum(np.asarray(metrics["loss_sum"])))  # fence
            dt = time.perf_counter() - t0
            final_loss = loss_sum / float(np.sum(np.asarray(metrics["n"])))
            assert np.isfinite(final_loss), (hook, topology)
            name = f"toy_mlp b{batch_per_chip} comm {hook} {topology}"
            sps = steps * global_batch / dt
            extra = {
                "comm_hook": hook,
                "comm_topology": topology,
                "comm_density": density if hook == "topk_ef" else None,
                "grad_comm_bytes_per_step": int(ddp.grad_comm_bytes_per_step),
                "grad_comm_bytes_per_step_f32": int(
                    ddp.grad_comm_bytes_per_step_f32
                ),
                "grad_comm_bytes_inter_host": int(
                    ddp.grad_comm_bytes_inter_host
                ),
                "grad_comm_bytes_intra_host": int(
                    ddp.grad_comm_bytes_intra_host
                ),
                "final_loss": round(final_loss, 6),
            }
            _record(name, sps / n_chips, dt / steps * 1e3, None, extra)
            stats[(hook, topology)] = {
                "sps": sps / n_chips, "loss": final_loss, **extra,
            }

    base = stats[("none", "flat")]
    f32 = base["grad_comm_bytes_per_step_f32"]
    for hook, floor in (("int8_ef", 0.70), ("topk_ef", 0.85)):
        cut = 1 - stats[(hook, "flat")]["grad_comm_bytes_per_step"] / f32
        assert cut >= floor, (
            f"{hook}: {cut * 100:.1f}% byte cut is under the {floor * 100:.0f}% "
            "acceptance floor"
        )
        log(f"comm matrix: {hook} cuts {cut * 100:.1f}% of gradient wire bytes")
    for (hook, topology), row in stats.items():
        tol = comm_lib.loss_parity_tol(hook, base["loss"])
        assert abs(row["loss"] - base["loss"]) <= tol, (
            f"{hook}/{topology}: final loss {row['loss']:.4f} diverged from "
            f"uncompressed {base['loss']:.4f} (documented tol {tol:.4f})"
        )
    if "hierarchical" in topologies:
        for hook in ("none", "bf16_ef", "int8_ef", "topk_ef"):
            flat_total = stats[(hook, "flat")]["grad_comm_bytes_per_step"]
            inter = stats[(hook, "hierarchical")]["grad_comm_bytes_inter_host"]
            assert inter < flat_total, (
                f"{hook}: hierarchical inter-host bytes {inter} not below "
                f"the flat total {flat_total}"
            )
        log("comm matrix: hierarchical inter-host bytes < flat totals for "
            "every hook")
    return stats[("int8_ef", "flat")]["sps"], base["sps"]


def bench_torch_cpu(batch=128, steps=30, warmup=3):
    """The reference stack's hot loop (toy MLP) on this host (torch CPU)."""
    try:
        import torch
        import torch.nn as tnn
    except Exception as e:  # pragma: no cover
        log(f"torch unavailable ({e}); vs_baseline=1.0")
        return None

    torch.manual_seed(0)
    model = tnn.Sequential(
        tnn.Flatten(),
        tnn.Linear(32 * 32 * 3, 256),
        tnn.ReLU(),
        tnn.Linear(256, 128),
        tnn.ReLU(),
        tnn.Linear(128, 10),
    )
    opt = torch.optim.Adam(model.parameters(), lr=1e-3)
    criterion = tnn.CrossEntropyLoss()
    x = torch.randn(batch, 3, 32, 32)
    y = torch.randint(0, 10, (batch,))

    def step():
        opt.zero_grad()
        loss = criterion(model(x), y)
        loss.backward()
        opt.step()
        return float(loss.item())  # the reference's per-batch sync (quirk Q5)

    for _ in range(warmup):
        step()
    t0 = time.perf_counter()
    for _ in range(steps):
        step()
    dt = time.perf_counter() - t0
    sps = steps * batch / dt
    log(f"torch-cpu baseline (toy MLP): {sps:,.0f} samples/s")
    return sps


def emit_summary(
    ours, baseline, out_path=None,
    metric="toy_mlp_train_samples_per_sec_per_chip",
    basis="torch-cpu",
):
    """The driver-parseable output contract: the FULL per-config payload goes
    to ``bench_results.json`` (next to this script unless ``out_path``), and
    the returned dict — compact, configs elided — is what :func:`main` prints
    as the LAST stdout line. Keeping the stdout line small and flat is the
    point: the round-5 verdict's ``parsed: null`` came from the full dict
    being the line. ``--pipeline`` mode swaps the headline metric and the
    baseline basis (pipeline-on vs pipeline-off)."""
    vs = ours / baseline if baseline else 1.0
    _, kind = _peak_flops()
    payload = {
        "metric": metric,
        "value": round(ours, 1),
        "unit": "samples/sec/chip",
        "vs_baseline": round(vs, 2),
        # default basis: the reference stack on this host's only torch
        # device (CPU — no NVIDIA hardware exists here); a chip-vs-CPU
        # ratio, NOT a GPU comparison. Cross-stack correctness evidence is
        # the loss-curve parity tests instead. --pipeline mode uses the
        # pipeline-off row as the basis instead.
        "vs_baseline_basis": basis,
        "device": kind,
        "configs": RESULTS,
    }
    path = out_path or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "bench_results.json"
    )
    # strict JSON on disk: a non-finite row value (a failed/blown-up config)
    # lands as null, never the bare NaN token strict parsers reject
    from tpuddp.observability import json_sanitize

    with open(path, "w") as f:
        json.dump(json_sanitize(payload), f, indent=2, allow_nan=False)
        f.write("\n")
    log(f"full per-config results -> {path}")
    return {
        "metric": payload["metric"],
        "value": payload["value"],
        "unit": payload["unit"],
        "vs_baseline": payload["vs_baseline"],
        "vs_baseline_basis": basis,
        "device": kind,
        "n_configs": len(RESULTS),
        "results_file": os.path.basename(path),
    }


def main(argv=None):
    import jax.numpy as jnp

    from tpuddp.data.transforms import make_train_augment
    from tpuddp.models import (
        AlexNet, ResNet18, ResNet34, ResNet50, ResNet101, ToyMLP, VGG11,
    )

    argv = sys.argv[1:] if argv is None else argv
    slow = "--slow" in argv or os.environ.get("TPUDDP_BENCH_SLOW") == "1"
    out_path = None
    if "--out" in argv:
        i = argv.index("--out")
        if i + 1 >= len(argv) or argv[i + 1].startswith("--"):
            log("--out needs a path argument")
            raise SystemExit(2)
        out_path = argv[i + 1]
    if "--comm" in argv:
        # the comm-compression-v2 A/B matrix (ISSUE 9 acceptance artifact):
        # hook x topology rows with wire-byte accounting + in-run byte-cut /
        # loss-parity / hierarchical-inter-host assertions; the headline is
        # int8_ef-flat throughput against the uncompressed flat baseline
        from tpuddp.observability import json_sanitize

        int8_sps, none_sps = bench_comm_matrix()
        summary = emit_summary(
            int8_sps, none_sps, out_path=out_path,
            metric="toy_mlp_int8_ef_train_samples_per_sec_per_chip",
            basis="comm-hook-none",
        )
        print(json.dumps(json_sanitize(summary), allow_nan=False), flush=True)
        return
    if "--pipeline" in argv:
        # the async-pipeline A/B mode: ONLY the loader-fed on/off pair, with
        # the pipeline-off (synchronous) row as the baseline basis — the
        # overlap win is the headline (ISSUE 8 acceptance artifact)
        from tpuddp.observability import json_sanitize

        on_sps, off_sps = bench_pipeline_pair()
        summary = emit_summary(
            on_sps, off_sps, out_path=out_path,
            metric="toy_cnn_pipeline_train_samples_per_sec_per_chip",
            basis="pipeline-off",
        )
        print(json.dumps(json_sanitize(summary), allow_nan=False), flush=True)
        return

    # Headline: the toy model is dispatch-bound (its compute is ~13 us/step),
    # so throughput scales with the fusion depth K until staging/memory costs
    # bite; K=200 measured 1.6-2.2M samples/s/chip across rounds (K=50:
    # 0.6M, K=400: 2.5M but the flops probe's scan cross-check no longer
    # resolves there).
    # The headline row feeds the driver's one-JSON-line contract, so unlike
    # the diagnostic rows below it retries through transient runtime flakes.
    last_err = None
    for attempt in range(3):
        try:
            ours, n_chips = bench_config(
                "toy_mlp f32 (scan-fused K=200)", ToyMLP(num_classes=10),
                (32, 32, 3), 128, steps=2000, scan=200,
            )
            break
        except Exception as e:
            last_err = e
            log(f"headline bench attempt {attempt + 1} failed: {e}; retrying")
    else:
        raise last_err
    try:
        bench_config(
            "toy_mlp f32 (per-step dispatch)", ToyMLP(num_classes=10),
            (32, 32, 3), 128, steps=256,
        )
    except Exception as e:
        log(f"per-step toy bench failed: {type(e).__name__}: {e}")

    def cifar_resnet(cls):
        # The TPU-friendly CIFAR recipe: a modern ResNet at the native 32x32
        # resolution instead of paying the reference's 49x resize FLOPs.
        return (
            cls(10, sync_bn=True, small_input=True),
            make_train_augment(size=None, compute_dtype=jnp.bfloat16),
        )

    def bf16_alexnet():
        return (
            AlexNet(10),
            make_train_augment(size=224, compute_dtype=jnp.bfloat16),
        )

    from tpuddp import optim as _optim

    bf16_opt = lambda: _optim.Adam(1e-3, state_dtype="bfloat16")
    cnn_configs = [
        # (name, factory, per-chip batch, scan K, timed steps, opt factory)
        # K=64 on the CNN rows = the product default (loop._AUTO_SCAN_CAP,
        # within the staged-chunk budget for these uint8 inputs): K
        # amortizes the per-dispatch latency (BASELINE.md)
        ("alexnet f32 224 (per-step dispatch)",
         lambda: (AlexNet(10), make_train_augment(size=224)), 128, 1, 64, None),
        ("alexnet f32 224 (scan-fused)",
         lambda: (AlexNet(10), make_train_augment(size=224)), 128, 64, 128, None),
        ("alexnet bf16 224 (scan-fused)", bf16_alexnet, 128, 64, 128, None),
        # bf16 Adam m/v storage (training.optimizer_state_dtype): halves the
        # optimizer-state HBM traffic that bounds AlexNet at the reference's
        # own b128 (profile-backed; see BASELINE.md "Where the time goes")
        ("alexnet bf16 224 bf16-opt (scan-fused)", bf16_alexnet, 128, 64, 128,
         bf16_opt),
        # exact space-to-depth stem reparameterization (model: alexnet_s2d):
        # the 11x11/s4 3-channel stem becomes a unit-stride conv over 48
        # blocked channels — same math/params, ~+2.5 MFU points at the
        # reference-constant b128 (amortized away at b512)
        ("alexnet bf16 224 bf16-opt s2d (scan-fused)",
         lambda: (AlexNet(10, space_to_depth=True),
                  make_train_augment(size=224, compute_dtype=jnp.bfloat16)),
         128, 64, 128, bf16_opt),
        # the TPU-right batch: amortizes the remaining fixed per-step
        # param+grad HBM traffic over 4x the samples
        ("alexnet bf16 224 b512 bf16-opt (scan-fused)", bf16_alexnet, 512, 16,
         32, bf16_opt),
        # the measured sweet spot: with the s2d stem, b256 matches-or-beats
        # the b512 row at half the per-chip batch (same-session artifact
        # pair, BENCH_r04.json: 39.3% vs 38.0%)
        ("alexnet bf16 224 b256 bf16-opt s2d (scan-fused)",
         lambda: (AlexNet(10, space_to_depth=True),
                  make_train_augment(size=224, compute_dtype=jnp.bfloat16)),
         256, 32, 64, bf16_opt),
        ("resnet18 bf16 32x32 sync-BN (scan-fused)",
         lambda: cifar_resnet(ResNet18), 128, 64, 128, None),
        ("resnet34 bf16 32x32 sync-BN (scan-fused)",
         lambda: cifar_resnet(ResNet34), 128, 64, 128, None),
        # the full-resolution reference-class CNN (data_and_toy_model.py:13-36
        # is 224x224): profile-backed accounting in BASELINE.md "Where the
        # time goes (ResNet-18@224)"; s2d = exact 7x7/s2 stem
        # reparameterization (resnet18_s2d)
        ("resnet18 bf16 224 b128 bf16-opt (scan-fused)",
         lambda: (ResNet18(10),
                  make_train_augment(size=224, compute_dtype=jnp.bfloat16)),
         128, 64, 128, bf16_opt),
        ("resnet18 bf16 224 b128 bf16-opt s2d (scan-fused)",
         lambda: (ResNet18(10, space_to_depth=True),
                  make_train_augment(size=224, compute_dtype=jnp.bfloat16)),
         128, 64, 128, bf16_opt),
        # the Bottleneck/VGG halves of the model zoo (half the
        # zoo had zero perf evidence) — measured rows with device-MFU like
        # every config above, at depths sized so one row stays O(minute)
        ("vgg11 bf16 224 b128 bf16-opt (scan-fused)",
         lambda: (VGG11(10),
                  make_train_augment(size=224, compute_dtype=jnp.bfloat16)),
         128, 16, 32, bf16_opt),
        ("resnet50 bf16 224 b128 bf16-opt (scan-fused)",
         lambda: (ResNet50(10),
                  make_train_augment(size=224, compute_dtype=jnp.bfloat16)),
         128, 16, 32, bf16_opt),
    ]
    if slow:
        # the big-model tail: ResNet-101 @ 224 is minutes of compile+run, so
        # it rides the same slow tier as the test suite's big donors
        cnn_configs.append(
            ("resnet101 bf16 224 b64 bf16-opt (scan-fused, slow)",
             lambda: (ResNet101(10),
                      make_train_augment(size=224, compute_dtype=jnp.bfloat16)),
             64, 8, 16, bf16_opt)
        )
    else:
        log("resnet101 row skipped (slow tier: pass --slow or TPUDDP_BENCH_SLOW=1)")
    for name, make, batch, scan, steps, opt in cnn_configs:
        try:  # diagnostics only — independent, and never break the headline line
            model, augment = make()
            bench_config(
                name, model, (32, 32, 3), batch, steps=steps,
                augment=augment, x_dtype=np.uint8, scan=scan, opt=opt,
            )
        except Exception as e:
            log(f"{name} bench failed: {type(e).__name__}: {e}")

    try:
        # comm-hook artifact pair (parallel/comm.py): the resnet18@32 sync-BN
        # workload again, under the bf16_ef bucketed compressed allreduce —
        # its grad_comm_bytes_per_step sits next to the uncompressed row's in
        # the results file, so the gradient-byte reduction (and any
        # throughput delta) is a recorded bench artifact, not a claim
        model, augment = cifar_resnet(ResNet18)
        bench_config(
            "resnet18 bf16 32x32 sync-BN (scan-fused, bf16_ef comm hook)",
            model, (32, 32, 3), 128, steps=128, augment=augment,
            x_dtype=np.uint8, scan=64, comm_hook="bf16_ef",
        )
    except Exception as e:
        log(f"comm-hook bench failed: {type(e).__name__}: {e}")

    try:
        # the managed path on the compute-bound flagship:
        # must land within ~5% of the native s2d scan-fused row
        bench_managed_alexnet(steps=96, fuse=32)
    except Exception as e:
        log(f"managed alexnet bench failed: {type(e).__name__}: {e}")

    for deferred, fuse in ((False, 1), (True, 1), (True, 32)):
        try:
            # eager mode syncs per batch (that IS its cost — quirk Q5 parity),
            # so 60 steps suffice; deferred modes fetch once per run, so they
            # time 256 steps — the same steps-per-fetch as the native per-step
            # config they are compared against (fence amortization parity)
            bench_managed(deferred=deferred, fuse=fuse, steps=256 if deferred else 60)
        except Exception as e:
            log(f"managed bench failed: {type(e).__name__}: {e}")

    try:
        bench_managed_eval(batches=256, fused=False)
        bench_managed_eval(batches=256, fused=True)
    except Exception as e:
        log(f"managed eval bench failed: {type(e).__name__}: {e}")

    try:
        # the async-pipeline A/B rows ride every full bench too, so each
        # BENCH_r artifact records the loader-fed wall/device pair
        bench_pipeline_pair()
    except Exception as e:
        log(f"pipeline A/B bench failed: {type(e).__name__}: {e}")

    baseline = bench_torch_cpu()
    # LAST stdout line: the compact machine-readable summary (the driver
    # parses exactly this line; the full per-config dict went to
    # bench_results.json inside emit_summary). Strict JSON: non-finite
    # values serialize as null, never a bare NaN token.
    from tpuddp.observability import json_sanitize

    print(
        json.dumps(
            json_sanitize(emit_summary(ours, baseline, out_path=out_path)),
            allow_nan=False,
        ),
        flush=True,
    )


if __name__ == "__main__":
    from tpuddp.utils import compile_cache

    compile_cache.enable()
    main()
