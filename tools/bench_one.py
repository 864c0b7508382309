"""Focused single-config bench: AlexNet b128 bf16-opt s2d scan-fused (K=16),
with optional jax.profiler trace. Mirrors bench.py's methodology."""
import argparse, os, sys, time
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

p = argparse.ArgumentParser()
p.add_argument("--trace", default=None)
p.add_argument("--batch", type=int, default=128)
p.add_argument("--scan", type=int, default=16)
p.add_argument("--steps", type=int, default=96)
p.add_argument("--model", default="alexnet_s2d")
p.add_argument("--size", type=int, default=224)
p.add_argument("--opt-dtype", default="bfloat16")
p.add_argument("--remat", action="store_true")
args = p.parse_args()

import jax, jax.numpy as jnp
from tpuddp import nn, optim
from tpuddp.models import load_model
from tpuddp.data.transforms import make_train_augment
from tpuddp.observability.recorder import PEAK_FLOPS
from tpuddp.parallel import make_mesh
from tpuddp.parallel.ddp import DistributedDataParallel
from tpuddp.training.step import stack_batches
from tpuddp.utils import compile_cache

compile_cache.enable()
devices = jax.devices()
kind = devices[0].device_kind
if kind not in PEAK_FLOPS:
    sys.exit(
        f"bench_one: no peak FLOP/s for device kind {kind!r} (platform "
        f"{devices[0].platform}); known kinds: {sorted(PEAK_FLOPS)}"
    )
PEAK = PEAK_FLOPS[kind]

model = load_model(args.model, 10)
augment = make_train_augment(size=args.size if args.size else None, compute_dtype=jnp.bfloat16)
mesh = make_mesh(devices)
opt = optim.Adam(1e-3, state_dtype=args.opt_dtype or None)
ddp = DistributedDataParallel(model, opt, nn.CrossEntropyLoss(), mesh=mesh,
                              mode="shard_map", augment=augment, remat=args.remat)
in_shape = (32, 32, 3)
model_in = augment(jax.random.key(0), jnp.zeros((1,) + in_shape, np.uint8)).shape[1:]
state = ddp.init_state(jax.random.key(0), jnp.zeros((1,) + tuple(model_in)))

rng = np.random.RandomState(0)
gb = args.batch * len(devices)
x = rng.randint(0, 256, (gb,) + in_shape).astype(np.uint8)
y = rng.randint(0, 10, gb).astype(np.int32)
w = np.ones(gb, np.float32)
batch = ddp.shard((x, y, w))
stacked = ddp.shard_stacked(stack_batches([tuple(np.asarray(b) for b in batch)] * args.scan))

state_box = [state]
def run(steps):
    outer = max(1, steps // args.scan)
    m = None
    for _ in range(outer):
        state_box[0], m = ddp.train_step_many(state_box[0], stacked)
    loss = float(np.sum(np.asarray(m["loss_sum"])))
    assert np.isfinite(loss)
    return outer * args.scan

run(args.scan); run(args.scan)

# flops probe
def program_flops(jitted, *a):
    try:
        c = jitted.lower(*a).compile().cost_analysis()
        if isinstance(c, (list, tuple)): c = c[0]
        f = float(c.get("flops", 0.0))
        return f if f > 0 else None
    except Exception as e:
        print("cost fail", e, file=sys.stderr); return None

bx, by, bw = batch
f_single = program_flops(jax.jit(lambda s,a,b,c: ddp.train_step(s,(a,b,c))), state_box[0], bx, by, bw)

if args.trace:
    jax.profiler.start_trace(args.trace)
t0 = time.perf_counter()
steps = run(args.steps)
dt = time.perf_counter() - t0
if args.trace:
    jax.profiler.stop_trace()
ms = dt / steps * 1e3
mfu = f_single / (ms / 1e3) / PEAK if f_single else float("nan")
print(f"{kind} x{len(devices)} {args.model} b{args.batch} K={args.scan}: {steps*args.batch/dt:,.0f} samples/s  {ms:.3f} ms/step  MFU {100*mfu:.2f}%")
