"""One run of one benchmark cell.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Loads the cell's files by name (``benchmark/cells.py``), builds the system on
the cell's chips, warms up the cell's own programs, measures a window that
opens and closes on a value fetch, reads the peak memory, checks the system
against the plain reference, and prints one JSON object as the last line of
its standard output. Without ``--trace`` the metrics are the cell's
end-to-end metrics; with it, a short window runs under the profiler and the
metrics are the cell's per-layer metrics. No accelerator, fewer chips than
the cell asks for, or a device kind without a published peak: a non-zero
exit and no result line.
"""

from __future__ import annotations

import time

_T_START = time.perf_counter()  # before the imports: they are set-up too

import argparse
import json
import math
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import cells  # noqa: E402
from benchmark.cells import BenchmarkError  # noqa: E402

# what JAX's monitoring calls the stages of making a program runnable
_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
    "/jax/compilation_cache/cache_retrieval_time_sec",
)
_LOWERED = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"


class CompileMonitor:
    """Counts and times the programs JAX makes runnable, from its own
    monitoring events; ``lowered`` counts every program met for the first
    time in this process, whether compiled or read from the cache."""

    def __init__(self):
        from jax import monitoring

        self.seconds = 0.0
        self.by_event = {}
        self.timed = []
        self.lowered = 0
        self.cache_hits = 0
        self.cache_misses = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, name, seconds, **info):
        if name in _COMPILE_EVENTS:
            self.seconds += seconds
            self.by_event[name] = self.by_event.get(name, 0.0) + seconds
            self.timed.append((seconds, name.rsplit("/", 1)[-1], info.get("fun_name")))
        if name == _LOWERED:
            self.lowered += 1

    def slowest(self, n: int = 6) -> list:
        return sorted(self.timed, reverse=True)[:n]

    def _on_event(self, name, **_):
        if name == _CACHE_HIT:
            self.cache_hits += 1
        elif name == _CACHE_MISS:
            self.cache_misses += 1


def require_devices(chips: int, root: str):
    """The cell's chips and their published peaks, or an error."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise BenchmarkError(f"JAX found no device: {e}") from e
    if devices[0].platform == "cpu":
        raise BenchmarkError("JAX found no accelerator (platform 'cpu'); the benchmark runs on the chip only")
    if len(devices) < chips:
        raise BenchmarkError(f"the cell needs {chips} chip(s), JAX found {len(devices)}")
    return devices[:chips], cells.load_peaks(devices[0].device_kind, root)


def make_mesh(cell, devices):
    """The mesh the cell's traffic file lays out, over the cell's chips."""
    from tpuddp.parallel import make_mesh as program_mesh

    axes = {k: int(v) for k, v in cell.traffic["mesh"].items()}
    if math.prod(axes.values()) != cell.chips:
        raise BenchmarkError(
            f"traffic {cell.traffic_name!r} lays out a mesh {axes} but the "
            f"cell has {cell.chips} chip(s)"
        )
    return program_mesh(list(devices)[: cell.chips], axes)


def peak_memory_bytes(devices) -> int:
    """Peak bytes on the fullest of the cell's chips, as the runtime counts
    them: ``peak_bytes_in_use`` is the arrays (state, data, results) and
    ``peak_bytes_reserved`` what the runtime set aside for the loaded
    programs' own scratch (the activations a step keeps for its backward
    pass live there, not in the first figure)."""
    peaks = []
    for d in devices:
        stats = d.memory_stats()
        if not stats or "peak_bytes_in_use" not in stats:
            raise BenchmarkError(f"device {d} reports no peak_bytes_in_use")
        peaks.append(
            int(stats["peak_bytes_in_use"]) + int(stats.get("peak_bytes_reserved", 0))
        )
    return max(peaks)


def _say(**fields) -> None:
    print(json.dumps(fields), file=sys.stderr, flush=True)


def run_cell(workload: str, seed: int, seconds: float, trace: bool, root: str = ROOT) -> dict:
    cell = cells.load_cell(workload, root)
    devices, peaks = require_devices(cell.chips, root)

    import jax

    from benchmark import check, spans as spans_lib, trace_reduce
    from tpuddp.utils import compile_cache

    # the program's own cache directory (inside the checkout, or where
    # $JAX_COMPILATION_CACHE_DIR says); every program is kept, however fast
    # it compiled, so that every run after a checkout's first compiles nothing
    cache_dir = compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    monitor = CompileMonitor()

    phases, mark = {}, [_T_START]

    def phase_done(name):
        now = time.perf_counter()
        phases[name], mark[0] = now - mark[0], now

    phase_done("imports_and_devices")
    spans = spans_lib.Spans()
    system = cells.load_system(cell)
    mesh = make_mesh(cell, devices)
    model, ddp = system.build_ddp(cell, mesh)
    state = system.init_state(model, ddp, cell.config, seed)
    jax.block_until_ready(state)
    phase_done("model_and_state")
    feed = cells.load_module("feeds", cell.traffic["feed"], root).Feed(cell, ddp, seed, spans)
    feed.setup()
    phase_done("data")
    state = feed.warm(state)
    jax.block_until_ready(state)
    phase_done("warm_up")
    setup = {
        "compile_s": monitor.seconds,
        "compile_by_event": dict(monitor.by_event),
        "slowest": monitor.slowest(),
        "programs": monitor.lowered,
        "cache_hits": monitor.cache_hits,
        "cache_misses": monitor.cache_misses,
        "cache_dir": cache_dir,
        "phases_s": phases,
    }
    spans.reset()

    trace_dir = os.path.join(root, ".bench_out", workload, "trace")
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir)
        seconds = min(seconds, float(cell.traffic.get("trace_seconds", seconds)))
        options = jax.profiler.ProfileOptions()
        # the device's timeline only: what the host did is in the harness's
        # own spans, and the host tracer distorts what it watches (spans.py)
        options.python_tracer_level = 0
        options.host_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    lowered_before = monitor.lowered
    setup_s = time.perf_counter() - _T_START
    try:
        with spans.span(spans_lib.WINDOW):
            state, window = feed.measure(state, seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
    memory_peak = peak_memory_bytes(devices)
    memory_stats = devices[0].memory_stats()
    compiles_in_window = monitor.lowered - lowered_before
    param_shapes = {
        ",".join(str(d) for d in leaf.shape)
        for leaf in jax.tree_util.tree_leaves(state.params)
    }
    skipped = state.skipped_steps
    del state

    flops_per_sample = cells.load_module(
        "flops", cell.config_name, root
    ).train_flops_per_sample(cell.config)
    per_chip = window["samples"] / window["window_s"] / cell.chips
    mfu = per_chip * flops_per_sample / peaks["bf16_flops_per_s"]

    reference = check.against_reference(cell, mesh, seed, feed)
    learned = check.window_losses(window)
    non_finite_steps = learned["non_finite"] * window["steps_per_readback"]
    failed = non_finite_steps + (int(skipped["total"]) if skipped is not None else 0)
    correct = bool(reference["ok"] and learned["ok"] and compiles_in_window == 0)

    events = reduced = None
    if trace:
        # one reading of the capture, the host spans beside it, serves both reductions
        spans.save(os.path.join(
            os.path.dirname(trace_reduce.find_capture(trace_dir)), trace_reduce.HOST_SPANS_FILE
        ))
        events = trace_reduce.capture_events(trace_dir)
        reduced = trace_reduce.reduce_events(events, param_shapes)
    run = {
        "cell": cell, "window": window, "setup": setup, "trace": reduced, "events": events,
        "spans": {"seconds": dict(spans.seconds), "counts": dict(spans.counts)},
        "counters": {"grad_comm_bytes_per_step": ddp.grad_comm_bytes_per_step},
        "flops_per_sample": flops_per_sample, "peaks": peaks,
    }
    if trace:
        metrics = {}
        for entry in cell.per_layer:
            value = cells.load_module("layer_metrics", entry["name"], root).read(run)
            if value is not None:
                metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}
    else:
        values = {
            "samples_per_s_per_chip": per_chip,
            "peak_hbm_gb": memory_peak / 1e9,
            "setup_s": setup_s,
        }
        metrics = {
            e["name"]: {"value": values[e["name"]], "unit": e["unit"]}
            for e in cell.end_to_end
        }

    device = {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices), "memory_peak_bytes": memory_peak,
    }
    _say(
        workload=workload, seed=seed, trace=trace, window_s=window["window_s"],
        steps=window["steps"], samples=window["samples"],
        samples_per_s_per_chip=per_chip, mfu_pct=100 * mfu,
        flops_per_sample=flops_per_sample, setup_s=setup_s, setup=setup,
        compiles_in_window=compiles_in_window, learned=learned, reference=reference,
        overlap=ddp.comm_overlap_meta, counters=window["counters"],
        spans=run["spans"], memory_peak_bytes=memory_peak, memory_stats=memory_stats,
    )
    unit = cell.config["sample_unit"]  # what the step counts with weight 1
    print(
        f"{workload} seed {seed}: {per_chip:.1f} {unit}s/s/chip, MFU {100 * mfu:.2f}% "
        f"of bf16 peak ({flops_per_sample:.4g} analytic FLOPs a {unit}), "
        f"{window['steps']} steps in {window['window_s']:.2f} s",
        flush=True,
    )
    result = {
        "correct": correct, "attempted": window["steps"], "failed": failed,
        "metrics": metrics, "device": device,
    }
    if trace:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = trace_reduce.breakdown(reduced)
    return result


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    except BenchmarkError as e:
        print(f"benchmark/run.py: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
