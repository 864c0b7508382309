#!/usr/bin/env python
"""loadgen — closed/open-loop load generator for the tpuddp serving engine.

Produces the latency-vs-offered-throughput curve that makes a serving stack
measurable: a closed-loop phase finds the engine's sustainable peak, then
open-loop phases replay fixed offered rates (fractions of that peak) and
record what clients would actually experience — end-to-end p50/p95/p99,
achieved throughput, batch occupancy, rejects. ``vs_baseline`` anchors
against sequential per-request serving (one request in flight, no
coalescing — the no-continuous-batching strawman, measured through the same
engine so queue costs land on both sides of the ratio); the raw batch=1
direct-dispatch rate is reported alongside as the device ceiling.

Artifacts:

- ``--out``         — the curve in the ``bench_results.json`` payload format
  (validated by ``tools/tpuddp_inspect.py --validate``; each offered-load
  point is one row under ``configs``);
- ``--history-dir`` — the engine's own ``history.jsonl`` (run_meta +
  serving_stats windows + drain event), same validation;
- stdout            — progress on stderr-like log lines, and the LAST line
  is one compact JSON summary (the driver-parseable contract).

Runs entirely in-process on the local mesh (CPU-friendly: the gate's serving
leg drives ~100 requests against 2 replicas over 2 tenants); the same flags
scale the sweep up on real chips.

``--decode`` switches to the TOKEN-level engine (tpuddp/serving/decode/):
the curve becomes tokens/sec + time-to-first-token vs offered request rate,
and ``vs_baseline`` anchors against request-level SEQUENTIAL decode (one
sequence in flight, no continuous batching — the regime the decode engine
exists to beat). Rows carry ``tokens_per_sec`` instead of
``samples_per_sec_per_chip``.

Usage:
    python tools/loadgen.py --quick --history-dir /tmp/serve \\
        --out /tmp/serve/bench_results.json
    python tools/loadgen.py --decode --quick --history-dir /tmp/decode
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


def log(msg: str) -> None:
    print(f"[loadgen] {msg}", flush=True)


def _make_requests(rng, n, rows_max, sample_shape):
    """Pre-generate request payloads so generation cost never pollutes the
    timed phases."""
    return [
        rng.randn(int(rng.randint(1, rows_max + 1)), *sample_shape).astype(
            np.float32
        )
        for _ in range(n)
    ]


def _pct(values, keys=(50, 95, 99)):
    from tpuddp.observability import percentiles

    return {
        k: (None if v is None else round(v, 3))
        for k, v in percentiles(values, keys).items()
    }


def closed_loop(engine, payloads, tenants, workers):
    """Every worker keeps exactly one request in flight (submit -> wait ->
    repeat): the classic saturation probe. Returns (e2e_ms list, wall_s)."""
    from tpuddp.serving import AdmissionError

    lock = threading.Lock()
    cursor = {"i": 0}
    e2e_ms = []

    def run(worker_idx):
        while True:
            with lock:
                i = cursor["i"]
                if i >= len(payloads):
                    return
                cursor["i"] = i + 1
            t0 = time.perf_counter()
            try:
                res = engine.submit(f"tenant{i % tenants}", payloads[i])
            except AdmissionError:
                continue  # counted by engine stats; keep probing
            res.result(timeout=120)
            with lock:
                e2e_ms.append((res.done_at - t0) * 1e3)

    threads = [
        threading.Thread(target=run, args=(w,), daemon=True)
        for w in range(workers)
    ]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return e2e_ms, time.perf_counter() - t0


def open_loop(engine, payloads, tenants, offered_rps):
    """Fixed-rate arrivals regardless of completions (the honest overload
    probe: a closed loop self-throttles, an open loop does not). Returns
    (e2e_ms of completed, rejected count, wall_s)."""
    from tpuddp.serving import AdmissionError

    interval = 1.0 / offered_rps
    inflight = []
    rejected = 0
    t_start = time.perf_counter()
    for i, x in enumerate(payloads):
        target = t_start + i * interval
        delay = target - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        t_submit = time.perf_counter()
        try:
            inflight.append((t_submit, engine.submit(f"tenant{i % tenants}", x)))
        except AdmissionError:
            rejected += 1
    e2e_ms = []
    for t_submit, res in inflight:
        res.result(timeout=120)
        e2e_ms.append((res.done_at - t_submit) * 1e3)
    return e2e_ms, rejected, time.perf_counter() - t_start


def raw_dispatch_rate(engine, payloads_1row, steps):
    """Raw device ceiling: one replica, batch=1, direct ``infer`` calls with
    no queue/thread machinery at all — the context figure that separates
    engine overhead from device time in the report."""
    replica = engine.pool.replicas[0]
    t0 = time.perf_counter()
    for x in payloads_1row[:steps]:
        np.asarray(replica.infer(x))
    dt = time.perf_counter() - t0
    return steps / dt


def _decode_prompts(rng, n, max_prompt, vocab):
    return [
        rng.randint(0, vocab, size=int(rng.randint(1, max_prompt + 1))).astype(
            np.int32
        )
        for _ in range(n)
    ]


class _occupancy_peak:
    """Context manager sampling ``engine.kv_occupancy()`` on a background
    thread while the phase runs — the loop drains every sequence before
    returning, so a post-hoc read always sees an EMPTY pool (0.0), never
    the pressure the phase actually applied. Enter yields a zero-arg
    callable returning the max observed so far."""

    def __init__(self, engine, interval_s: float = 0.005):
        self._engine = engine
        self._interval = interval_s
        self._peak = 0.0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)

    def _run(self):
        while not self._stop.is_set():
            self._peak = max(self._peak, self._engine.kv_occupancy())
            self._stop.wait(self._interval)

    def __enter__(self):
        self._thread.start()
        return lambda: self._peak

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join(timeout=10)
        return False


def decode_closed_loop(engine, prompts, tenants, workers):
    """Workers each keep one SEQUENCE in flight (submit -> stream to the
    end -> repeat). Returns (completed count, wall_s)."""
    from tpuddp.serving import AdmissionError

    lock = threading.Lock()
    cursor = {"i": 0, "done": 0}

    def run(_w):
        while True:
            with lock:
                i = cursor["i"]
                if i >= len(prompts):
                    return
                cursor["i"] = i + 1
            try:
                res = engine.submit(f"tenant{i % tenants}", prompts[i])
            except AdmissionError:
                continue
            res.result(timeout=300)
            with lock:
                cursor["done"] += 1

    threads = [
        threading.Thread(target=run, args=(w,), daemon=True)
        for w in range(workers)
    ]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return cursor["done"], time.perf_counter() - t0


def decode_open_loop(engine, prompts, tenants, offered_rps):
    """Fixed-rate sequence arrivals; returns (completed, rejected, wall_s)."""
    from tpuddp.serving import AdmissionError

    interval = 1.0 / offered_rps
    inflight = []
    rejected = 0
    t_start = time.perf_counter()
    for i, p in enumerate(prompts):
        target = t_start + i * interval
        delay = target - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        try:
            inflight.append(engine.submit(f"tenant{i % tenants}", p))
        except AdmissionError:
            rejected += 1
    for res in inflight:
        res.result(timeout=300)
    return len(inflight), rejected, time.perf_counter() - t_start


def _decode_row(name, mode, d, offered_rps=None, **extra):
    """One bench-format row from a DecodeStats.since delta: the token-rate
    family (tokens_per_sec + TTFT/ITL) instead of samples/sec/chip."""
    return {
        name: {
            "mode": mode,
            "offered_rps": offered_rps,
            "achieved_rps": round(d["completed"] / max(d["wall_s"], 1e-9), 2),
            "requests": d["submitted"],
            "completed": d["completed"],
            "rejected": d["rejected"],
            "tokens": d["tokens"],
            "tokens_per_sec": d["tokens_per_sec"],
            **{f"ttft_ms_{k}": v for k, v in d["ttft_ms"].items()
               if k in ("p50", "p95", "p99")},
            **{f"itl_ms_{k}": v for k, v in d["itl_ms"].items()
               if k in ("p50", "p95", "p99")},
            # the decode path's "step" is one token: ITL p50 is its ms/step
            "ms_per_step": d["itl_ms"]["p50"],
            **extra,
        }
    }


def _decode_chaos_phase(engine, rng, max_prompt, configs) -> int:
    """The serving-chaos proof (README "Serving survivability", the full
    gate's serving-chaos leg): kill a replica MID-SWEEP through the real
    ``$TPUDDP_FAULT`` env contract and require the survivability layer's
    headline — zero lost streams, every stream BITWISE-equal to its
    undisturbed same-seed twin, the replica back in routing after
    probation — plus the deadline-shedding contract (an expired queued
    request is rejected typed, never dispatched). Returns 0 on pass; on
    failure logs FATAL and returns 1 (the caller fails the run)."""
    from tpuddp.resilience import faults
    from tpuddp.serving import AdmissionError

    n_sessions = min(6, 2 * engine.replicas[0].cache.max_slots)
    prompts = _decode_prompts(rng, n_sessions, max_prompt, engine.vocab_size)
    # undisturbed twins first: same seeds, same temperature-sampled stream
    twins = [
        np.asarray(
            engine.submit("chaos", p, seed=900 + i, temperature=0.9)
            .result(timeout=300)
        )
        for i, p in enumerate(prompts)
    ]
    # arm a replica kill a few decode steps ahead via the env contract the
    # chaos suite documents (tools/run_chaos.py). The engine's fault-site
    # step counter has advanced exactly once per executed decode step, and
    # the pool is idle right now — so "current total + 3" lands mid-sweep.
    steps_now = sum(r.steps for r in engine.replicas)
    prev = os.environ.get("TPUDDP_FAULT")
    os.environ["TPUDDP_FAULT"] = f"replica_kill@step={steps_now + 3}"
    faults.reload_faults()
    m = engine.stats.mark()
    try:
        results = [
            engine.submit("chaos", p, seed=900 + i, temperature=0.9)
            for i, p in enumerate(prompts)
        ]
        outs = [np.asarray(r.result(timeout=300)) for r in results]
        fired = all(s.fired for s in faults.active_faults())
    finally:
        if prev is None:
            os.environ.pop("TPUDDP_FAULT", None)
        else:
            os.environ["TPUDDP_FAULT"] = prev
        faults.reload_faults()
    if not fired:
        log("FATAL: chaos phase finished without the replica_kill firing")
        return 1
    for i, (out, twin) in enumerate(zip(outs, twins)):
        if not np.array_equal(out, twin):
            log(f"FATAL: stream {i} diverged from its undisturbed twin "
                "after failover")
            return 1
    # deadline shedding: an already-expired queued request must be shed
    # with the typed verdict before it can cost a prefill
    doomed = engine.submit("chaos", prompts[0], deadline_s=0.0)
    try:
        doomed.result(timeout=60)
        log("FATAL: an expired queued request was served, not shed")
        return 1
    except AdmissionError as e:
        if e.reason != "deadline_exceeded":
            log(f"FATAL: shed rejection carried reason {e.reason!r}, not "
                "deadline_exceeded")
            return 1
    d = engine.stats.since(m)
    if d["failovers"] < 1:
        log("FATAL: the kill fired but no session_failover was recorded")
        return 1
    if not all(r.healthy for r in engine.replicas):
        log("FATAL: a replica is still out of routing after probation")
        return 1
    configs.update(_decode_row(
        "chaos_failover", "chaos", d,
        fault=f"replica_kill@step={steps_now + 3}",
        sessions=n_sessions,
        failovers=d["failovers"],
        shed=d["shed"],
        bitwise_equal=True,
        replicas_healthy=sum(1 for r in engine.replicas if r.healthy),
    ))
    log(
        f"chaos: replica_kill mid-sweep -> {d['failovers']} session "
        f"failover(s), {n_sessions}/{n_sessions} streams bitwise-equal to "
        f"their undisturbed twins, {d['shed']} expired request(s) shed "
        "typed, replica back in routing after probation"
    )
    return 0


def run_decode(args) -> int:
    """The --decode sweep: tokens/sec + TTFT vs offered sequence rate, with
    request-level sequential decode as the vs_baseline anchor."""
    from tpuddp import config as config_lib
    from tpuddp.observability import json_sanitize
    from tpuddp.parallel.backend import resolve_devices
    from tpuddp.serving.decode import DecodeEngine

    settings = (
        config_lib.load_settings(args.settings) if args.settings else {}
    )
    serving = config_lib.serving_config(settings)
    cfg = config_lib.decode_config(serving) or dict(config_lib.DECODE_DEFAULTS)
    if args.model:
        cfg["model"] = args.model
    if args.replicas:
        cfg["num_replicas"] = args.replicas
    n_per_load = args.requests
    if args.quick:
        # CI sizing: tiny vocab/model state, short generations, ~100
        # sequences across calibration + 3 open points
        n_per_load = 24
        cfg.update(
            vocab_size=min(int(cfg["vocab_size"]), 64),
            max_slots=min(int(cfg["max_slots"]), 4),
            max_seq_len=min(int(cfg["max_seq_len"]), 64),
            max_new_tokens=min(int(cfg["max_new_tokens"]), 8),
            stats_window=32,
        )

    observability = None
    if args.exporter is not None:
        observability = {"exporter": True, "exporter_port": args.exporter}
    engine = DecodeEngine.from_config(
        cfg, out_dir=args.history_dir, observability=observability,
        devices=resolve_devices(backend=config_lib.device_from(settings)),
    )
    log(
        f"decode engine: model={cfg['model']} replicas={len(engine.replicas)} "
        f"max_slots={cfg['max_slots']} kv={cfg['kv_blocks']}x"
        f"{cfg['kv_block_size']} prefill_buckets={engine.buckets}"
    )
    engine.start()
    if engine.exporter is not None:
        log(f"exporter: /metrics on {engine.exporter.host}:{engine.exporter.port}")

    rng = np.random.RandomState(args.seed)
    max_prompt = min(16, engine.max_prompt_len)
    configs = {}

    # -- correctness proof before any timing: a sequence decoded inside a
    # full concurrent batch must be BITWISE the sequence decoded alone —
    # continuous batching and KV paging are numerically invisible
    probe = _decode_prompts(rng, 1 + int(cfg["max_slots"]), max_prompt,
                            engine.vocab_size)
    solo = engine.submit("verify", probe[0], seed=123).result(timeout=300)
    crowd = [engine.submit("verify", p, seed=123) for p in probe]
    packed = crowd[0].result(timeout=300)
    for r in crowd[1:]:
        r.result(timeout=300)
    if not np.array_equal(solo, packed):
        log("FATAL: batched decode diverged from single-sequence decode")
        return 1
    log("verified: batched decode bitwise-equal to single-sequence decode")

    # -- baseline: request-level SEQUENTIAL decode (one sequence in flight,
    # the no-continuous-batching strawman) through the same engine
    # one-sequence-in-flight decode is the slowest phase of the sweep: cap
    # it in the full run (the quick sizing is already tiny) — 64 sequences
    # is plenty of signal for a tokens/sec anchor
    base_n = n_per_load if args.quick else min(n_per_load, 64)
    base_prompts = _decode_prompts(rng, base_n, max_prompt, engine.vocab_size)
    m = engine.stats.mark()
    decode_closed_loop(engine, base_prompts, args.tenants, workers=1)
    d_base = engine.stats.since(m)
    base_tps = d_base["tokens_per_sec"]
    configs.update(_decode_row("sequential_baseline", "sequential", d_base))
    log(
        f"baseline (sequential, 1 sequence in flight): {base_tps:,.1f} "
        f"tokens/s, TTFT p50 {d_base['ttft_ms']['p50']} ms"
    )

    # -- closed loop: saturate the slots, find the peak token rate
    workers = args.workers or 2 * int(cfg["max_slots"]) * len(engine.replicas)
    prompts = _decode_prompts(rng, n_per_load, max_prompt, engine.vocab_size)
    m = engine.stats.mark()
    with _occupancy_peak(engine) as kv_peak:
        done, wall = decode_closed_loop(engine, prompts, args.tenants, workers)
    d = engine.stats.since(m)
    peak_tps = d["tokens_per_sec"]
    peak_rps = done / max(wall, 1e-9)
    configs.update(_decode_row(
        "closed_loop", "closed", d, workers=workers,
        kv_occupancy_peak=round(kv_peak(), 4),
    ))
    log(
        f"closed loop ({workers} workers): {peak_tps:,.1f} tokens/s "
        f"({peak_rps:,.1f} seq/s), TTFT p50 {d['ttft_ms']['p50']} ms, "
        f"ITL p50 {d['itl_ms']['p50']} ms"
    )

    # -- open loop: TTFT/ITL vs offered sequence rate
    fractions = [float(f) for f in args.loads.split(",") if f.strip()]
    for frac in fractions:
        offered = max(0.5, peak_rps * frac)
        prompts = _decode_prompts(rng, n_per_load, max_prompt, engine.vocab_size)
        m = engine.stats.mark()
        _, rejected, _ = decode_open_loop(engine, prompts, args.tenants, offered)
        d = engine.stats.since(m)
        name = f"open_{frac:g}x"
        configs.update(_decode_row(
            name, "open", d,
            offered_rps=round(offered, 2),
            offered_fraction_of_peak=frac,
        ))
        log(
            f"open loop {frac:g}x ({offered:,.1f} seq/s offered): "
            f"{d['tokens_per_sec']:,.1f} tokens/s, TTFT p50 "
            f"{d['ttft_ms']['p50']} ms, ITL p99 {d['itl_ms']['p99']} ms, "
            f"rejected {rejected}"
        )

    if args.chaos:
        rc = _decode_chaos_phase(engine, rng, max_prompt, configs)
        if rc:
            engine.drain(reason="loadgen_chaos_failed")
            return rc

    summary = engine.drain(reason="loadgen_complete")

    import jax

    device_kind = jax.devices()[0].device_kind
    vs = peak_tps / base_tps if base_tps else 1.0
    payload = {
        "metric": f"decode_{cfg['model']}_tokens_per_sec",
        "value": round(peak_tps, 1),
        "unit": "tokens/sec",
        "vs_baseline": round(vs, 2),
        "vs_baseline_basis": "request-level sequential decode (1 sequence in flight)",
        "baseline_tokens_per_sec": round(base_tps, 2),
        "device": device_kind,
        "tenants": args.tenants,
        "replicas": len(engine.replicas),
        "max_slots": int(cfg["max_slots"]),
        "kv_blocks": int(cfg["kv_blocks"]),
        "kv_block_size": int(cfg["kv_block_size"]),
        "max_new_tokens": int(cfg["max_new_tokens"]),
        "configs": configs,
    }
    out_path = args.out or (
        os.path.join(args.history_dir, "bench_results.json")
        if args.history_dir
        else os.path.join(_REPO, "bench_results.json")
    )
    os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(json_sanitize(payload), f, indent=2, allow_nan=False)
        f.write("\n")
    log(f"token curve -> {out_path}")
    if args.history_dir:
        log(f"history -> {os.path.join(args.history_dir, 'history.jsonl')}")

    print(json.dumps(json_sanitize({
        "metric": payload["metric"],
        "value": payload["value"],
        "unit": payload["unit"],
        "vs_baseline": payload["vs_baseline"],
        "device": device_kind,
        "n_configs": len(configs),
        "submitted": summary["submitted"],
        "completed": summary["completed"],
        "tokens": summary["tokens"],
        "rejected": sum(summary["rejected"].values()),
        "shed": summary["shed"],
        "failovers": summary["failovers"],
        "results_file": os.path.basename(out_path),
    }), allow_nan=False))
    return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[1])
    parser.add_argument("--settings", default=None,
                        help="YAML settings file (serving block)")
    parser.add_argument("--model", default=None, help="override serving.model")
    parser.add_argument("--replicas", type=int, default=2)
    parser.add_argument("--tenants", type=int, default=2)
    parser.add_argument("--max-batch", type=int, default=None)
    parser.add_argument("--requests", type=int, default=300,
                        help="requests per load point")
    parser.add_argument("--rows-max", type=int, default=4,
                        help="rows per request drawn uniform from [1, rows_max]")
    parser.add_argument("--loads", default="0.5,0.75,1.0",
                        help="open-loop offered rates as fractions of the "
                        "closed-loop peak (comma separated)")
    parser.add_argument("--workers", type=int, default=None,
                        help="closed-loop concurrency (default 4 x replicas)")
    parser.add_argument("--history-dir", default=None,
                        help="engine history.jsonl destination")
    parser.add_argument("--out", default=None,
                        help="bench-format curve destination "
                        "(default: <history-dir>/bench_results.json)")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--quick", action="store_true",
                        help="CI sizing: ~100 requests total, tiny model")
    parser.add_argument("--decode", action="store_true",
                        help="token-level decode sweep (tokens/sec + TTFT "
                        "curves against the serving.decode engine)")
    parser.add_argument("--chaos", action="store_true",
                        help="(--decode only) append the serving-chaos "
                        "proof: kill a replica mid-sweep via $TPUDDP_FAULT "
                        "and require zero lost streams, bitwise-equal "
                        "continuations, typed deadline shedding, and the "
                        "replica back after probation")
    parser.add_argument("--exporter", nargs="?", const=0, default=None,
                        type=int, metavar="PORT",
                        help="serve the live /metrics endpoint during the "
                        "run (PORT omitted or 0 = ephemeral; the bound port "
                        "lands in <history-dir>/exporter.port)")
    args = parser.parse_args(argv)

    if args.chaos and not args.decode:
        parser.error("--chaos requires --decode (the serving-chaos proof "
                     "runs against the token-level engine)")
    if args.decode:
        return run_decode(args)

    from tpuddp import config as config_lib
    from tpuddp.observability import json_sanitize
    from tpuddp.parallel.backend import resolve_devices
    from tpuddp.serving import ServingEngine

    settings = (
        config_lib.load_settings(args.settings) if args.settings else {}
    )
    cfg = config_lib.serving_config(settings)
    if args.model:
        cfg["model"] = args.model
    if args.replicas:
        cfg["num_replicas"] = args.replicas
    if args.max_batch:
        cfg["max_batch_size"] = args.max_batch
    n_per_load = args.requests
    if args.quick:
        n_per_load = 34  # 3 open points -> ~100 requests + calibration
        cfg["max_batch_size"] = min(int(cfg["max_batch_size"]), 8)
        cfg["stats_window"] = 16

    observability = None
    if args.exporter is not None:
        observability = {"exporter": True, "exporter_port": args.exporter}
    engine = ServingEngine.from_config(
        cfg, out_dir=args.history_dir, observability=observability,
        devices=resolve_devices(backend=config_lib.device_from(settings)),
    )
    log(
        f"engine: model={cfg['model']} replicas={len(engine.pool)} "
        f"max_batch={engine.scheduler.max_batch_size} "
        f"buckets={engine.scheduler.buckets} tenants={args.tenants}"
    )
    engine.start()  # warms every bucket program on every replica
    if engine.exporter is not None:
        log(f"exporter: /metrics on {engine.exporter.host}:{engine.exporter.port}")

    rng = np.random.RandomState(args.seed)
    shape = engine.pool.sample_shape
    rows_max = max(1, min(args.rows_max, engine.scheduler.max_batch_size))
    configs = {}

    # -- correctness proof before any timing: served logits must be bitwise
    # a direct model forward over the same padded batch (params passed as
    # arguments, exactly like the replica's own program)
    import jax

    from tpuddp.nn.core import Context
    from tpuddp.utils import batching

    module = engine.pool.module
    r0 = engine.pool.replicas[0]

    @jax.jit
    def _direct(p, s, x):
        ctx = Context(train=False, rng=jax.random.key(0), axis_name=None)
        return module.apply(p, s, x, ctx)[0]

    for rows in sorted({1, rows_max, engine.scheduler.max_batch_size}):
        x = rng.randn(rows, *shape).astype(np.float32)
        served = engine.submit("verify", x).result(timeout=120)
        xp, _, _ = batching.pad_batch(
            x, None, batching.bucket_for(rows, engine.scheduler.max_batch_size)
        )
        ref = np.asarray(_direct(r0.params, r0.model_state, xp))[:rows]
        if not np.array_equal(served, ref):
            log(f"FATAL: served logits diverge from direct forward at "
                f"rows={rows}")
            return 1
    log("verified: served logits bitwise-equal direct forward "
        f"(rows in {sorted({1, rows_max, engine.scheduler.max_batch_size})})")

    # -- baseline: sequential per-request serving (the strawman a server
    # WITHOUT continuous batching is: one request in flight, no coalescing,
    # every request its own dispatch) — through the engine, so queue/thread
    # costs land on both sides of the ratio honestly
    ones = _make_requests(rng, 64, 1, shape)
    baseline_steps = 32 if args.quick else 128
    raw_dispatch_rate(engine, ones, 8)  # warm the (1,...) program path
    raw_rps = raw_dispatch_rate(engine, ones, min(baseline_steps, len(ones)))
    base_n = min(n_per_load, 64) if args.quick else n_per_load
    base_payloads = _make_requests(rng, base_n, rows_max, shape)
    # a per-request server would not linger hoping to coalesce — zero the
    # batch timeout for the baseline phase so the ratio measures continuous
    # batching, not the engine's own linger penalty charged to the strawman
    linger = engine.scheduler.batch_timeout_s
    engine.scheduler.batch_timeout_s = 0.0
    try:
        base_e2e, base_wall = closed_loop(engine, base_payloads, args.tenants, 1)
    finally:
        engine.scheduler.batch_timeout_s = linger
    base_rps = len(base_e2e) / max(base_wall, 1e-9)
    log(
        f"baseline (sequential per-request serving): {base_rps:,.1f} req/s "
        f"(raw single-dispatch ceiling {raw_rps:,.0f}/s)"
    )

    # -- closed loop: find the sustainable peak -----------------------------
    workers = args.workers or 4 * len(engine.pool)
    payloads = _make_requests(rng, n_per_load, rows_max, shape)
    m = engine.stats.mark()
    e2e, wall = closed_loop(engine, payloads, args.tenants, workers)
    d = engine.stats.since(m)
    peak_rps = len(e2e) / wall if wall else 0.0
    configs["closed_loop"] = {
        "mode": "closed",
        "workers": workers,
        "offered_rps": None,
        "achieved_rps": round(peak_rps, 2),
        "requests": len(payloads),
        "completed": len(e2e),
        "rejected": d["rejected"],
        **{f"e2e_ms_{k}": v for k, v in _pct(e2e).items()
           if k in ("p50", "p95", "p99")},
        "queue_ms_p50": d["queue_ms"]["p50"],
        "batch_occupancy": d["batch_occupancy"],
        "samples_per_sec_per_chip": round(
            d["rows"] / max(wall, 1e-9) / len(engine.pool), 2
        ),
        "ms_per_step": d["device_ms"]["p50"],
    }
    log(
        f"closed loop ({workers} workers): {peak_rps:,.1f} req/s, "
        f"p99 {configs['closed_loop']['e2e_ms_p99']} ms, "
        f"occupancy {d['batch_occupancy']}"
    )

    # -- open loop: the latency-vs-offered-throughput curve -----------------
    fractions = [float(f) for f in args.loads.split(",") if f.strip()]
    for frac in fractions:
        offered = max(1.0, peak_rps * frac)
        payloads = _make_requests(rng, n_per_load, rows_max, shape)
        m = engine.stats.mark()
        e2e, rejected, wall = open_loop(engine, payloads, args.tenants, offered)
        d = engine.stats.since(m)
        name = f"open_{frac:g}x"
        configs[name] = {
            "mode": "open",
            "offered_fraction_of_peak": frac,
            "offered_rps": round(offered, 2),
            "achieved_rps": round(len(e2e) / max(wall, 1e-9), 2),
            "requests": len(payloads),
            "completed": len(e2e),
            "rejected": rejected,
            **{f"e2e_ms_{k}": v for k, v in _pct(e2e).items()
               if k in ("p50", "p95", "p99")},
            "queue_ms_p50": d["queue_ms"]["p50"],
            "batch_occupancy": d["batch_occupancy"],
            "samples_per_sec_per_chip": round(
                d["rows"] / max(wall, 1e-9) / len(engine.pool), 2
            ),
            "ms_per_step": d["device_ms"]["p50"],
        }
        log(
            f"open loop {frac:g}x ({offered:,.1f} req/s offered): "
            f"achieved {configs[name]['achieved_rps']:,.1f} req/s, "
            f"p50 {configs[name]['e2e_ms_p50']} ms, "
            f"p99 {configs[name]['e2e_ms_p99']} ms, rejected {rejected}"
        )

    summary = engine.drain(reason="loadgen_complete")

    # -- bench-format artifact ----------------------------------------------
    import jax

    device_kind = jax.devices()[0].device_kind
    vs = peak_rps / base_rps if base_rps else 1.0
    payload = {
        "metric": f"serving_{cfg['model']}_peak_requests_per_sec",
        "value": round(peak_rps, 1),
        "unit": "requests/sec",
        "vs_baseline": round(vs, 2),
        "vs_baseline_basis": "sequential per-request serving (1 in flight)",
        "baseline_rps": round(base_rps, 2),
        "raw_single_dispatch_rps": round(raw_rps, 2),
        "device": device_kind,
        "tenants": args.tenants,
        "replicas": len(engine.pool),
        "max_batch_size": engine.scheduler.max_batch_size,
        "rows_max": rows_max,
        "configs": configs,
    }
    out_path = args.out or (
        os.path.join(args.history_dir, "bench_results.json")
        if args.history_dir
        else os.path.join(_REPO, "bench_results.json")
    )
    os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(json_sanitize(payload), f, indent=2, allow_nan=False)
        f.write("\n")
    log(f"curve -> {out_path}")
    if args.history_dir:
        log(f"history -> {os.path.join(args.history_dir, 'history.jsonl')}")

    # last stdout line: compact driver-parseable summary
    print(json.dumps(json_sanitize({
        "metric": payload["metric"],
        "value": payload["value"],
        "unit": payload["unit"],
        "vs_baseline": payload["vs_baseline"],
        "device": device_kind,
        "n_configs": len(configs),
        "completed": summary["completed"],
        "rejected": sum(summary["rejected"].values()),
        "results_file": os.path.basename(out_path),
    }), allow_nan=False))
    return 0


if __name__ == "__main__":
    from tpuddp.utils import compile_cache

    compile_cache.enable()
    sys.exit(main())
