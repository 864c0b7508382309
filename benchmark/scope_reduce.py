"""From a profiler capture to device time by the program's own names: by
phase of the training step, and by model layer, forward and backward.

The program opens ``jax.named_scope``s while a step is traced
(``tpuddp/observability/profiling.py``): a phase scope ``tpuddp.<phase>``
round each part of the step and, under ``tpuddp.forward``, one scope per
model layer (``3_Conv2d``, ``12_Bottleneck/conv2``). XLA keeps the scope path
as every operation's ``op_name``, and the capture shows it as ``args.tf_op``:

    jit(multi)/while/body/closed_call/transpose(jvp(tpuddp.forward))/3_Conv2d/conv_general_dilated:

The backward pass has no scope of its own; JAX writes it as the
``transpose(...)`` of the forward's. A fusion carries ONE name, its root's:
XLA fuses Adam's update into the weight-gradient product, which then counts
as backward, so the ``update`` figures here are what XLA left unfused
(``optimizer_share_pct``'s bucket holds both, by a shape rule).

The prefix and the phase names below are this file's own copy, on purpose: an
edit of the program's vocabulary must not move the yardstick silently. The
window, the device thread and the rule for what is a leaf operation are
``trace_reduce``'s, imported.

    python3 -m benchmark.scope_reduce <capture dir or file> [--steps N]
        [--flops <config> --batch <samples a step on the chip>]
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import re
import sys

from benchmark import cells
from benchmark import trace_reduce as tr

SCOPE_PREFIX = "tpuddp."
PHASE_SCOPES = (
    "augment", "forward", "loss", "buffers", "exchange", "clip", "guard",
    "optimizer", "metrics",
)
UPDATE_SCOPES = ("clip", "guard", "optimizer")
# what an operation can be counted as: a phase scope, or one of these
BACKWARD, RECOMPUTE, OTHER, UNSCOPED = "backward", "recompute", "other_scoped", "unscoped"
PHASES = PHASE_SCOPES + (BACKWARD, RECOMPUTE, OTHER, UNSCOPED)
NO_LAYER = "(no layer scope)"

_REMAT = "rematted_computation"
_NOT_LAYER = ("checkpoint", _REMAT)
_PRODUCT_LAYER = re.compile(r"conv|linear", re.IGNORECASE)

NO_SCOPE_REASON = (
    "the capture carries no '" + SCOPE_PREFIX + "' scope in any device "
    "operation's tf_op: either the program that ran was built before the "
    "scopes existed, or its executables came from a compile cache an older "
    "build wrote (JAX leaves names out of the cache key, so a cached program "
    "keeps the names it was compiled with); the scope metrics are absent, "
    "not zero"
)


def _scope_of(part: str):
    """The phase a path component names (``transpose(jvp(tpuddp.forward))``
    -> ``forward``), or ``None`` for a component with no scope of ours."""
    if SCOPE_PREFIX not in part:
        return None
    return part.split(SCOPE_PREFIX, 1)[1].strip(")")


def attribute(tf_op: str):
    """``(phase, layer)`` of one operation from its ``tf_op``. ``layer`` is
    the path of layer scopes under ``tpuddp.forward`` (forward, backward and
    recompute alike), ``None`` for every other phase."""
    parts = tf_op.rstrip(":").split("/")
    scopes = [_scope_of(p) for p in parts]
    first = next((i for i, name in enumerate(scopes) if name is not None), None)
    if first is None:
        return UNSCOPED, None
    if scopes[first] not in PHASE_SCOPES:
        return OTHER, None
    if scopes[first] != "forward":
        return scopes[first], None
    last_forward = max(i for i, name in enumerate(scopes) if name == "forward")
    layer = "/".join(
        p.strip(")") for p in parts[last_forward + 1:-1]
        if "(" not in p and p.strip(")") not in _NOT_LAYER
    ) or NO_LAYER
    if _REMAT in parts:
        return RECOMPUTE, layer
    if any("transpose(" in p for p in parts[:first + 1]):
        return BACKWARD, layer
    return "forward", layer


def first_plane_leaves(events) -> list:
    """The leaf operations of the first chip's plane inside the
    ``bench:window`` annotation: the operations ``trace_reduce`` sums into
    that plane's ``op_s``."""
    process, thread = tr._index(events)
    device_pids = {pid: name for pid, name in process.items() if tr._is_device(name)}
    if not device_pids:
        raise tr.TraceError("the capture has no device plane (no process named TPU or GPU)")
    windows = sorted(
        (e["ts"], e["ts"] + e["dur"]) for e in events
        if tr._complete(e) and e.get("pid") not in device_pids
        and tr._annotation(e) == tr.WINDOW_ANNOTATION
    )
    if not windows:
        raise tr.TraceError(f"the capture has no {tr.WINDOW_ANNOTATION!r} annotation")
    lo, hi = windows[0]
    for pid in sorted(device_pids, key=device_pids.get):
        ops = [
            e for e in events
            if tr._complete(e) and e.get("pid") == pid
            and thread.get((pid, e.get("tid"))) == tr.DEVICE_THREAD
            and e["ts"] + e["dur"] > lo and e["ts"] < hi
        ]
        leaves = tr._leaves(ops)
        if leaves:
            return leaves
    raise tr.TraceError("no operation ran on any device inside the window")


def reduce_events(events, top: int = 10) -> dict:
    """Device-operation seconds of the first chip's plane by phase and by
    model layer. ``scoped`` says whether any operation carried a scope at
    all; where none did the phases are meaningless and callers report
    nothing (:data:`NO_SCOPE_REASON`)."""
    phases = collections.Counter()
    layers = collections.defaultdict(collections.Counter)
    unscoped = collections.Counter()
    for e in first_plane_leaves(events):
        phase, layer = attribute((e.get("args") or {}).get("tf_op") or "")
        phases[phase] += e["dur"]
        if layer is not None:
            layers[layer][phase] += e["dur"]
        if phase == UNSCOPED:
            unscoped[e.get("name") or ""] += e["dur"]
    op_us = sum(phases.values())
    return {
        "op_s": op_us / 1e6,
        "scoped": op_us > phases[UNSCOPED],
        "phases_s": {p: phases.get(p, 0) / 1e6 for p in PHASES},
        "layers_s": {
            layer: {p: by.get(p, 0) / 1e6 for p in ("forward", BACKWARD, RECOMPUTE)}
            for layer, by in sorted(layers.items(), key=lambda kv: _model_order(kv[0]))
        },
        "top_unscoped": [[n, d / 1e6] for n, d in unscoped.most_common(top)],
    }


def reduce_capture(capture: str) -> dict:
    return reduce_events(tr.capture_events(capture))


def phase_seconds(reduced: dict, *phases: str) -> float:
    return sum(reduced["phases_s"][p] for p in phases)


def _model_order(layer: str):
    head = layer.split("/", 1)[0].split("_", 1)[0]
    return (int(head) if head.isdigit() else 1 << 30, layer)


def achieved_tflops(reduced: dict, products, samples: float) -> dict:
    """Per convolution / matrix-product layer, forward and backward achieved
    TFLOP/s: the analytic operations of ``products`` (``benchmark/flops/
    <config>.products``: ``(macs, needs_input_grad)`` in model order) for
    ``samples`` samples, over the layer's device seconds. A layer whose
    product XLA fused into a neighbour's operation shows no time of its own
    and reads ``None``."""
    layers = [name for name in reduced["layers_s"] if _PRODUCT_LAYER.search(name.rsplit("/", 1)[-1])]
    if len(layers) != len(products):
        raise tr.TraceError(
            f"the capture names {len(layers)} convolution/matrix-product layers, "
            f"the configuration has {len(products)} products"
        )
    out = {}
    for name, (macs, needs_dx) in zip(layers, products):
        seconds = reduced["layers_s"][name]
        flops = {"forward": 2.0 * macs * samples, BACKWARD: 2.0 * macs * samples * (2 if needs_dx else 1)}
        out[name] = {
            p: (flops[p] / seconds[p] / 1e12 if seconds[p] else None) for p in flops
        }
    return out


# -- one reduction a run, shared by the five readers ---------------------------

_KEY = "scope_reduce"


def for_run(run: dict):
    """The reduction of a traced run's capture, made once and kept on
    ``run``; ``None`` where there is nothing to read (no traced window, or
    a capture whose operations carry no scope: the reason goes to stderr,
    once). The first call prints both tables as one JSON line on stderr."""
    if _KEY in run:
        return run[_KEY]
    run[_KEY] = None
    if run.get("trace") is None:
        return None
    try:
        reduced = reduce_events(run["events"])
    except tr.TraceError as e:
        print(f"benchmark/scope_reduce.py: {e}", file=sys.stderr, flush=True)
        return None
    if not reduced["scoped"]:
        print(f"benchmark/scope_reduce.py: {NO_SCOPE_REASON}", file=sys.stderr, flush=True)
        return None
    print(json.dumps({"scope_reduce": reduced}), file=sys.stderr, flush=True)
    run[_KEY] = reduced
    return reduced


def ms_per_step(run: dict, *phases: str):
    """What the four ``*_ms_per_step`` readers return: the named phases'
    device milliseconds a step, or ``None``."""
    reduced = for_run(run)
    if reduced is None or not run["window"]["steps"]:
        return None
    return 1e3 * phase_seconds(reduced, *phases) / run["window"]["steps"]


# -- the command ---------------------------------------------------------------

def _products(config_name: str):
    entry = next(
        (c for c in cells.load_benchmark()["configs"] if c["name"] == config_name), None
    )
    if entry is None:
        raise cells.BenchmarkError(f"no configuration {config_name!r} in BENCHMARK.json")
    config = cells._read_json(os.path.join(cells.ROOT, entry["file"]))
    return cells.load_module("flops", config_name).products(config)


def _table(rows, header) -> str:
    rows = [header] + [[f"{c:.3f}" if isinstance(c, float) else str(c) for c in r] for r in rows]
    widths = [max(len(r[i]) for r in rows) for i in range(len(header))]
    return "\n".join("  ".join(c.ljust(w) for c, w in zip(r, widths)).rstrip() for r in rows)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("capture", help="a capture directory or a *.trace.json.gz file")
    parser.add_argument("--steps", type=int, default=1, help="steps in the window (times are then a step)")
    parser.add_argument("--flops", metavar="CONFIG", help="a configuration of BENCHMARK.json, for TFLOP/s per layer")
    parser.add_argument("--batch", type=int, help="samples a step on the chip (with --flops)")
    args = parser.parse_args(argv)
    if args.flops and not args.batch:
        parser.error("--flops needs --batch")
    try:
        reduced = reduce_capture(args.capture)
        if not reduced["scoped"]:
            raise tr.TraceError(NO_SCOPE_REASON)
    except tr.TraceError as e:
        print(f"benchmark/scope_reduce.py: {e}", file=sys.stderr)
        return 2
    tflops = None
    if args.flops:
        try:
            tflops = achieved_tflops(
                reduced, _products(args.flops), args.steps * args.batch
            )
        except (tr.TraceError, cells.BenchmarkError) as e:
            print(f"benchmark/scope_reduce.py: no TFLOP/s: {e}", file=sys.stderr)
    ms = 1e3 / args.steps
    total = reduced["op_s"]
    print(_table(
        [[p, s * ms, 100 * s / total] for p, s in reduced["phases_s"].items() if s]
        + [["update = clip + guard + optimizer", phase_seconds(reduced, *UPDATE_SCOPES) * ms,
            100 * phase_seconds(reduced, *UPDATE_SCOPES) / total],
           ["all device operations", total * ms, 100.0]],
        ["phase", "ms" if args.steps == 1 else "ms/step", "% of device-op time"],
    ))
    print()
    rows = []
    for layer, s in reduced["layers_s"].items():
        row = [layer, s["forward"] * ms, s[BACKWARD] * ms, s[RECOMPUTE] * ms]
        if tflops is not None:
            t = tflops.get(layer, {})
            row += [t.get("forward") or "", t.get(BACKWARD) or ""]
        rows.append(row)
    header = ["layer", "forward", "backward", "recompute"]
    print(_table(rows, header + (["fwd TFLOP/s", "bwd TFLOP/s"] if tflops is not None else [])))
    if reduced["top_unscoped"]:
        print()
        print(_table([[n, s * ms] for n, s in reduced["top_unscoped"]], ["unscoped operation", "ms"]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
