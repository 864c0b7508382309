"""From a profiler capture to numbers: per device plane the busy union and
idle share, operation time by bucket, collective time and how much of it no
compute hides, the operations that took most time, and the longest idle gaps
with what the host was doing in each.

Reads the trace-viewer JSON the JAX profiler writes beside its ``.xplane.pb``
(``<dir>/plugins/profile/<time>/<host>.trace.json.gz``): one process per
device ("/device:TPU:0"), on it a thread "XLA Ops" whose complete events
(``ph == "X"``, ``ts`` and ``dur`` in microseconds) are the operations that
ran, with ``args`` naming their source line, framework op and output shape;
and the host's threads, on which ``jax.profiler.TraceAnnotation`` spans land
under their own names on the same clock.

The loader and the bucket rules are a copy of ``tools/trace_breakdown.py``
(PR 22 took it; the original is listed for deletion in PERF.md). That tool
summed durations; this one also takes the union of the intervals, so it has
an idle share, and knows collectives.

Conventions. An operation that encloses others on its own thread (the
``while`` of a scan, a ``conditional``) is a container and is left out: its
children are the work. Busy is the union of the remaining operations,
collectives included: a device waiting inside an all-reduce counts as busy,
and ``collective_exposed_s`` says how much of that was not covered by
compute. An asynchronous collective is the whole interval from its
``-start`` to the end of its ``-done``.
"""

from __future__ import annotations

import collections
import glob
import gzip
import json
import os
import re

ANNOTATION_PREFIX = "bench:"
WINDOW_ANNOTATION = ANNOTATION_PREFIX + "window"
DEVICE_THREAD = "XLA Ops"

BUCKETS = (
    "weight-grad + optimizer (fused)",
    "fwd/input-grad conv+matmul",
    "collective",
    "augment/resize",
    "copies/slices",
    "other elementwise",
)

_COLLECTIVE = re.compile(
    r"^(all-reduce|reduce-scatter|all-gather|collective-permute|all-to-all"
    r"|collective-broadcast)(-start|-done)?(\.|$)"
)
_SHAPE_TOKEN = re.compile(r"\b(?:f32|bf16|f16)\[[\d,]+\]")


class TraceError(Exception):
    """The capture cannot be reduced (no file, no device plane, no window)."""


def find_capture(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.trace.json.gz"), recursive=True))
    if not files:
        raise TraceError(f"no *.trace.json.gz under {trace_dir}")
    return files[-1]


def load_events(path: str) -> list:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as fh:
        events = json.load(fh).get("traceEvents")
    if not isinstance(events, list):
        raise TraceError(f"{path} has no traceEvents list")
    return [e for e in events if isinstance(e, dict)]


# -- the copied bucket rules -------------------------------------------------

def _looks_like_optimizer_update(shape_with_layout: str, param_shapes=None) -> bool:
    """An output tuple that repeats one weight shape three times or more is a
    fused stateful-optimizer update: Adam's (new parameter, m, v) riding on
    the weight-gradient product. One float32 master with two or more
    lower-precision moments of the same shape is the bf16-moment signature.

    Two limits the original did not have. Only a shape of two or more
    dimensions counts, a matrix or a kernel: a bias or a norm's scale is a
    few kB, and XLA hangs its update on whatever large fusion is near. On
    ResNet-50 that put the input-gradient convolutions and BatchNorm
    backward passes that carry a 256-float Adam update into this bucket, 60%
    of device time (chip run, PR 22). And ``param_shapes`` (the model's
    parameter shapes as ``"11,11,3,64"`` strings), where given, keeps the
    rule to shapes that are weights and not activations."""
    if not shape_with_layout.startswith("("):
        return False
    by = collections.Counter()
    for token in _SHAPE_TOKEN.findall(shape_with_layout):
        dtype, shape = token.split("[", 1)
        dims = shape.rstrip("]")
        if "," in dims and (param_shapes is None or dims in param_shapes):
            by[(dtype, shape)] += 1
    if any(c >= 3 for c in by.values()):
        return True
    return any(
        dtype != "f32" and c >= 2 and by.get(("f32", shape), 0) >= 1
        for (dtype, shape), c in by.items()
    )


def _collective_match(event):
    """A collective by its name (``all-reduce.114``) or, where the compiler
    named the instruction after the JAX primitive (``psum.161``), by the
    ``hlo_category`` the profiler gives it."""
    category = (event.get("args") or {}).get("hlo_category") or ""
    return _COLLECTIVE.match(event.get("name") or "") or _COLLECTIVE.match(category)


def is_collective(event) -> bool:
    return bool(_collective_match(event))


def categorize(event, param_shapes=None) -> str:
    args = event.get("args") or {}
    src, tf_op = args.get("source") or "", args.get("tf_op") or ""
    name = event.get("name") or ""
    if is_collective(event):
        return "collective"
    if "transforms.py" in src or "_resize" in tf_op:
        return "augment/resize"
    if "optim" in src or _looks_like_optimizer_update(
        args.get("shape_with_layout") or "", param_shapes
    ):
        # these fused operations hold BOTH the weight-gradient product and
        # the optimizer's state update
        return "weight-grad + optimizer (fused)"
    if "conv" in tf_op or "dot_general" in tf_op:
        return "fwd/input-grad conv+matmul"
    if "copy" in name or "slice" in name:
        return "copies/slices"
    return "other elementwise"


# -- intervals ---------------------------------------------------------------

def union(intervals) -> list:
    """Sorted, disjoint ``(start, end)`` covering the same points."""
    out = []
    for start, end in sorted(i for i in intervals if i[1] > i[0]):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        else:
            out.append((start, end))
    return out


def total(intervals) -> float:
    return float(sum(end - start for start, end in intervals))


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if min(e, hi) > max(s, lo)]


def subtract(a, b) -> list:
    """The part of disjoint sorted ``a`` that disjoint sorted ``b`` does not
    cover."""
    out, j = [], 0
    for start, end in a:
        cur = start
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < end:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < end:
            out.append((cur, end))
    return out


def _leaves(ops) -> list:
    """Operations that enclose no other operation of their thread."""
    ops = sorted(ops, key=lambda e: (e["ts"], -e["dur"]))
    leaves = []
    for e, nxt in zip(ops, ops[1:] + [None]):
        end = e["ts"] + e["dur"]
        if nxt and nxt["ts"] < end and nxt["ts"] + nxt["dur"] <= end and nxt["dur"] < e["dur"]:
            continue  # a container: the while of a scan, a conditional
        leaves.append(e)
    return leaves


def _collective_spans(leaves) -> list:
    """One interval per collective: a synchronous one is its own event, an
    asynchronous one runs from its ``-start`` to the end of its ``-done``."""
    spans, open_starts = [], {}
    for e in leaves:
        m = _collective_match(e)
        if not m:
            continue
        key = e["name"].replace("-start", "").replace("-done", "")
        if m.group(2) == "-start":
            open_starts[key] = e["ts"]
        elif m.group(2) == "-done" and key in open_starts:
            spans.append((open_starts.pop(key), e["ts"] + e["dur"]))
        else:
            spans.append((e["ts"], e["ts"] + e["dur"]))
    return spans


# -- the reduction -----------------------------------------------------------

def _index(events):
    process, thread = {}, {}
    for e in events:
        if e.get("ph") != "M":
            continue
        args = e.get("args") or {}
        if e.get("name") == "process_name":
            process[e.get("pid")] = args.get("name") or ""
        elif e.get("name") == "thread_name":
            thread[(e.get("pid"), e.get("tid"))] = args.get("name") or ""
    return process, thread


def _annotation(e) -> str:
    """The name a ``TraceAnnotation`` was given. The trace viewer shows what
    follows the last colon as ``name`` and keeps the whole in ``long_name``."""
    return (e.get("args") or {}).get("long_name") or e.get("name") or ""


def _complete(e) -> bool:
    return (
        e.get("ph") == "X"
        and isinstance(e.get("ts"), (int, float))
        and isinstance(e.get("dur"), (int, float))
    )


def reduce_events(events, param_shapes=None, top: int = 10, gaps: int = 5) -> dict:
    """Reduce one capture. Times in the result are seconds."""
    process, thread = _index(events)
    device_pids = sorted(
        pid for pid, name in process.items() if "TPU" in name or "GPU" in name
    )
    if not device_pids:
        raise TraceError("the capture has no device plane (no process named TPU or GPU)")

    annotations = sorted(
        (e["ts"], e["ts"] + e["dur"], _annotation(e)[len(ANNOTATION_PREFIX):])
        for e in events
        if _complete(e) and e.get("pid") not in device_pids
        and _annotation(e).startswith(ANNOTATION_PREFIX)
    )
    windows = [(s, t) for s, t, n in annotations if ANNOTATION_PREFIX + n == WINDOW_ANNOTATION]
    if not windows:
        raise TraceError(f"the capture has no {WINDOW_ANNOTATION!r} annotation")
    lo, hi = windows[0]
    # the innermost annotation that covers an instant says what the host did
    inner = [a for a in annotations if ANNOTATION_PREFIX + a[2] != WINDOW_ANNOTATION]

    def host_activity(start, end):
        mid = (start + end) / 2
        covering = [a for a in inner if a[0] <= mid < a[1]]
        if not covering:
            return "none"
        return min(covering, key=lambda a: a[1] - a[0])[2]

    planes = {}
    for pid in device_pids:
        ops = [
            e for e in events
            if _complete(e) and e.get("pid") == pid
            and thread.get((pid, e.get("tid"))) == DEVICE_THREAD
        ]
        ops = [e for e in ops if e["ts"] + e["dur"] > lo and e["ts"] < hi]
        leaves = _leaves(ops)
        if not leaves:
            continue
        busy = clip(union((e["ts"], e["ts"] + e["dur"]) for e in leaves), lo, hi)
        compute = clip(
            union((e["ts"], e["ts"] + e["dur"]) for e in leaves if not is_collective(e)),
            lo, hi,
        )
        coll = clip(union(_collective_spans(leaves)), lo, hi)
        buckets = collections.Counter()
        by_name = collections.Counter()
        for e in leaves:
            buckets[categorize(e, param_shapes)] += e["dur"]
            by_name[e["name"]] += e["dur"]
        idle = subtract([(lo, hi)], busy)
        longest = sorted(idle, key=lambda g: g[0] - g[1])[:gaps]
        by_activity = collections.Counter()
        for s, t in idle:
            by_activity[host_activity(s, t)] += t - s
        planes[process[pid]] = {
            "busy_s": total(busy) / 1e6,
            "op_s": sum(e["dur"] for e in leaves) / 1e6,
            "n_ops": len(leaves),
            "buckets_s": {b: buckets.get(b, 0) / 1e6 for b in BUCKETS},
            "collective_s": total(coll) / 1e6,
            "collective_exposed_s": total(subtract(coll, compute)) / 1e6,
            "top_ops": [[n, d / 1e6] for n, d in by_name.most_common(top)],
            "longest_gaps": [[host_activity(s, t), (t - s) / 1e6] for s, t in longest],
            "idle_by_host_activity_s": {
                n: d / 1e6 for n, d in by_activity.most_common()
            },
        }
    if not planes:
        on_device = [
            e for e in events if _complete(e) and e.get("pid") in device_pids
            and thread.get((e.get("pid"), e.get("tid"))) == DEVICE_THREAD
        ]
        span = (
            f"{min(e['ts'] for e in on_device):.0f}..{max(e['ts'] + e['dur'] for e in on_device):.0f}"
            if on_device else "none"
        )
        raise TraceError(
            f"no operation ran on any device inside the window {lo:.0f}..{hi:.0f} us "
            f"({len(on_device)} device operations in the capture, at {span}; "
            f"{len(events)} events in all)"
        )
    window_s = (hi - lo) / 1e6
    busy_s = sum(p["busy_s"] for p in planes.values()) / len(planes)
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_pct": 100.0 * (1.0 - busy_s / window_s),
        "first_plane": sorted(planes)[0],  # the one chip per-step metrics read
        "planes": planes,
    }


def first_plane(reduced: dict) -> dict:
    return reduced["planes"][reduced["first_plane"]]


def reduce_capture(capture: str, param_shapes=None) -> dict:
    """Reduce a capture directory's newest trace file, or one such file."""
    path = capture if os.path.isfile(capture) else find_capture(capture)
    return reduce_events(load_events(path), param_shapes)


def breakdown(reduced: dict, limit: int = 10) -> dict:
    """The ``breakdown`` of a traced run's result line, two lists of
    ``[name, seconds]`` with at most ``limit`` entries each, from the first
    chip's plane. ``device_ops``: the buckets that took any time, as
    ``bucket:<name>``, then the single operations that took most, under the
    names the trace gives them. ``idle_gaps``: the five longest gaps, each
    under what the host was doing in it, then all idle time summed by that
    host activity, as ``sum:<activity>``."""
    plane = first_plane(reduced)
    buckets = sorted(
        ([f"bucket:{b}", s] for b, s in plane["buckets_s"].items() if s > 0),
        key=lambda entry: -entry[1],
    )
    sums = [[f"sum:{n}", s] for n, s in plane["idle_by_host_activity_s"].items()]
    gaps = plane["longest_gaps"][: limit // 2]
    return {
        "device_ops": (buckets + [list(op) for op in plane["top_ops"]])[:limit],
        "idle_gaps": (gaps + sums)[:limit],
    }


if __name__ == "__main__":
    import sys

    print(json.dumps(reduce_capture(sys.argv[1]), indent=1))
