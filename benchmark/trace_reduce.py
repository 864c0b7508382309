"""From a profiler capture to numbers: per device plane the busy union and
idle share, operation time by bucket, collective time and how much of it no
compute hides, the operations that took most time, and the longest idle gaps
with what the host was doing in each.

Reads the ``.xplane.pb`` the JAX profiler writes
(``<dir>/plugins/profile/<time>/<host>.xplane.pb``): one plane per device
("/device:TPU:0"), on it a line "XLA Ops" whose events are the operations
that ran, their metadata naming source line, framework op and output shape.
What the host did comes from the harness's own spans, saved beside the file
(``host_spans.json``) on the wall clock, which is the clock the profile
starts on; they become ``bench:<span>`` annotations on the device's timeline.
The profiler's host tracer is off, and a host plane is never read: with it on,
one staged chunk of a loader-fed cell logs millions of "Transpose" slices,
which filled the trace viewer's million-event ``<host>.trace.json.gz`` before
its first device row (PR 22) and made the staging itself seven times slower
(PR 26). That JSON is read only where there is no ``.xplane.pb``, which is how
the cut fixtures under ``benchmark/tests/data/`` are kept, annotations and
all. Either way the result is one list of events (:func:`capture_events`),
the one both reductions read.

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
# beside a capture's file: the harness's own host spans, [name, start, end] in
# nanoseconds of the wall clock (``spans.Spans.save``)
HOST_SPANS_FILE = "host_spans.json"
PROFILE_START_EVENT = "profile_start_ns"
HOST_PROCESS = "/host:benchmark spans"

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
    """The newest capture under ``trace_dir``: the profiler's own file, or
    the trace viewer's where there is no other."""
    for pattern in ("*.xplane.pb", "*.trace.json.gz"):
        files = sorted(glob.glob(os.path.join(trace_dir, "**", pattern), recursive=True))
        if files:
            return files[-1]
    raise TraceError(f"no *.xplane.pb or *.trace.json.gz under {trace_dir}")


def _is_device(plane_name: str) -> bool:
    return "TPU" in plane_name or "GPU" in plane_name


# The profiler's file is one ``XSpace`` message (tsl/profiler/protobuf/
# xplane.proto) in protobuf's wire format. ``jax.profiler.ProfileData`` reads
# it too, but shows of an event only its own stats, not its metadata's, and
# an operation's framework op, source line, output shape and short name are
# its metadata's. So the few fields the reductions need are decoded here,
# with no schema beyond these field numbers:
_SPACE_PLANES = 1
_PLANE_NAME, _PLANE_LINES, _PLANE_EVENT_METADATA, _PLANE_STAT_METADATA, _PLANE_STATS = 2, 3, 4, 5, 6
_LINE_NAME, _LINE_TIMESTAMP_NS, _LINE_EVENTS = 2, 3, 4
_EVENT_METADATA_ID, _EVENT_OFFSET_PS, _EVENT_DURATION_PS = 1, 2, 3
_METADATA_NAME, _METADATA_DISPLAY_NAME, _METADATA_STATS = 2, 4, 5
_STAT_METADATA_ID, _STAT_UINT64, _STAT_STR, _STAT_REF = 1, 3, 5, 7
_MAP_KEY, _MAP_VALUE = 1, 2
_ENVIRONMENT_PLANE, _PROFILE_START = "Task Environment", "profile_start_time"


def _varint(buf, pos: int):
    value = shift = 0
    while True:
        byte = buf[pos]
        pos += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, pos
        shift += 7


def _fields(buf, pos: int, end: int):
    """``(field number, value)`` of the message in ``buf[pos:end]``: a varint
    as an int, a length-delimited field as its ``(start, end)``; fixed-width
    fields (doubles) are passed over."""
    while pos < end:
        key, pos = _varint(buf, pos)
        wire = key & 7
        if wire == 0:
            value, pos = _varint(buf, pos)
        elif wire == 2:
            size, pos = _varint(buf, pos)
            value, pos = (pos, pos + size), pos + size
            if pos > end:
                raise TraceError(f"a field runs over its message's end at byte {end}: not an XSpace")
        elif wire in (1, 5):
            value, pos = None, pos + (8 if wire == 1 else 4)
        else:
            raise TraceError(f"wire type {wire} at byte {pos}: not an XSpace")
        yield key >> 3, value
    if pos != end:
        raise TraceError(f"a message runs over its end at byte {end}: not an XSpace")


def _text(buf, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _map_entry(buf, span):
    entry = dict(_fields(buf, *span))
    return entry.get(_MAP_KEY), entry.get(_MAP_VALUE)


def _stats(buf, spans, stat_names) -> dict:
    """The string-valued stats among ``spans`` by name; a reference is the
    name of the stat metadata it points at."""
    out = {}
    for span in spans:
        stat = dict(_fields(buf, *span))
        name = stat_names.get(stat.get(_STAT_METADATA_ID))
        text = _text(buf, stat[_STAT_STR]) if _STAT_STR in stat else stat_names.get(stat.get(_STAT_REF))
        if name is not None and text is not None:
            out[name] = text
    return out


def _plane_events(buf, span, pid: int) -> list:
    """The "XLA Ops" line of a device plane as events; of the plane that
    carries it, the wall-clock time every other time in the file counts
    from, as one metadata event; of any other plane nothing."""
    name, lines, metadata, stat_names, plane_stats = "", [], {}, {}, []
    for field, v in _fields(buf, *span):
        if field == _PLANE_NAME:
            name = _text(buf, v)
        elif field == _PLANE_LINES:
            lines.append(v)
        elif field == _PLANE_EVENT_METADATA:
            key, value = _map_entry(buf, v)
            metadata[key] = value
        elif field == _PLANE_STAT_METADATA:
            key, value = _map_entry(buf, v)
            stat_names[key] = next(
                (_text(buf, f) for n, f in _fields(buf, *value) if n == _METADATA_NAME), ""
            )
        elif field == _PLANE_STATS:
            plane_stats.append(v)
    if name == _ENVIRONMENT_PLANE:
        for stat in plane_stats:
            found = dict(_fields(buf, *stat))
            if stat_names.get(found.get(_STAT_METADATA_ID)) == _PROFILE_START:
                return [{"ph": "M", "name": PROFILE_START_EVENT,
                         "args": {"ns": found.get(_STAT_UINT64, 0)}}]
    if not _is_device(name):
        return []  # with the host tracer on, millions of slices: passed over whole

    described = {}

    def describe(found: int):
        """``(name, args)`` of an event metadata as the trace viewer shows
        them: the short name where there is one, with the whole as
        ``long_name``, and the metadata's string stats."""
        full = short = ""
        stats = []
        for field, v in _fields(buf, *metadata[found]):
            if field == _METADATA_NAME:
                full = _text(buf, v)
            elif field == _METADATA_DISPLAY_NAME:
                short = _text(buf, v)
            elif field == _METADATA_STATS:
                stats.append(v)
        args = _stats(buf, stats, stat_names)
        if short and short != full:
            args["long_name"] = full
        described[found] = short or full, args
        return described[found]

    events = [{"ph": "M", "name": "process_name", "pid": pid, "args": {"name": name}}]
    for tid, line_span in enumerate(lines):
        line_name, t0_ns, spans = "", 0, []
        for field, v in _fields(buf, *line_span):
            if field == _LINE_EVENTS:
                spans.append(v)
            elif field == _LINE_NAME:
                line_name = _text(buf, v)
            elif field == _LINE_TIMESTAMP_NS:
                t0_ns = v
        if line_name != DEVICE_THREAD:
            continue
        events.append({"ph": "M", "name": "thread_name", "pid": pid, "tid": tid,
                       "args": {"name": line_name}})
        for span in spans:
            found = dict(_fields(buf, *span))
            shown, args = described.get(found[_EVENT_METADATA_ID]) or describe(found[_EVENT_METADATA_ID])
            events.append({
                "ph": "X", "pid": pid, "tid": tid, "name": shown,
                "ts": t0_ns / 1e3 + found.get(_EVENT_OFFSET_PS, 0) / 1e6,
                "dur": found.get(_EVENT_DURATION_PS, 0) / 1e6, "args": args,
            })
    return events


def _xplane_events(path: str) -> list:
    """The device operations of an ``.xplane.pb`` in the trace viewer's shape
    (``ph``, ``pid``, ``tid``, ``name``, ``ts`` and ``dur`` in microseconds
    from the profile's start, ``args``), and the profile's start."""
    with open(path, "rb") as fh:
        buf = fh.read()
    events = []
    try:
        for pid, (field, span) in enumerate(_fields(buf, 0, len(buf))):
            if field == _SPACE_PLANES:
                events.extend(_plane_events(buf, span, pid))
    except (IndexError, TypeError, KeyError) as e:  # a message cut short, a field of another type
        raise TraceError(f"{path} does not decode: not an XSpace ({e!r})") from e
    return events


def load_events(path: str) -> list:
    """The capture's events, from either file the profiler writes."""
    if path.endswith(".xplane.pb"):
        return _xplane_events(path)
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
    device_pids = sorted(pid for pid, name in process.items() if _is_device(name))
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

    # the window cut wherever an annotation starts or ends: in each piece
    # the host did one thing
    cuts = sorted({lo, hi}.union(t for a in inner for t in a[:2] if lo < t < hi))
    timeline = [(s, t, host_activity(s, t)) for s, t in zip(cuts, cuts[1:])]

    def by_host_activity(gaps):
        """Microseconds of the sorted ``gaps`` under each host activity; a
        gap that spans several is shared out among them."""
        shares, j = collections.Counter(), 0
        for start, end in gaps:
            while j < len(timeline) and timeline[j][1] <= start:
                j += 1
            k = j
            while k < len(timeline) and timeline[k][0] < end:
                shares[timeline[k][2]] += min(end, timeline[k][1]) - max(start, timeline[k][0])
                k += 1
        return shares

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
        by_activity = by_host_activity(idle)
        planes[process[pid]] = {
            "busy_s": total(busy) / 1e6,
            "op_s": sum(e["dur"] for e in leaves) / 1e6,
            "n_ops": len(leaves),
            "buckets_s": {b: buckets.get(b, 0) / 1e6 for b in BUCKETS},
            "collective_s": total(coll) / 1e6,
            "collective_exposed_s": total(subtract(coll, compute)) / 1e6,
            "top_ops": [[n, d / 1e6] for n, d in by_name.most_common(top)],
            # each under the activity that took most of it
            "longest_gaps": [
                [by_host_activity([(s, t)]).most_common(1)[0][0], (t - s) / 1e6] for s, t in longest
            ],
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


def host_span_events(spans, start_ns: int, pid: int = -1) -> list:
    """The harness's host spans as the annotations the reductions look for,
    on the capture's clock: microseconds from the profile's start."""
    events = [
        {"ph": "M", "name": "process_name", "pid": pid, "args": {"name": HOST_PROCESS}},
        {"ph": "M", "name": "thread_name", "pid": pid, "tid": 0, "args": {"name": "main"}},
    ]
    for name, t0, t1 in spans:
        events.append({
            "ph": "X", "pid": pid, "tid": 0, "name": name,
            "ts": (t0 - start_ns) / 1e3, "dur": (t1 - t0) / 1e3,
            "args": {"long_name": ANNOTATION_PREFIX + name},
        })
    return events


def capture_events(capture: str) -> list:
    """The events of a capture directory's newest file, or of one such file,
    with the host spans saved beside it: where both reductions get them."""
    path = capture if os.path.isfile(capture) else find_capture(capture)
    events = load_events(path)
    beside = os.path.join(os.path.dirname(path), HOST_SPANS_FILE)
    if os.path.isfile(beside):
        start = next((e["args"]["ns"] for e in events if e.get("name") == PROFILE_START_EVENT), None)
        if not start:
            raise TraceError(f"{path} does not say when the profile started: the host spans cannot be placed")
        with open(beside) as f:
            events += host_span_events(json.load(f), start)
    return events


def reduce_capture(capture: str, param_shapes=None) -> dict:
    return reduce_events(capture_events(capture), param_shapes)


def breakdown(reduced: dict, limit: int = 10) -> dict:
    """The ``breakdown`` of a traced run's result line, two lists of
    ``[name, seconds]`` with at most ``limit`` entries each, from the first
    chip's plane. ``device_ops``: the buckets that took any time, as
    ``bucket:<name>``, then the single operations that took most, under the
    names the trace gives them. ``idle_gaps``: the longest gaps (five, or
    fewer where the sums need the room), each under what the host was doing
    for most of it, then all idle time summed by host activity, as
    ``sum:<activity>``."""
    plane = first_plane(reduced)
    buckets = sorted(
        ([f"bucket:{b}", s] for b, s in plane["buckets_s"].items() if s > 0),
        key=lambda entry: -entry[1],
    )
    sums = [[f"sum:{n}", s] for n, s in plane["idle_by_host_activity_s"].items()]
    gaps = plane["longest_gaps"][: max(0, min(limit // 2, limit - len(sums)))]
    return {
        "device_ops": (buckets + [list(op) for op in plane["top_ops"]])[:limit],
        "idle_gaps": (gaps + sums)[:limit],
    }


if __name__ == "__main__":
    import sys

    print(json.dumps(reduce_capture(sys.argv[1]), indent=1))
