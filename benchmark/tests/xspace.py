"""Write trace-viewer-shaped events as a traced run leaves a capture: the
device's (and any other) events in the profiler's own file, an ``XSpace`` in
an ``.xplane.pb``, through the text form of the message that
``jax.profiler.ProfileData`` serialises; and the ``bench:`` annotations as
the harness's host spans beside it. How the tests get a capture of the kind
the chip writes without a chip."""

from __future__ import annotations

import collections
import json
import os

from jax.profiler import ProfileData

from benchmark import trace_reduce as tr

START_NS = 1_790_000_000_000_000_000  # some wall-clock time the profile started at


def _quoted(text: str) -> str:
    return '"' + text.replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n") + '"'


def write_capture(events, directory: str) -> str:
    """``events`` (``ph`` "M" process and thread names, ``ph`` "X" complete
    events with ``ts`` and ``dur`` in microseconds and string ``args``) under
    ``directory``: one plane a process and one line a thread in
    ``host.xplane.pb``, names and stats on the event metadata as the profiler
    has them, and every ``bench:`` annotation in ``host_spans.json`` on the
    wall clock. Returns the ``.xplane.pb``'s path."""
    spans = [
        [tr._annotation(e)[len(tr.ANNOTATION_PREFIX):],
         START_NS + round(e["ts"] * 1e3), START_NS + round((e["ts"] + e["dur"]) * 1e3)]
        for e in events
        if e.get("ph") == "X" and tr._annotation(e).startswith(tr.ANNOTATION_PREFIX)
    ]
    events = [e for e in events if not tr._annotation(e).startswith(tr.ANNOTATION_PREFIX)]
    os.makedirs(directory, exist_ok=True)
    with open(os.path.join(directory, tr.HOST_SPANS_FILE), "w") as f:
        json.dump(spans, f)
    path = os.path.join(directory, "host.xplane.pb")
    _write_xplane(events, path)
    return path


def _write_xplane(events, path: str) -> None:
    process, thread = {}, {}
    by_line = collections.defaultdict(list)
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            process[e["pid"]] = e["args"]["name"]
        elif e.get("ph") == "M" and e.get("name") == "thread_name":
            thread[(e["pid"], e["tid"])] = e["args"]["name"]
        elif e.get("ph") == "X":
            by_line[(e["pid"], e["tid"])].append(e)
    planes = []
    for pid, plane_name in process.items():
        metadata, stat_ids, lines = {}, {}, []
        for (p, tid), line_events in by_line.items():
            if p != pid:
                continue
            rows = []
            for e in line_events:
                args = {k: v for k, v in (e.get("args") or {}).items() if k != "long_name"}
                full = (e.get("args") or {}).get("long_name") or e["name"]
                key = (full, e["name"], tuple(sorted(args.items())))
                if key not in metadata:
                    stats = "".join(
                        f" stats {{ metadata_id: {stat_ids.setdefault(k, len(stat_ids) + 1)}"
                        f" str_value: {_quoted(str(v))} }}"
                        for k, v in args.items()
                    )
                    metadata[key] = (len(metadata) + 1, full, e["name"], stats)
                rows.append(
                    f"events {{ metadata_id: {metadata[key][0]} "
                    f"offset_ps: {round(e['ts'] * 1e6)} duration_ps: {round(e['dur'] * 1e6)} }}"
                )
            lines.append(
                f"lines {{ id: {tid} name: {_quoted(thread.get((pid, tid), ''))} "
                + "\n".join(rows) + " }"
            )
        described = "\n".join(
            f"event_metadata {{ key: {i} value {{ id: {i} name: {_quoted(full)}"
            + (f" display_name: {_quoted(shown)}" if shown != full else "")
            + stats + " } }"
            for i, full, shown, stats in metadata.values()
        )
        named = "\n".join(
            f"stat_metadata {{ key: {i} value {{ id: {i} name: {_quoted(k)} }} }}"
            for k, i in stat_ids.items()
        )
        planes.append(
            f"planes {{ id: {pid} name: {_quoted(plane_name)}\n"
            + "\n".join(lines) + "\n" + described + "\n" + named + "\n}"
        )
    planes.append(
        'planes { id: 99 name: "Task Environment" '
        f"stats {{ metadata_id: 1 uint64_value: {START_NS} }} "
        'stat_metadata { key: 1 value { id: 1 name: "profile_start_time" } } }'
    )
    with open(path, "wb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace("\n".join(planes)))
