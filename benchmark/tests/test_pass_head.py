"""``pass_head_ms`` on hand-made event lists: two passes of several chunks, a
third the clock stopped before its first dispatch, a run with no ``dispatch``
span, and the entry ``BENCHMARK.json`` lists it under."""

import pytest

from benchmark import cells, trace_reduce as tr

READER = cells.load_module("layer_metrics", "pass_head_ms")


def _run(host_spans, window_s=2400e-6):
    events = tr.host_span_events(
        [[name, 1000 * t0, 1000 * t1] for name, t0, t1 in host_spans], start_ns=0
    )
    return {
        "events": events, "trace": None,
        "window": {"steps": 8, "window_s": window_s, "counters": {}},
        "spans": {"seconds": {}, "counts": {}},
    }


# microseconds. Two passes of two chunks each: the re-shuffle, the first
# chunk's waits and its stage, the first dispatch (100 and 130 after the
# re-shuffle ended), the second chunk staged behind it. The clock stops a
# third pass in its first wait: its value fetch never comes, so the window
# closes at 2,400
PASSES = [
    ("window", 0, 2600),
    ("between_passes", 0, 10),
    ("input_wait", 10, 40), ("input_wait", 40, 70), ("stage", 70, 108),
    ("dispatch", 110, 112),
    ("input_wait", 112, 150), ("input_wait", 150, 190), ("stage", 190, 230),
    ("dispatch", 230, 232), ("readback", 232, 1200),
    ("between_passes", 1200, 1220),
    ("input_wait", 1220, 1280), ("input_wait", 1280, 1300), ("stage", 1300, 1345),
    ("dispatch", 1350, 1352),
    ("input_wait", 1352, 1400), ("input_wait", 1400, 1440), ("stage", 1440, 1480),
    ("dispatch", 1480, 1482), ("readback", 1482, 2400),
    ("between_passes", 2400, 2410), ("input_wait", 2410, 2600),
]


def test_mean_over_the_windows_passes():
    assert READER.read(_run(PASSES)) == pytest.approx((0.100 + 0.130) / 2)


def test_a_pass_stopped_before_its_first_dispatch_is_left_out():
    # the clock stops the second pass after its re-shuffle: one pass counts,
    # and the first pass's later dispatches are not read as the second's head
    stopped = [s for s in PASSES if s[1] < 1220] + [("input_wait", 1220, 2600)]
    assert READER.read(_run(stopped, window_s=1200e-6)) == pytest.approx(0.100)
    assert READER.read(_run(stopped)) == pytest.approx(0.100)
    # a third pass inside the window's annotation but after the last value
    # fetch is outside the window, dispatch or not
    late = PASSES + [("dispatch", 2500, 2502)]
    assert READER.read(_run(late)) == pytest.approx((0.100 + 0.130) / 2)


def test_one_dispatch_a_pass_reads_the_whole_staging():
    whole = [
        ("window", 0, 1000), ("between_passes", 0, 10), ("input_wait", 10, 160),
        ("stage", 160, 330), ("dispatch", 330, 332), ("readback", 332, 1000),
    ]
    assert READER.read(_run(whole, window_s=1000e-6)) == pytest.approx(0.320)


def test_a_run_without_the_spans_reads_nothing():
    """A resident cell (no passes, no ``dispatch`` span), an untraced run, a
    capture without the window's annotation."""
    resident = [("window", 0, 1000), ("loader_next", 0, 200), ("between_passes", 500, 510)]
    assert READER.read(_run(resident)) is None
    no_passes = [("window", 0, 1000), ("dispatch", 100, 102), ("readback", 102, 900)]
    assert READER.read(_run(no_passes)) is None
    assert READER.read(dict(_run(PASSES), events=None)) is None
    assert READER.read(_run([s for s in PASSES if s[0] != "window"])) is None


def test_the_entry_in_benchmark_json():
    per_layer = cells.load_benchmark()["per_layer"]
    (entry,) = [m for m in per_layer if m["name"] == "pass_head_ms"]
    assert entry == {
        "name": "pass_head_ms", "unit": READER.UNIT, "better": "lower",
        "source": READER.SOURCE, "layer": READER.LAYER, "moves": READER.MOVES,
        "workloads": ["alexnet_b2048_loader"],
    }
    # the layer is one the benchmark already names, letter for letter
    assert READER.LAYER in {m["layer"] for m in per_layer if m is not entry}
