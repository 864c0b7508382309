"""bench.py's driver-parseable output contract (round 5: the artifact's
``parsed`` field was null because the full results dict was the stdout line).

The contract: the FULL per-config payload lands in ``bench_results.json``;
the LAST stdout line is one compact JSON summary carrying the headline
toy-MLP number. Pinned here without running the (TPU-scale) benchmarks by
driving :func:`bench.emit_summary` directly."""

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import bench  # noqa: E402


def test_summary_line_parses_and_carries_headline(tmp_path, monkeypatch):
    monkeypatch.setitem(bench.RESULTS, "toy_mlp f32 (scan-fused K=200)", {
        "samples_per_sec_per_chip": 1234567.8,
        "ms_per_step": 0.1,
        "mfu": None,
        "grad_comm_bytes_per_step": 1577248,
    })
    out = tmp_path / "bench_results.json"
    summary = bench.emit_summary(1234567.8, 1000.0, out_path=str(out))

    # exactly what main() prints as the last stdout line: it must survive a
    # strict json.loads round trip and stay compact (no per-config payload)
    line = json.dumps(summary)
    parsed = json.loads(line)
    assert parsed["metric"] == "toy_mlp_train_samples_per_sec_per_chip"
    assert parsed["value"] == 1234567.8
    assert parsed["unit"] == "samples/sec/chip"
    assert parsed["vs_baseline"] == 1234.57
    assert parsed["n_configs"] >= 1
    assert parsed["results_file"] == "bench_results.json"
    assert "configs" not in parsed
    assert "\n" not in line

    # the full payload (with per-config rows) round-trips from the file
    payload = json.loads(out.read_text())
    row = payload["configs"]["toy_mlp f32 (scan-fused K=200)"]
    assert row["grad_comm_bytes_per_step"] == 1577248
    assert payload["value"] == parsed["value"]


def test_summary_without_baseline(tmp_path):
    bench.RESULTS.clear()
    summary = bench.emit_summary(10.0, None, out_path=str(tmp_path / "r.json"))
    assert summary["vs_baseline"] == 1.0  # torch missing -> neutral ratio


def test_nonfinite_row_values_serialize_as_strict_json_null(tmp_path):
    """ISSUE 3 satellite: a failed/blown-up config row (NaN/Inf values) must
    land in bench_results.json as ``null`` — never the bare ``NaN`` token
    Python's default json.dump emits, which strict parsers reject. Pinned as
    a full round trip through a parser that refuses non-finite constants."""
    bench.RESULTS.clear()
    bench.RESULTS["exploded f32 (diverged)"] = {
        "samples_per_sec_per_chip": float("nan"),
        "ms_per_step": float("inf"),
        "mfu": None,
    }
    out = tmp_path / "bench_results.json"
    bench.emit_summary(123.0, 10.0, out_path=str(out))
    raw = out.read_text()
    assert "NaN" not in raw and "Infinity" not in raw

    def reject(tok):
        raise AssertionError(f"non-strict JSON token {tok!r} in bench_results.json")

    payload = json.loads(raw, parse_constant=reject)
    row = payload["configs"]["exploded f32 (diverged)"]
    assert row["samples_per_sec_per_chip"] is None
    assert row["ms_per_step"] is None
    assert row["mfu"] is None
    bench.RESULTS.clear()
