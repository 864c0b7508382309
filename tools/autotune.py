#!/usr/bin/env python
"""autotune — the A/B probe harness: advisor predictions vs measured truth.

The offline advisor (``tpuddp_inspect tune``) PREDICTS; this tool makes it
answer for the prediction. It drives the REAL epoch driver twice — a
baseline dryrun on the given knobs, then a tuned dryrun launched under the
advisor's ``$TPUDDP_TUNE_OVERLAY`` — measures both runs from their own
history artifacts (``tpuddp.observability.advisor.measure_run``), and
writes every recommendation's predicted-vs-measured delta into a
schema-v12-validated ``TUNE_rNN.json``. A rule whose measured delta
regresses ships ``endorsed: false``
— the probe refuses to endorse it, whatever the prediction promised — and
the fleet tuner (tpuddp/tune/online.py) only ever acts on endorsed rules.

Honesty note: on the CPU rung (forced host-platform devices) the measured
deltas calibrate the RULES' direction, not TPU magnitudes — wire-byte and
counter metrics (grad_comm_bytes, snapshot skips) transfer; wall-clock
ratios largely do not. ``device`` in the artifact records the rung so
a reader never mixes rungs.

Usage:
    python tools/autotune.py --quick                  # CPU-rung probe
    python tools/autotune.py --baseline-dir RUN_DIR   # reuse a run as A
    python tools/autotune.py --training '{"snapshot": {"every_steps": 1}}'

Exit: 0 on a written report (even when nothing is endorsed — the artifact
IS the result), nonzero when a dryrun or validation fails.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from tpuddp.observability import advisor as advisor_lib  # noqa: E402
from tpuddp.tune import probe  # noqa: E402

# deliberately BAD defaults for the quick probe: each arms a different rule
# class on a real run (pipeline_sync_readback, snapshot_cadence_hot,
# comm_hook_uncompressed fires off the default hook=none)
_QUICK_BASELINE = {
    "pipeline": False,
    "snapshot": {"every_steps": 1, "inflight": 1},
    "step_stats_every": 4,
}


def _worker_env(extra=None):
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        "TPUDDP_BACKEND": "cpu",
        "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
    })
    env.pop("TPUDDP_TUNE_OVERLAY", None)
    env.update(extra or {})
    return env


def _dryrun(out_dir, *, training, epochs, world, overlay=None):
    """One pass through the real epoch driver (the chaos worker's spawn
    path — drain handlers, snapshots, tracing all live). ``overlay`` rides
    ``$TPUDDP_TUNE_OVERLAY`` exactly as a fleet relaunch would."""
    extra = {
        "TPUDDP_CHAOS_TRAINING": json.dumps(training),
        "TPUDDP_CHAOS_OBS": json.dumps({"tracing": True}),
        "TPUDDP_WORLD_SIZE": str(world),
    }
    if overlay is not None:
        extra["TPUDDP_TUNE_OVERLAY"] = json.dumps(overlay)
    return subprocess.call(
        [
            sys.executable, "-u",
            os.path.join(REPO, "tests", "_chaos_train_worker.py"),
            out_dir, str(epochs),
        ],
        cwd=REPO, env=_worker_env(extra),
    )


def _device_of(run_dir):
    try:
        with open(os.path.join(run_dir, "history.jsonl")) as f:
            for line in f:
                rec = json.loads(line)
                if rec.get("type") == "run_meta":
                    return rec.get("device_kind")
    except (OSError, ValueError):
        pass
    return None


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument(
        "--quick", action="store_true",
        help="CPU-rung probe on deliberately bad baseline knobs (2 epochs)",
    )
    parser.add_argument(
        "--training", default=None, metavar="JSON",
        help="baseline training-config overrides (JSON object); default: "
        "the --quick bad-knob set",
    )
    parser.add_argument(
        "--baseline-dir", default=None, metavar="RUN_DIR",
        help="reuse an existing run dir as the baseline (skips the A leg; "
        "its history must carry the knobs the advisor should see)",
    )
    parser.add_argument("--epochs", type=int, default=2)
    parser.add_argument("--world", type=int, default=4)
    parser.add_argument(
        "--out", default=None, metavar="PATH",
        help="report path (default: next TUNE_rNN.json at the repo root)",
    )
    parser.add_argument(
        "--min-improvement", type=float, default=0.0, metavar="PCT",
        help="endorsement floor on the measured delta (default 0.0: any "
        "regression refuses endorsement)",
    )
    parser.add_argument(
        "--keep", default=None, metavar="DIR",
        help="keep the probe run dirs under DIR (default: temp, deleted)",
    )
    args = parser.parse_args(argv)

    training = dict(_QUICK_BASELINE)
    if args.training:
        training.update(json.loads(args.training))

    with tempfile.TemporaryDirectory(prefix="tpuddp_autotune_") as tmp:
        work = args.keep or tmp
        os.makedirs(work, exist_ok=True)
        baseline_dir = args.baseline_dir
        if baseline_dir is None:
            baseline_dir = os.path.join(work, "baseline")
            print(f"autotune: baseline dryrun -> {baseline_dir}")
            rc = _dryrun(
                baseline_dir, training=training, epochs=args.epochs,
                world=args.world,
            )
            if rc != 0:
                print(f"autotune: baseline dryrun exited {rc}",
                      file=sys.stderr)
                return rc

        report = advisor_lib.advise(baseline_dir)
        recs = report["recommendations"]
        if not recs:
            print("autotune: advisor found nothing to recommend on the "
                  "baseline — no probe to run, no report written")
            return 0
        overlay = advisor_lib.overlay_from(recs)
        overlay["source"] = "autotune"
        print(f"autotune: {len(recs)} recommendation(s); overlay = "
              + json.dumps(overlay, sort_keys=True))

        tuned_dir = os.path.join(work, "tuned")
        print(f"autotune: tuned dryrun -> {tuned_dir}")
        rc = _dryrun(
            tuned_dir, training=training, epochs=args.epochs,
            world=args.world, overlay=overlay,
        )
        if rc != 0:
            print(f"autotune: tuned dryrun exited {rc}", file=sys.stderr)
            return rc

        baseline_metrics = advisor_lib.measure_run(baseline_dir)
        tuned_metrics = advisor_lib.measure_run(tuned_dir)
        results = [
            probe.make_result_row(
                rec, baseline_metrics, tuned_metrics,
                min_improvement_pct=args.min_improvement,
            )
            for rec in recs
        ]
        payload = probe.build_tune_report(
            device=_device_of(baseline_dir) or "cpu",
            mode="train",
            baseline_metrics=baseline_metrics,
            results=results,
            extra={
                "tuned_metrics": tuned_metrics,
                "overlay": overlay,
                "epochs": args.epochs,
                "world_size": args.world,
                "baseline_training": training,
            },
        )
        out = args.out or probe.next_tune_path(REPO)
        tmp_path = out + ".tmp"
        with open(tmp_path, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
            f.write("\n")
        os.replace(tmp_path, out)

        endorsed = [r for r in results if r["endorsed"]]
        print(f"\nautotune: wrote {out}")
        for r in results:
            verdict = "endorsed" if r["endorsed"] else "REFUSED"
            meas = r["measured_delta_pct"]
            meas_s = f"{meas:+.1f}%" if meas is not None else "unmeasured"
            print(f"  [{verdict}] {r['rule']} ({r['metric']}): predicted "
                  f"{r['predicted_delta_pct']:+.1f}%, measured {meas_s}")
        print(f"autotune: {len(endorsed)}/{len(results)} endorsed")
        return 0


if __name__ == "__main__":
    sys.exit(main())
