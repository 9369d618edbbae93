"""Bench regression gate (tools/bench_compare.py + bench.trajectory_row):
unit coverage of the skip/tolerance/exit-code semantics on synthetic
trajectories. (No trajectory is committed: the only rows there ever were
came from the `tiny` model on the CPU backend.)
"""
import json
import os
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO_ROOT, "tools"))

import bench_compare  # noqa: E402


def _write(tmp_path, rows, gates=None):
    traj = tmp_path / "traj.jsonl"
    traj.write_text("".join(json.dumps(r) + "\n" for r in rows))
    base = tmp_path / "base.json"
    base.write_text(json.dumps({"gates": gates or {}}))
    return str(traj), str(base)


def _row(value, run_id="r1", metric="m", extras=None):
    return {"run_id": run_id, "metric": metric, "value": value,
            "unit": "tok/s", "extras": extras or {}}


def test_regression_beyond_tolerance_exits_nonzero(tmp_path):
    gates = {"m": {"baseline": 100.0, "rel_tolerance": 0.25}}
    traj, base = _write(tmp_path, [_row(70.0)], gates)
    assert bench_compare.main(["--trajectory", traj, "--baseline", base,
                               "--quiet"]) == 1


def test_tolerance_boundary_is_inclusive(tmp_path):
    gates = {"m": {"baseline": 100.0, "rel_tolerance": 0.25}}
    traj, base = _write(tmp_path, [_row(75.0)], gates)   # exactly the floor
    assert bench_compare.main(["--trajectory", traj, "--baseline", base,
                               "--quiet"]) == 0


def test_failed_capture_after_good_row_does_not_regress(tmp_path):
    gates = {"m": {"baseline": 100.0, "rel_tolerance": 0.25}}
    traj, base = _write(tmp_path, [
        _row(110.0, "good"),
        _row(0.0, "no_tpu", extras={"failure": "no TPU"}),
    ], gates)
    assert bench_compare.main(["--trajectory", traj, "--baseline", base,
                               "--quiet"]) == 0
    report = bench_compare.compare(traj, base)
    assert report["results"][0]["run_id"] == "good"


def test_ungated_metric_trend_checks_against_previous_row(tmp_path):
    traj, base = _write(tmp_path, [_row(100.0, "a"), _row(60.0, "b")])
    assert bench_compare.main(["--trajectory", traj, "--baseline", base,
                               "--quiet"]) == 1
    traj2, base2 = _write(tmp_path, [_row(100.0, "a"), _row(90.0, "b")])
    assert bench_compare.main(["--trajectory", traj2, "--baseline", base2,
                               "--quiet"]) == 0


def test_no_measured_rows_is_exit_2(tmp_path):
    traj, base = _write(tmp_path, [_row(0.0)])
    assert bench_compare.main(["--trajectory", traj, "--baseline", base,
                               "--quiet"]) == 2


def test_lower_is_better_direction(tmp_path):
    gates = {"ttft": {"baseline": 0.1, "rel_tolerance": 0.5,
                      "direction": "lower"}}
    traj, base = _write(tmp_path, [_row(0.2, metric="ttft")], gates)
    assert bench_compare.main(["--trajectory", traj, "--baseline", base,
                               "--quiet"]) == 1
    traj2, base2 = _write(tmp_path, [_row(0.12, metric="ttft")], gates)
    assert bench_compare.main(["--trajectory", traj2, "--baseline", base2,
                               "--quiet"]) == 0


def test_trajectory_row_normalization():
    sys.path.insert(0, REPO_ROOT)
    from bench import trajectory_row
    row = trajectory_row(
        {"metric": "m", "value": 81.33, "unit": "tok/s",
         "vs_baseline": 0.08,
         "extras": {"failure": "x", "quant": "int8",
                    "probes": ["dropped"], "huge": "dropped"}},
        run_id="r9")
    assert row["run_id"] == "r9"
    assert row["value"] == 81.33
    # bounded extras subset: fingerprint keys kept, blobs dropped
    assert set(row["extras"]) == {"failure", "quant"}


def test_gated_metric_with_no_measured_row_is_surfaced(tmp_path):
    gates = {"ghost": {"baseline": 10.0}}
    traj, base = _write(tmp_path, [_row(100.0, metric="m")], gates)
    report = bench_compare.compare(traj, base)
    skipped = [r for r in report["results"] if r["status"] == "skipped"]
    assert any(r["metric"] == "ghost" for r in skipped)
    assert report["ok"]   # surfaced, not failed (nothing measured it)
