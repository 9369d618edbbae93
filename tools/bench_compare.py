#!/usr/bin/env python
"""bench_compare: the bench regression gate over BENCH_TRAJECTORY.jsonl.

The bench trajectory (BENCH_r0N.json wrappers) was unparseable by
downstream tooling: every run a differently-shaped blob, no machine
check that a PR regressed the headline number. bench.py now appends one
normalized row per supervised run to BENCH_TRAJECTORY.jsonl
(`bench.trajectory_row`); this tool diffs the LATEST MEASURED row per
metric against the gate table in BASELINE.json and exits nonzero on
regression — wired as a tier-1 test over the committed artifacts
(tests/test_bench_compare.py).

Semantics:

- a row with value <= 0 or extras.failure is an INFRASTRUCTURE-FAILED
  capture (the run never reached a measurement) — skipped, never a
  regression: it measures the environment, not the code;
- the gate table lives in BASELINE.json under "gates":
      {"<metric>": {"baseline": 81.33, "rel_tolerance": 0.25,
                    "direction": "higher"}}
  direction "higher" (default) fails when
      value < baseline * (1 - rel_tolerance);
  direction "lower" fails when value > baseline * (1 + rel_tolerance);
- a metric with no gate entry is compared against the PREVIOUS measured
  row of the same metric with --default-tolerance (trend gate);
- --backfill converts committed BENCH_r0N.json supervisor wrappers into
  trajectory rows (the one-time migration of the historical trail).

Exit codes: 0 ok / within tolerance; 1 regression; 2 no usable data.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List, Optional

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)


def load_rows(path: str) -> List[dict]:
    rows = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if isinstance(rec, dict) and rec.get("metric"):
                rows.append(rec)
    return rows


def measured(row: dict) -> bool:
    """A row that actually measured the code (vs. a failed capture)."""
    if float(row.get("value") or 0.0) <= 0.0:
        return False
    return "failure" not in (row.get("extras") or {})


def latest_measured(rows: List[dict]) -> Dict[str, List[dict]]:
    """metric -> measured rows in file (= time) order."""
    by_metric: Dict[str, List[dict]] = {}
    for row in rows:
        if measured(row):
            by_metric.setdefault(row["metric"], []).append(row)
    return by_metric


def check_metric(metric: str, rows: List[dict], gate: Optional[dict],
                 default_tolerance: float) -> dict:
    """One metric's verdict dict; 'status' in ok|regression|skipped."""
    latest = rows[-1]
    value = float(latest["value"])
    if gate is not None:
        baseline = float(gate["baseline"])
        tol = float(gate.get("rel_tolerance", default_tolerance))
        direction = gate.get("direction", "higher")
        source = "baseline"
    elif len(rows) >= 2:
        baseline = float(rows[-2]["value"])
        tol = default_tolerance
        direction = "higher"
        source = f"previous row ({rows[-2].get('run_id')})"
    else:
        return {"metric": metric, "status": "skipped",
                "reason": "no gate entry and no prior measured row",
                "value": value}
    if direction == "higher":
        floor = baseline * (1.0 - tol)
        ok = value >= floor
        bound = {"floor": round(floor, 4)}
    else:
        ceil = baseline * (1.0 + tol)
        ok = value <= ceil
        bound = {"ceiling": round(ceil, 4)}
    return {"metric": metric,
            "status": "ok" if ok else "regression",
            "value": value, "baseline": baseline,
            "rel_tolerance": tol, "direction": direction,
            "source": source, "run_id": latest.get("run_id"), **bound}


def compare(trajectory_path: str, baseline_path: str,
            default_tolerance: float = 0.25) -> dict:
    rows = load_rows(trajectory_path)
    with open(baseline_path) as f:
        gates = (json.load(f).get("gates") or {})
    by_metric = latest_measured(rows)
    skipped_captures = sum(1 for r in rows if not measured(r))
    results = [check_metric(metric, mrows, gates.get(metric),
                            default_tolerance)
               for metric, mrows in sorted(by_metric.items())]
    # a gate whose metric never produced a measured row is surfaced
    # (the gate exists because the number matters; silence would read
    # as "covered")
    for metric in sorted(set(gates) - set(by_metric)):
        results.append({"metric": metric, "status": "skipped",
                        "reason": "gated metric has no measured row"})
    return {
        "rows": len(rows),
        "skipped_failed_captures": skipped_captures,
        "results": results,
        "regressions": [r for r in results
                        if r["status"] == "regression"],
        "ok": bool(results) and not any(
            r["status"] == "regression" for r in results),
    }


def backfill(out_path: str, wrappers: List[str]) -> int:
    """Convert committed BENCH_r0N.json supervisor wrappers into
    trajectory rows (their 'parsed' field is the final result line)."""
    sys.path.insert(0, REPO_ROOT)
    from bench import trajectory_row

    from tools.artifacts import append_jsonl
    n = 0
    for path in wrappers:
        with open(path) as f:
            wrapper = json.load(f)
        parsed = wrapper.get("parsed")
        if not isinstance(parsed, dict) or not parsed.get("metric"):
            print(f"skip {path}: no parsed result", file=sys.stderr)
            continue
        run_id = os.path.splitext(os.path.basename(path))[0]
        append_jsonl(out_path, trajectory_row(parsed, run_id=run_id))
        n += 1
    return n


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="bench_compare", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--trajectory",
                    default=os.path.join(REPO_ROOT,
                                         "BENCH_TRAJECTORY.jsonl"))
    ap.add_argument("--baseline",
                    default=os.path.join(REPO_ROOT, "BASELINE.json"))
    ap.add_argument("--default-tolerance", type=float, default=0.25,
                    help="relative tolerance for ungated trend checks")
    ap.add_argument("--backfill", nargs="+", metavar="BENCH_rNN.json",
                    help="append trajectory rows converted from "
                         "committed supervisor wrappers, then exit")
    ap.add_argument("--quiet", action="store_true")
    args = ap.parse_args(argv)

    if args.backfill:
        n = backfill(args.trajectory, args.backfill)
        print(f"backfilled {n} row(s) -> {args.trajectory}")
        return 0 if n else 2

    if not os.path.exists(args.trajectory):
        print(f"no trajectory at {args.trajectory}", file=sys.stderr)
        return 2
    report = compare(args.trajectory, args.baseline,
                     args.default_tolerance)
    if not args.quiet:
        print(json.dumps(report, indent=1))
    if not report["results"]:
        print("no measured rows to gate on", file=sys.stderr)
        return 2
    if report["regressions"]:
        for r in report["regressions"]:
            print(f"REGRESSION {r['metric']}: {r['value']} vs "
                  f"{r['source']} {r['baseline']} "
                  f"(tolerance {r['rel_tolerance']})", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
