"""Read a cell's end-to-end metrics under several settings of the scheduler
in ONE served process: a cold set-up of a large cell costs 10-17 chip
minutes, a 51 s window one. Serves the cell exactly as a run does
(benchmark/run.py's own `bench()`, launcher, socket and load generator, the
reference check left out), makes ONE long window, and switches fields of
`scheduler.cfg` at given offsets into it. Then prints, for every phase,
`itl_p95_ms`, tokens/s and the share of gaps past `--edge-ms` over
sub-windows of `--sub` seconds, and the phase's histogram of gaps: a
percentile is steady only INSIDE a group of gaps, and this shows how many
points of mass lie between it and the group's edge (PERF.md section 6,
PR 33).

    chiprun -- python3 tools/serve_knob_windows.py \\
        --workload ling-3.0-flash-vl.decode-closed --seed 2147491357 \\
        --serve '["--max-slots","64", ... ,"--decode-steps","4"]' \\
        --phases '[[0,{}],[160,{"max_prefill_batch":3}]]' --seconds 320

`--serve` replaces `meta.json`'s flags (what needs a program the launch
flags do not warm compiles inside the window: give such a phase room).
A field switched on the way must be one the scheduler reads at every
plan (`max_prefill_batch`, `prefill_skip_ahead`; NOT `decode_steps` or
`max_slots`, which size programs and slots at launch). A sub-window's
numbers are not a run's: the closed mix walks its pool, so later
sub-windows meet other request sizes, which spreads them MORE than runs
of one seed schedule. `--rehearsal`: the tiny configuration on the CPU.
"""
from __future__ import annotations

import argparse
import asyncio
import bisect
import dataclasses
import importlib.util
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_runner():
    spec = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(ROOT, "benchmark", "run.py"))
    run = importlib.util.module_from_spec(spec)
    sys.modules["bench_run"] = run
    spec.loader.exec_module(run)
    return run


def serve_and_switch(run, serve, phases: list, out_path: str) -> None:
    """benchmark/run.py's `main()` with the cell's flags replaced (`serve`
    None: as they are), no reference check, and a task that switches
    `scheduler.cfg` fields at each phase's offset after the warm-up. What
    it switched when (on the rows' clock) and the engine's counters there
    go to `out_path`."""
    load_json = run.traffic.load_json

    def load_with_flags(path):
        d = load_json(path)
        if path.endswith("meta.json") and "serve" in d:
            d = dict(d, serve=serve or d["serve"])
            d.pop("reference_check", None)
        return d
    run.traffic.load_json = load_with_flags

    box = {}
    start = run.Served.start

    async def start_and_keep(self):
        await start(self)
        box["served"] = self
    run.Served.start = start_and_keep

    async def switcher(t_warm):
        log = []
        for off, fields in phases:
            await asyncio.sleep(max(0.0, t_warm + off - time.monotonic()))

            def switch(eng, fields=fields):
                if fields:
                    eng.scheduler.cfg = dataclasses.replace(
                        eng.scheduler.cfg, **fields)
                return dataclasses.asdict(eng.metrics())
            counters = await box["served"].worker.submit(switch)
            log.append({"t": time.monotonic(), "offset": off,
                        "fields": fields, "engine": counters})
            with open(out_path, "w") as f:
                json.dump(log, f)
            run.log("phase", off, fields)

    read_event = run.read_event

    async def read_then_switch(proc, name, timeout):
        ev = await read_event(proc, name, timeout)
        if name == "warm_done":
            box["task"] = asyncio.create_task(switcher(time.monotonic()))
        return ev
    run.read_event = read_then_switch
    run.main()      # prints the whole window's line and exits the process


def report(rows_path: str, phases_path: str, sub: float, settle: float,
           edge_ms: float) -> None:
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    from harness import stats
    with open(rows_path) as f:
        rows = [json.loads(line) for line in f]
    with open(phases_path) as f:
        phases = json.load(f)
    gaps = sorted((b, (b - a) * 1e3) for r in rows
                  for a, b in zip(r.get("frames", ()),
                                  r.get("frames", ())[1:]))
    ends = [g[0] for g in gaps]
    last = ends[-1] - 2.0      # the clients are cut a little after the window

    def between(a, b):
        return [g[1] for g in gaps[bisect.bisect_left(ends, a):
                                   bisect.bisect_left(ends, b)]]
    for i, p in enumerate(phases):
        a = p["t"] + settle
        b = phases[i + 1]["t"] if i + 1 < len(phases) else last
        print(json.dumps({"phase": p["fields"], "from_s": p["offset"]}))
        t = a
        while t + sub <= b + 0.5:
            g = between(t, t + sub)
            print("  itl_p95_ms %.1f  tok/s %.0f  gaps >= %g ms %.2f %%" % (
                stats.percentile(g, 95), len(g) / sub, edge_ms,
                100.0 * sum(1 for x in g if x >= edge_ms) / len(g)))
            t += sub
        g = between(a, b)
        hist = {}
        for x in g:
            hist[int(x // 10) * 10] = hist.get(int(x // 10) * 10, 0) + 1
        print("  % of gaps by 10 ms:", " ".join(
            f"{k}:{100 * v / len(g):.1f}" for k, v in sorted(hist.items())
            if v * 1000 > len(g)))


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--serve", default="",
                   help="JSON list of launcher flags (default: meta.json's)")
    p.add_argument("--phases", default="[[0, {}]]",
                   help="JSON [[offset_s, {scheduler.cfg field: value}], ...]")
    p.add_argument("--sub", type=float, default=51.0)
    p.add_argument("--settle", type=float, default=5.0)
    p.add_argument("--edge-ms", type=float, default=95.0)
    p.add_argument("--rehearsal", action="store_true")
    p.add_argument("--report-only", action="store_true",
                   help="the run is there: print its report again")
    args = p.parse_args()
    out_dir = os.path.join(ROOT, "chiprun_out", "benchmark", args.workload,
                           f"s{args.seed}-t0")
    phases_path = os.path.join(ROOT, "chiprun_out", "benchmark",
                               args.workload, f"s{args.seed}-phases.json")
    if not args.report_only:
        pid = os.fork()      # run.py's main() ends in os._exit
        if pid == 0:
            run = load_runner()
            serve = json.loads(args.serve) if args.serve else None
            sys.argv = [sys.argv[0], "--workload", args.workload, "--seed",
                        str(args.seed), "--seconds", str(args.seconds),
                        "--trace", "0"] \
                + (["--rehearsal"] if args.rehearsal else [])
            serve_and_switch(run, serve, json.loads(args.phases),
                             phases_path)
            os._exit(1)
        _, status = os.waitpid(pid, 0)
        if os.waitstatus_to_exitcode(status):
            sys.exit(os.waitstatus_to_exitcode(status))
    report(os.path.join(out_dir, "rows.jsonl"), phases_path, args.sub,
           args.settle, args.edge_ms)


if __name__ == "__main__":
    main()
