"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run of one cell of BENCHMARK.json. This process holds the chip(s): it
serves exactly as `python -m dynamo_tpu.run in=http:<port> out=native
<model dir> <serve flags>` does, by running that module's own `amain()` in
its event loop, and it alone can take a profiler trace. The load generator
(harness/loadgen.py) is a child that never imports JAX and talks to the
real socket.

Set-up (all of it inside `setup_s`, process start to the first scheduled
request): backend start, seeded on-device init, the set-up checks
(checks/*.py), the warm-up walk over every (program, bucket) the cell's
traffic can reach. Then the window: `--seconds` of the cell's traffic.
`--trace 0` prints the cell's end-to-end metrics; `--trace 1` runs the same
traffic, traces ~4 s in the middle of the window (longer, up to twice
that, where no decode window was dispatched inside the 4 s:
`slice_with_a_window`) and prints the per-layer metrics and a `breakdown`.

The last line of stdout is one JSON object: `correct`, `attempted`,
`failed`, `metrics`, `device` (and `breakdown`). No TPU, an unknown
device_kind or fewer chips than the cell asks for: nonzero exit, no line.
`--rehearsal` runs the same control flow on the CPU at a tiny size, marks
its line `"rehearsal": true` and `"correct": false`, and is never a result.
"""
from __future__ import annotations

import time

T_START = time.monotonic()   # set-up is measured from here

import argparse   # noqa: E402
import asyncio   # noqa: E402
import dataclasses   # noqa: E402
import importlib.util   # noqa: E402
import json   # noqa: E402
import os   # noqa: E402
import random   # noqa: E402
import shutil   # noqa: E402
import socket   # noqa: E402
import sys   # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, CHECKOUT)

from harness import readers, stats, traffic   # noqa: E402
from harness.loadgen import Row, do_request   # noqa: E402
from harness.modeldir import build_model_dir   # noqa: E402

TRACE_SLICE_S = 4.0
TRACE_EXTEND_STEP_S = 0.5
READY_TIMEOUT_S = 1000.0
LADDER_KEYS = ("page_size", "prefill_buckets", "mixed_token_budget",
               "max_prefill_chunk", "max_prefill_batch", "max_slots",
               "decode_steps")


def log(*a) -> None:
    print("[bench]", *a, file=sys.stderr, flush=True)


def die(msg: str, code: int = 2):
    log(msg)
    sys.stdout.flush()
    os._exit(code)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def find(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"BENCHMARK.json has no {what} named {name!r}")


async def http_get(port: int, path: str) -> str:
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    try:
        writer.write(f"GET {path} HTTP/1.1\r\nHost: bench\r\n\r\n".encode())
        await writer.drain()
        status = int((await reader.readline()).split()[1])
        length = 0
        while True:
            line = await reader.readline()
            if line in (b"\r\n", b"\n", b""):
                break
            k, _, v = line.decode().partition(":")
            if k.strip().lower() == "content-length":
                length = int(v)
        body = (await reader.readexactly(length)).decode()
        if status != 200:
            raise RuntimeError(f"GET {path} -> {status}")
        return body
    finally:
        writer.close()


class Served:
    """The launcher's `amain()` running in this loop, and the worker it
    built (kept only for `metrics()` / `device_info()` and the ladders)."""

    def __init__(self, model_dir: str, serve_flags: list, port: int):
        self.model_dir, self.flags, self.port = model_dir, serve_flags, port
        self.worker = None
        self.task = None

    async def start(self) -> None:
        import dynamo_tpu.run as launcher
        build = launcher.build_engine

        async def build_and_keep(out_spec, card, args):
            self.worker = await build(out_spec, card, args)
            return self.worker

        launcher.build_engine = build_and_keep
        sys.argv = ["dynamo_tpu.run", f"in=http:{self.port}", "out=native",
                    self.model_dir, *self.flags]
        log("serving:", " ".join(sys.argv))
        self.task = asyncio.create_task(launcher.amain())
        deadline = time.monotonic() + READY_TIMEOUT_S
        while time.monotonic() < deadline:
            if self.task.done():
                self.task.result()
                raise RuntimeError("the launcher returned before READY")
            if self.worker is not None:
                try:
                    if os.path.basename(self.model_dir) in \
                            await http_get(self.port, "/v1/models"):
                        return
                except (OSError, RuntimeError, ValueError, IndexError,
                        asyncio.IncompleteReadError):
                    pass
            await asyncio.sleep(0.05)
        raise TimeoutError("no READY from the launcher")

    def ladders(self) -> dict:
        """The engine's bucket ladders, read from the live engine, so the
        warm-up follows a PR that changes a value. One that renames or
        drops a ladder stops the run here, naming it: the warm-up walk
        (harness/loadgen.py) is built on these, and a guessed value would
        warm the wrong programs and show as compiles in the window."""
        eng = self.worker.engine
        out = {}
        for key in LADDER_KEYS:
            if not hasattr(eng.cfg, key):
                raise RuntimeError(f"the engine's config has no {key!r}: "
                                   f"the warm-up walk cannot be planned")
            v = getattr(eng.cfg, key)
            out[key] = list(v) if isinstance(v, (tuple, list)) else v
        out["page_buckets"] = list(eng.scheduler.page_buckets)
        return out

    async def engine_metrics(self) -> dict:
        m = await self.worker.submit(lambda eng: eng.metrics())
        return dataclasses.asdict(m)

    async def prom(self) -> dict:
        return readers.parse_prom(await http_get(self.port, "/metrics"))


class CheckCtx:
    """What a set-up check gets: a way to send one request."""

    def __init__(self, served: Served, model: str, vocab: int):
        self.served, self.model, self.vocab = served, model, vocab
        self.template_tokens = None

    async def request(self, prompt_tokens: int, max_tokens: int, seed: int,
                      sampling: dict, extra: dict = None) -> Row:
        words = prompt_tokens - (self.template_tokens or 0)
        rng = random.Random(seed)   # same seed, same prompt
        req = {"prompt_tokens": prompt_tokens, "max_tokens": max_tokens,
               "seed": seed, "sampling": sampling, "extra": extra or {},
               "content": traffic.prompt_words(rng, words, self.vocab)}
        row = Row(logprobs=[])
        return await do_request(self.served.port, self.model, req, row)


async def run_checks(ctx: CheckCtx, meta: dict) -> list:
    problems = []
    # how many tokens the chat template adds: read, not assumed
    row = await ctx.request(8, 1, 1, {"temperature": 0.0})
    if row.get("status") != 200 or not row.get("usage"):
        return [f"template probe failed: {row.get('status')} "
                f"{row.get('error')}"]
    ctx.template_tokens = row["usage"]["prompt_tokens"] - 8
    for path in sorted(os.listdir(os.path.join(HERE, "checks"))):
        if not path.endswith(".py") or path.startswith("_"):
            continue
        spec = importlib.util.spec_from_file_location(
            f"bench_check_{path[:-3]}", os.path.join(HERE, "checks", path))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        if mod.applies(meta):
            found = await mod.run(ctx)
            problems += [f"{path[:-3]}: {p}" for p in found]
            log(f"check {path[:-3]}:", "ok" if not found else found)
    return problems


async def slice_with_a_window(windows, slice_s: float,
                              step_s: float = TRACE_EXTEND_STEP_S,
                              sleep=asyncio.sleep) -> tuple:
    """Hold an open trace for `slice_s`, and where no decode window was
    DISPATCHED inside that (`windows()`: the engine's count of window
    programs launched; a count of commits would also take a window that
    ran before the trace began), go on in steps of `step_s` until one
    was, and one step more so that it runs inside the trace: at most
    2 x `slice_s` in all. A `context-closed` cell spends ~70 % of its
    time in mixed steps, and one dispatch that stalls for ~2 s beside
    them (1 traced run in 24, PERF.md section 6, PR 54) leaves a slice
    without a window and every window-step and window-roofline metric
    with nothing to read (ledger, PRs 48 and 52). A slice that holds a
    window is held exactly `slice_s`, as before PR 54. Returns
    the windows dispatched inside the planned slice (0: the slice as it
    was until PR 53 held none) and inside the whole trace."""
    w0 = await windows()
    await sleep(slice_s)
    in_slice = seen = await windows() - w0
    waited = 0.0
    while not seen and waited + 2 * step_s <= slice_s:
        await sleep(step_s)
        waited += step_s
        seen = await windows() - w0
    if seen and not in_slice:
        await sleep(step_s)
        seen = await windows() - w0
    return in_slice, seen


async def read_event(proc, name: str, timeout: float) -> dict:
    """Next stdout line of the child that is the named event."""
    deadline = time.monotonic() + timeout
    while True:
        left = deadline - time.monotonic()
        if left <= 0:
            raise TimeoutError(f"load generator: no {name!r} event")
        line = await asyncio.wait_for(proc.stdout.readline(), left)
        if not line:
            raise RuntimeError(
                f"load generator exited (rc={await proc.wait()}) before "
                f"{name!r}")
        try:
            ev = json.loads(line)
        except ValueError:
            continue
        if ev.get("event") == name:
            return ev


def live_kv_tokens(rows: list, t: float) -> float:
    """Tokens of context held by the streams alive at time t (prompt plus
    frames so far): what a decode step at t must read K and V for."""
    total = 0.0
    for r in rows:
        f = r.get("frames") or ()
        if f and f[0] <= t and (r.get("end") or f[-1]) >= t:
            total += r["prompt_tokens"] + sum(1 for x in f if x <= t)
    return total


async def sweep(args, proc, plan: dict, out_dir: str) -> None:
    """Find the knee once, by hand: further windows in the same process
    and set-up, one per rate, each drained before the next. Writes
    `sweep.json`; the knee is the highest rate whose backlog (requests in
    flight) does not grow over the window. Not part of any check."""
    table = []
    for i, rate in enumerate(float(r) for r in args.sweep.split(",")):
        t0 = time.monotonic() + 0.2 + float(plan["mix"].get("lead_in_s", 0))
        proc.stdin.write((json.dumps({"t0": t0, "rate_per_s": rate})
                          + "\n").encode())
        await proc.stdin.drain()
        await read_event(proc, "done", args.seconds + 120.0)
        with open(plan["rows_path"]) as f:
            rows = [json.loads(line) for line in f]
        rows = [r for r in rows if r.get("phase") == f"window{i + 2}"]
        t1 = t0 + args.seconds

        def in_flight(t):
            return sum(1 for r in rows
                       if r["due"] <= t and r.get("end", 1e18) > t)
        firsts = [r["frames"][0] - r["due"] for r in rows if r["frames"]]
        table.append({
            "rate_per_s": rate, "offered": len(rows),
            "ended_in_window": sum(1 for r in rows
                                   if r.get("end", 1e18) <= t1),
            "in_flight_mid": in_flight(t0 + args.seconds / 2),
            "in_flight_end": in_flight(t1 - 0.05),
            "drain_s": max((r.get("end", t1) for r in rows), default=t1)
            - t1,
            "ttft_p50_s": stats.percentile(firsts, 50) if firsts else None,
            "ttft_p95_s": stats.percentile(firsts, 95) if firsts else None,
            "tokens_per_s": stats.tokens_in_window(rows, t0, t1)
            / args.seconds})
        log("sweep:", json.dumps(table[-1]))
    with open(os.path.join(out_dir, "sweep.json"), "w") as f:
        json.dump(table, f, indent=1)


async def bench(args, bench_json: dict, cell_entry: dict) -> dict:
    import jax
    chips = int(cell_entry["chips"])
    config = find(bench_json["configs"], cell_entry["config"], "config")
    config_dir = os.path.dirname(os.path.join(CHECKOUT, config["file"]))
    meta = traffic.load_json(os.path.join(config_dir, "meta.json"))
    if args.rehearsal:
        config_dir = os.path.join(HERE, "configs", meta["rehearsal_config"])
    with open(os.path.join(config_dir, "config.json")) as f:
        model_cfg = json.load(f)
    mix = traffic.load_mix(cell_entry["traffic"], HERE)
    cell = traffic.load_cell(cell_entry["name"], HERE)
    out_dir = os.path.join(CHECKOUT, "chiprun_out", "benchmark",
                           cell_entry["name"],
                           f"s{args.seed}-t{args.trace}")
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)

    devices = jax.devices()
    kind = devices[0].device_kind
    peaks = traffic.load_json(os.path.join(HERE, "harness", "peaks.json"))
    if not args.rehearsal:
        if devices[0].platform != "tpu":
            die(f"no TPU: jax found {devices[0].platform!r}")
        if kind not in peaks:
            die(f"device_kind {kind!r} is not in harness/peaks.json")
    if len(devices) < chips:
        die(f"the cell needs {chips} chips, jax found {len(devices)}")

    model_dir = build_model_dir(config_dir, os.path.join(out_dir, "model"))
    served = Served(model_dir, list(meta["serve"]), free_port())
    await served.start()
    t_ready = time.monotonic()
    log(f"READY after {t_ready - T_START:.1f}s",
        json.dumps(served.worker.engine.device_info()))
    ladders = served.ladders()
    traffic.check_admission(mix, ladders["page_size"],
                            ladders["page_buckets"])   # if the mix pins it

    model = os.path.basename(model_dir)
    ctx = CheckCtx(served, model, int(model_cfg["vocab_size"]))
    problems = await run_checks(ctx, meta)
    t_checked = time.monotonic()

    plan = {"port": served.port, "model": model, "mix": mix, "cell": cell,
            "seed": args.seed, "seconds": args.seconds, "ladders": ladders,
            "template_tokens": ctx.template_tokens,
            "vocab": int(model_cfg["vocab_size"]),
            "rows_path": os.path.join(out_dir, "rows.jsonl")}
    plan_path = os.path.join(out_dir, "plan.json")
    with open(plan_path, "w") as f:
        json.dump(plan, f)
    proc = await asyncio.create_subprocess_exec(
        sys.executable, os.path.join(HERE, "harness", "loadgen.py"),
        plan_path, stdin=asyncio.subprocess.PIPE,
        stdout=asyncio.subprocess.PIPE, limit=1 << 22)
    try:
        warm = await read_event(proc, "warm_done", 1100.0)
        prom_warm = await served.prom()
        log(f"warm-up: {warm['warm_s']:.1f}s, {warm['warm_requests']} "
            f"requests, {prom_warm.get('llm_engine_recompiles', 0):.0f} "
            f"programs first dispatched so far")
        if mix["kind"] == "open":      # the holders were cut: slots free?
            for _ in range(400):
                if (await served.engine_metrics())[
                        "request_active_slots"] == 0:
                    break
                await asyncio.sleep(0.025)
        # an open mix may start its arrivals `lead_in_s` before the window:
        # that stretch is set-up, and the counters are read where it ends
        lead = float(mix.get("lead_in_s", 0.0)) if mix["kind"] == "open" \
            else 0.0
        if not lead:
            eng0, prom0 = await served.engine_metrics(), await served.prom()
        t0 = time.monotonic() + 0.2 + lead
        setup_s = t0 - T_START
        proc.stdin.write((json.dumps({"t0": t0}) + "\n").encode())
        await proc.stdin.drain()
        if lead:
            await asyncio.sleep(max(0.0, t0 - 0.2 - time.monotonic()))
            eng0, prom0 = await served.engine_metrics(), await served.prom()
        trace_dir, trace_wall = os.path.join(out_dir, "trace"), None
        stopping, trace_windows = None, (None, None)
        if args.trace:
            slice_s = min(TRACE_SLICE_S, args.seconds / 3)
            await asyncio.sleep(max(0.0, t0 + (args.seconds - slice_s) / 2
                                    - time.monotonic()))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0   # it slows the host it measures
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            ts = time.monotonic()

            async def windows():
                return (await served.engine_metrics())["decode_windows"]
            trace_windows = await slice_with_a_window(windows, slice_s)
            trace_wall = time.monotonic() - ts
            # stopping writes the file, and on a busy cell that outlasts
            # the window: the end-of-window scrape does not wait for it,
            # or it reads the batch draining behind the clients' cut and
            # counts what that first dispatches as compiled in the window
            stopping = asyncio.get_running_loop().run_in_executor(
                None, jax.profiler.stop_trace)
        await asyncio.sleep(max(0.0, t0 + args.seconds - time.monotonic()))
        eng1, prom1 = await served.engine_metrics(), await served.prom()
        scrape_late_s = time.monotonic() - (t0 + args.seconds)
        if stopping is not None:
            await stopping
            log(f"traced {trace_wall:.2f}s, {trace_windows[1]} decode windows "
                f"dispatched in it ({trace_windows[0]} in the first "
                f"{slice_s:.1f}s); profiler stopped "
                f"{time.monotonic() - ts - trace_wall:.1f}s "
                f"after the slice; the scrape came {scrape_late_s:.3f}s "
                f"after the window's end")
        await read_event(proc, "done", args.seconds + 90.0)
        if args.sweep:
            await sweep(args, proc, plan, out_dir)
        proc.stdin.write(b'{"stop": true}\n')
        await proc.stdin.drain()
        await proc.wait()
    finally:
        if proc.returncode is None:
            proc.kill()
            await proc.wait()

    with open(plan["rows_path"]) as f:
        rows = [json.loads(line) for line in f]
    e2e = stats.end_to_end(rows, t0, args.seconds, mix["kind"], chips)
    e2e["metrics"]["setup_s"] = setup_s
    recompiles = prom1.get("llm_engine_recompiles", 0) \
        - prom0.get("llm_engine_recompiles", 0)
    if recompiles:
        problems.append(f"{recompiles:.0f} programs compiled in the window")
    if e2e["attempted"] == 0:
        problems.append("no request finished in the window")
    lead_bad = [r["id"] for r in rows if r.get("phase") == "window"
                and r.get("due", t0) < t0 and not stats.request_ok(r)]
    if lead_bad:
        problems.append(f"lead-in requests failed: {lead_bad[:10]}")
    need = mix.get("min_requests_per_window", 0) * args.seconds \
        / bench_json["run_seconds"]
    if mix["kind"] == "open" and e2e["attempted"] < need:
        problems.append(f"{e2e['attempted']} requests, the tail needs "
                        f"{need:.0f}")
    mem = [d.memory_stats() or {} for d in jax.local_devices()]
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices),
              "memory_peak_bytes": max(
                  (int(m.get("peak_bytes_in_use", 0)) for m in mem),
                  default=0)}

    e2e_cells = {m["name"]: m.get("workloads")
                 for m in bench_json["end_to_end"]}

    def in_cell(m):
        """A listed cell's; without a list every cell's, and for a
        per-layer metric every cell's that reports the end-to-end metric
        it moves (itl_p95_ms is end to end in some cells only)."""
        cells = m.get("workloads", e2e_cells.get(m.get("moves")))
        return cells is None or cell_entry["name"] in cells

    result = {"correct": not problems and e2e["failed"] == 0,
              "attempted": e2e["attempted"], "failed": e2e["failed"],
              "metrics": {}, "device": device}
    breakdown = None
    if args.trace:
        trace = {}
        try:
            from harness import trace_reduce
            trace = trace_reduce.reduce_trace(trace_dir)
            if args.keep_trace:      # to look at by hand: what is in it
                with open(os.path.join(out_dir, "trace_planes.json"),
                          "w") as f:
                    json.dump(trace_reduce.describe(
                        trace_reduce.find_xplane(trace_dir)), f, indent=1)
        except Exception as e:   # a run without a trace still has counters
            log(f"trace reduction failed: {type(e).__name__}: {e}")
        if not args.keep_trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
        if trace.get("busy_s"):
            device["busy_s"] = trace["busy_s"]
            device["window_s"] = trace["window_s"]
            breakdown = {"device_ops": trace["device_ops"],
                         "idle_gaps": trace["idle_gaps"]}
        t_mid = t0 + args.seconds / 2
        from harness import shapes
        rctx = {
            "prom": (prom0, prom1), "engine": (eng0, eng1), "trace": trace,
            "client": stats.client_side(rows, t0, args.seconds, mix["kind"]),
            "peak": peaks.get(kind, {}),
            "run": {"decode_steps": ladders["decode_steps"], "chips": chips,
                    "decode_step_bytes": shapes.decode_step_bytes(
                        model_cfg, live_kv_tokens(rows, t_mid), chips)}}
        for m in bench_json["per_layer"]:
            if not in_cell(m):
                continue
            spec = readers.load_metric(m["name"], HERE)
            v = readers.evaluate(spec["expr"], rctx)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v,
                                                "unit": m["unit"]}
        # everything an expression can read, kept: a later metric, or an
        # old one under a new name, is evaluated on this run again without
        # the chip (tests/test_harness.py does)
        with open(os.path.join(out_dir, "layer_context.json"), "w") as f:
            json.dump(rctx, f, default=str)
        trace.pop("all_ops", None)
        trace.pop("modules", None)
        with open(os.path.join(out_dir, "trace_reduced.json"), "w") as f:
            json.dump({"trace": trace, "trace_wall_s": trace_wall}, f)
    else:
        for m in bench_json["end_to_end"]:
            if in_cell(m) and m["name"] in e2e["metrics"]:
                result["metrics"][m["name"]] = {
                    "value": e2e["metrics"][m["name"]], "unit": m["unit"]}
    if breakdown:
        result["breakdown"] = breakdown
    side = {"cell": cell_entry["name"], "seed": args.seed,
            "seconds": args.seconds, "problems": problems,
            "failed_ids": e2e["failed_ids"], "end_to_end": e2e["metrics"],
            "setup": {"ready_s": t_ready - T_START,
                      "checks_s": t_checked - t_ready,
                      "warmup_s": warm["warm_s"], "setup_s": setup_s},
            "scrape_late_s": scrape_late_s,
            # a traced run: the measured length of the slice, the decode
            # windows dispatched inside it, and inside its planned length
            # (0 there: the slice was held on until one came)
            "trace_slice_s": trace_wall,
            "trace_window_programs": trace_windows[1],
            "trace_window_programs_planned_slice": trace_windows[0],
            "programs_first_dispatched": prom1.get("llm_engine_recompiles"),
            "engine_delta": {k: eng1[k] - eng0[k] for k in eng0
                             if isinstance(eng0[k], (int, float))},
            "ladders": ladders, "result": result,
            # which (program, bucket) keys this run dispatched: a record
            # for whoever tunes the warm-up, read by nothing
            "programs": sorted(map(str, getattr(
                served.worker.engine, "_seen_programs", ())))}
    if args.rehearsal:
        result["rehearsal"] = True
        result["correct"] = False
    with open(os.path.join(out_dir, "run.json"), "w") as f:
        json.dump(side, f, indent=1)
    log("problems:", problems or "none", "| end to end:",
        json.dumps(e2e["metrics"]))
    return result


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearsal", action="store_true",
                   help="CPU, tiny model, same control flow; never a result")
    p.add_argument("--sweep", default="",
                   help="rates (a,b,c per second): after the run, one more "
                        "window per rate in the same process, to find an "
                        "open cell's knee by hand")
    p.add_argument("--keep-trace", action="store_true",
                   help="keep the raw trace and list its planes and lines")
    args = p.parse_args()
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        bench_json = json.load(f)
    cell_entry = find(bench_json["workloads"], args.workload, "workload")
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if int(cell_entry["chips"]) > 1:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "") +
                f" --xla_force_host_platform_device_count="
                f"{cell_entry['chips']}")
    try:
        import dynamo_tpu   # noqa: F401  the system under test
    except ImportError:
        die("the program (dynamo_tpu) is not in this checkout")
    try:
        result = asyncio.run(bench(args, bench_json, cell_entry))
    except Exception as e:   # any failure: nonzero exit, no result line
        import traceback
        traceback.print_exc()
        die(f"run failed: {type(e).__name__}: {e}", 1)
    print(json.dumps(result), flush=True)
    # the launcher's tasks and the engine's executor thread have no clean
    # stop from outside; the result is out, every child has been waited for
    os._exit(0)


if __name__ == "__main__":
    main()
