"""From a profiler capture of the serving loop to a table: device time by
program, by bucket and by named scope, and the device's idle gaps by what
the host loop was doing.

    python -m dynamo_tpu.observability.profile <trace_dir>

reads the `*.xplane.pb` under `<trace_dir>` (through
`jax.profiler.ProfileData`, as benchmark/harness/trace_reduce.py does)
and writes `profile_summary.json` beside it. `NativeEngineWorker.
capture_profile` runs exactly that in a child process after `stop_trace`
(llm/worker.py); `POST /debug/profile` answers with the file's path and
its top level. docs/OBSERVABILITY.md section 5 has the schema and a
runbook.

What a v5e xplane holds (looked at with `ProfileData` on the chip, PR 52):
an `XLA Ops` event is named by its whole HLO line WITHOUT the metadata and
carries only `device_offset_ps` / `device_duration_ps`; the ops of a
`while` / `conditional` / `call` body lie nested inside the container's
event on the same line; a `TraceAnnotation`'s keyword arguments come back
as the host event's stats, beside its name; an `XLA Modules` event and the
runtime's host events `DoEnqueueProgram` / `CompleteCallbacks` carry one
`run_id`; and the device's clock reads earlier than the host's by a
constant of the capture (1.3-1.6 ms in the first one looked at). So

- an op's SELF time is its duration less the events nested in it (a
  `while` is its loop overhead, not its body);
- an op's scope comes from the optimised HLO text of the program it ran in
  (instruction name -> `op_name`), which `capture_profile` writes beside
  the capture (`programs/*.hlo.txt`, NativeEngine.program_texts; for a
  benchmark run's kept trace, tools/trace_programs.py does); the leaf is
  the innermost name of
  `observability/metrics.SCOPES` on the `op_name`'s path, a bare family
  name never displacing a leaf of its own family (`scope_of`). A fusion
  counts to the scope of the instruction that names it: the `op_name` on
  the fusion's own line, which XLA takes from the fused root;
- an execution's kind and bucket come from the `engine.dispatch` /
  `engine.compile` annotation of the launch (engine.NativeEngine.
  _dispatch_phase), found through the `run_id`: the dispatch inside
  which the runtime enqueued the run; where a trace holds no `run_id`,
  the k-th dispatch and the k-th engine program on the `XLA Modules` line
  are one step;
- the device's events are moved onto the host's clock before a gap is put
  down to the host loop: by the least shift for which no run starts
  before it was enqueued (`device.clock_shift_ns`, with the bounds the
  enqueues and the completion callbacks leave it);
- the host loop is the engine's flat `engine.<phase>` events, the
  worker's `worker.emit` / `worker.apply_pending`, and the stretches
  between them: `resume` from a call's last phase to the next
  `worker.emit`, `emit` from there to `worker.apply_pending`, `submit`
  from its end to the next call's first phase.

The core (`reduce_capture`: planes and HLO texts in, table out) imports
nothing of the engine, so that the benchmark can take it as a file of its
own. `ITEMSIZE`, `_SHAPE`, `_INSTR`, `_OPNAME`, `shape_bytes` and
`split_computations` are the one parser of optimised HLO lines in the
tree: tools/pool_ops.py imports them from here.
"""
from __future__ import annotations

import bisect
import glob
import json
import os
import re
import statistics
import sys
import time
from collections import defaultdict
from typing import Dict, List, Optional

from dynamo_tpu.observability.metrics import SCOPES, scope_family

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = re.compile(r"^/host:")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
MIN_GAP_NS = 20_000     # the benchmark's MIN_GAP_S: launch latency below
TOP_OPS = 20
CONTAINERS = ("while", "conditional", "call")
SUMMARY = "profile_summary.json"
# a dispatch's `kind` -> the XLA module it launches
KIND_MODULE = {"mixed": "jit_engine_step", "prefill": "jit_engine_step",
               "window": "jit_engine_decode_window",
               "verify": "jit_engine_verify_step"}

# -- optimised HLO text ---------------------------------------------------------

ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
            "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
            "u64": 8}
_SHAPE = re.compile(r"\b(pred|s8|u8|bf16|f16|s16|u16|f32|s32|u32|f64|s64|u64)"
                    r"\[([0-9,]*)\](\{[^}]*\})?")
_INSTR = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*?)\s([\w\-]+)\(")
_OPNAME = re.compile(r'op_name="([^"]*)"')
_MODULE = re.compile(r"^HloModule\s+([\w.\-]+)", re.M)


def shape_bytes(text: str) -> int:
    """Largest array in an HLO result type (a tuple's largest element)."""
    best = 0
    for dt, dims, _ in _SHAPE.findall(text):
        n = ITEMSIZE[dt]
        for d in filter(None, dims.split(",")):
            n *= int(d)
        best = max(best, n)
    return best


def split_computations(hlo: str) -> Dict[str, List[str]]:
    """HLO module text -> {computation name: its instruction lines}."""
    comps: Dict[str, List[str]] = {}
    cur: Optional[str] = None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$",
                        line)
        if head:
            cur = head.group(1)
            comps[cur] = []
        elif line.startswith("}"):
            cur = None
        elif cur is not None and "=" in line:
            comps[cur].append(line)
    return comps


def instruction_scopes(hlo: str) -> tuple:
    """Optimised HLO text -> (module name, {instruction name: `op_name`}),
    every computation's instructions together: a name is unique in its
    module, and a trace's op events say the name alone."""
    module = _MODULE.search(hlo)
    names = {}
    for lines in split_computations(hlo).values():
        for line in lines:
            m = _INSTR.match(line)
            if m:
                found = _OPNAME.search(line)
                names[m.group(2)] = found.group(1) if found else ""
    return (module.group(1) if module else ""), names


def scope_of(op_name: str, scopes=SCOPES) -> str:
    """The leaf an `op_name` path counts to: its innermost component (or
    pair of components: `block.parallel/ssm.conv` is one name) on the
    closed list, but that a bare family name inside a leaf of its own
    family leaves the leaf standing (`attention.window/attention/...`
    is `attention.window`, `mlp.dense_lead/mlp` is `mlp.dense_lead`).
    "" where the path holds no name of the list."""
    parts = op_name.split("/")
    leaf = tail = ""
    for i, part in enumerate(parts):
        pair = f"{parts[i - 1]}/{part}" if i else ""
        name = pair if pair in scopes else part if part in scopes else ""
        if not name:
            continue
        new_tail = name.rsplit("/", 1)[-1]
        if tail == new_tail or tail.startswith(new_tail + "."):
            continue
        leaf, tail = name, new_tail
    return leaf


# -- the capture's planes -----------------------------------------------------------

def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return paths[-1]


def load_planes(xplane_path: str) -> list:
    """[(plane, [(line, [(start_ns, end_ns, name, stats)])])]: what
    `reduce_capture` takes, and what a fixture under tests/ holds as
    JSON. `stats` is the event's own, numbers and short strings."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(xplane_path)
    return [(p.name, [(ln.name, [
        (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name,
         {k: v for k, v in ev.stats
          if isinstance(v, (int, float)) or len(str(v)) <= 64})
        for ev in ln.events]) for ln in p.lines]) for p in data.planes]


def load_programs(root: str) -> Dict[str, str]:
    """{file: optimised HLO text} of the `*.hlo.txt` under `root`."""
    out = {}
    for path in sorted(glob.glob(os.path.join(root, "*.hlo.txt"))):
        with open(path, errors="replace") as f:
            out[os.path.basename(path)] = f.read()
    return out


# -- the core: planes and HLO texts in, table out -------------------------------------

def union(intervals) -> list:
    """Merge [(start, end)] into disjoint sorted intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _span(ev) -> tuple:
    """(start_ns, end_ns) of an event: a device event's own picoseconds
    where its stats carry them (`ProfileData` cuts them to whole ns, a
    third of a 3 ns op)."""
    stats = ev[3]
    if "device_offset_ps" in stats:
        return (stats["device_offset_ps"] / 1e3, (
            stats["device_offset_ps"] + stats["device_duration_ps"]) / 1e3)
    return ev[0], ev[1]


def strip_fingerprint(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def self_times(ops: list) -> list:
    """[(start, end, name)] of one `XLA Ops` line -> [self_ns] in the
    same order: an event's duration less the events nested directly
    inside it (same line: a container's body runs inside its event)."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][0], -ops[i][1]))
    own = [0.0] * len(ops)
    stack = []
    for i in order:
        s, e = ops[i][0], ops[i][1]
        while stack and ops[stack[-1]][1] <= s:
            stack.pop()
        own[i] = e - s
        if stack:
            own[stack[-1]] -= e - s
        stack.append(i)
    return [max(0.0, t) for t in own]


def _bucket(stats: dict) -> str:
    if "rung" in stats:
        return f"{stats.get('rows', '?')}xw{stats['rung']}"
    if "chunk" in stats:
        return f"{stats.get('rows', '?')}x{stats['chunk']}"
    return ""


def join_dispatches(dispatches: list, modules: list,
                    enqueued: Optional[dict] = None) -> list:
    """The launch of every engine program run: `dispatches`
    [(start, end, stats)] and `modules` [(start, end, name, stats)], both
    in time order -> one stats dict (or None) a module. `enqueued`
    {run_id: host ns at which the runtime enqueued the run}: a module
    that carries a `run_id` found there was launched by the dispatch that
    was open then. Without run ids the join is by order. The first few
    runs of a capture were launched before it began and have no dispatch
    in it: the join is the smallest number of such runs for which every
    dispatch launches the module its kind names and one executable (a
    name's fingerprint) keeps one bucket."""
    def compatible(stats, name):
        want = KIND_MODULE.get(stats.get("kind"))
        return want is None or strip_fingerprint(name).startswith(want)

    if enqueued and any(m[3].get("run_id") in enqueued for m in modules):
        begun = [d[0] for d in dispatches]
        out = []
        for m in modules:
            at = enqueued.get(m[3].get("run_id"))
            i = None if at is None else bisect.bisect_right(begun, at) - 1
            out.append(dispatches[i][2] if i is not None and i >= 0
                       and compatible(dispatches[i][2], m[2]) else None)
        return out
    best = None
    for skip in range(min(4, len(modules) + 1)):
        pairs = list(zip(dispatches, modules[skip:]))
        if any(not compatible(d[2], m[2]) for d, m in pairs):
            continue
        buckets = defaultdict(set)
        for d, m in pairs:
            buckets[m[2]].add((d[2].get("kind"), _bucket(d[2])))
        clash = sum(len(b) > 1 for b in buckets.values())
        if best is None or clash < best[0]:
            best = (clash, skip)
        if not clash:
            break
    out = [None] * len(modules)
    if best is not None:    # else no join by order holds: no run a bucket
        for (_, _, stats), i in zip(dispatches,
                                    range(best[1], len(modules))):
            out[i] = stats
    return out


def host_loop(host_events: list) -> list:
    """The engine's host loop as disjoint sorted segments
    [(start, end, part)] from a host plane's events [(start, end, name)]:
    the flat `engine.<phase>` and `worker.*` annotations, and between
    two of them `resume` (a phase, then `worker.emit`), `emit`
    (`worker.emit`, then `worker.apply_pending`) or `submit`
    (`worker.apply_pending`, then a phase)."""
    spans = sorted((s, e, n.split(".", 1)[1]) for s, e, n in host_events
                   if n.startswith("engine.") or n in (
                       "worker.emit", "worker.apply_pending"))
    phases = {n.split(".", 1)[1] for _, _, n in host_events
              if n.startswith("engine.")}
    out = []
    for prev, nxt in zip(spans, spans[1:]):
        out.append(prev)
        if nxt[0] <= prev[1]:
            continue
        a, b = prev[2], nxt[2]
        part = "resume" if a in phases and b == "emit" else \
            "emit" if a == "emit" and b == "apply_pending" else \
            "submit" if a == "apply_pending" and b in phases else None
        if part:
            out.append((prev[1], nxt[0], part))
    out.extend(spans[-1:])
    return out


def reduce_capture(planes: list, programs: Optional[Dict[str, str]] = None,
                   scopes=SCOPES) -> dict:
    """`planes` as `load_planes` gives them; `programs` {any name: the
    optimised HLO text of a program the capture may hold}. The table
    `profile_summary.json` holds (docs/OBSERVABILITY.md section 5)."""
    lo = hi = None
    devices, host = [], []
    for pname, lines in planes:
        for _, evs in lines:
            for ev in evs:
                lo = ev[0] if lo is None or ev[0] < lo else lo
                hi = ev[1] if hi is None or ev[1] > hi else hi
        if DEVICE_PLANE.match(pname):
            devices.append((pname, dict(lines)))
        elif HOST_PLANE.match(pname):
            for _, evs in lines:
                host.extend(evs)
    if lo is None:
        return {}
    devices.sort()
    out = {"device": {"window_s": (hi - lo) / 1e9, "chips": len(devices)}}
    if not devices:
        return out
    busy = []
    for _, lines in devices:
        evs = lines.get(OPS_LINE) or lines.get(MODULES_LINE) or []
        busy.append(sum(e - s for s, e in union(
            (ev[0], ev[1]) for ev in evs)) / 1e9)
    out["device"]["busy_s"] = sum(busy) / len(busy)
    out["device"]["idle_s"] = out["device"]["window_s"] \
        - out["device"]["busy_s"]

    dev0 = devices[0][1]
    ops = sorted((*_span(ev), ev[2]) for ev in dev0.get(OPS_LINE, ()))
    runs = sorted((*_span(ev), ev[2], ev[3])
                  for ev in dev0.get(MODULES_LINE, ()))
    engine_runs = [i for i, r in enumerate(runs)
                   if r[2].startswith("jit_engine_")]
    dispatches = sorted(
        ((ev[0], ev[1], ev[3]) for ev in host
         if ev[2] in ("engine.dispatch", "engine.compile")
         and "kind" in ev[3]),
        key=lambda d: d[0])
    by_run = {name: {ev[3]["run_id"]: ev[0] for ev in host
                     if ev[2] == name and "run_id" in ev[3]}
              for name in ("DoEnqueueProgram", "CompleteCallbacks")}
    joined = join_dispatches(dispatches, [runs[i] for i in engine_runs],
                             by_run["DoEnqueueProgram"])
    launch = dict(zip(engine_runs, joined))
    # the device's clock on the host's: no run starts before the runtime
    # enqueued it, none ends after its completion callback began
    lo_ns = [by_run["DoEnqueueProgram"][r[3]["run_id"]] - r[0] for r in runs
             if r[3].get("run_id") in by_run["DoEnqueueProgram"]]
    hi_ns = [by_run["CompleteCallbacks"][r[3]["run_id"]] - r[1] for r in runs
             if r[3].get("run_id") in by_run["CompleteCallbacks"]]
    shift = 0.0
    if lo_ns and max(lo_ns) > 0:
        shift = max(lo_ns)
    elif hi_ns and min(hi_ns) < 0:
        shift = min(hi_ns)
    out["device"]["clock_shift_ns"] = shift
    out["device"]["clock_shift_bounds_ns"] = [
        max(lo_ns) if lo_ns else None, min(hi_ns) if hi_ns else None]
    # one executable keeps one bucket: a run launched before the capture
    # began takes what the other runs of its fingerprint were launched as
    by_print = {}
    for i, stats in launch.items():
        if stats:
            by_print.setdefault(runs[i][2], stats)
    identity = []       # a run's (program, kind, bucket)
    for i, r in enumerate(runs):
        stats = launch.get(i) or by_print.get(r[2]) or {}
        identity.append((strip_fingerprint(r[2]), stats.get("kind", ""),
                         _bucket(stats)))

    # an op's run: the module event that holds its start
    starts = [r[0] for r in runs]
    own = self_times(ops)
    run_of = []
    for s, _, _ in ops:
        i = bisect.bisect_right(starts, s) - 1
        run_of.append(i if i >= 0 and s < runs[i][1] else None)

    # an executable's HLO text: of the texts of its module's name, the one
    # that holds most of the instruction names its ops show
    parsed = [instruction_scopes(text)
              for text in (programs or {}).values()]
    seen = defaultdict(set)
    instr = []
    for (_, _, name), i in zip(ops, run_of):
        m = _INSTR.match(name)
        instr.append((m.group(2), m.group(4)) if m else (name[:64], ""))
        if i is not None:
            seen[runs[i][2]].add(instr[-1][0])
    leaves = {}         # fingerprint -> {instruction name: its leaf}
    for fingerprint, names in seen.items():
        module = strip_fingerprint(fingerprint)
        found = max((p for p in parsed if p[0] == module),
                    key=lambda p: len(names & p[1].keys()), default=None)
        if found and len(names & found[1].keys()) * 2 >= len(names):
            leaves[fingerprint] = {name: scope_of(found[1][name], scopes)
                                   for name in names & found[1].keys()}

    rows = defaultdict(lambda: {"runs": 0, "device": [], "leaf": defaultdict(
        float), "copies": defaultdict(float), "bare": defaultdict(float),
        "covered": 0.0})
    for i, r in enumerate(runs):
        row = rows[identity[i]]
        row["runs"] += 1
        row["device"].append(r[1] - r[0])
    by_op = defaultdict(lambda: [0.0, 0, None])
    for (s, e, name), t, i, (iname, opcode) in zip(ops, own, run_of, instr):
        if i is None:
            continue
        row = rows[identity[i]]
        row["covered"] += t
        leaf = leaves.get(runs[i][2], {}).get(iname, "")
        if opcode in CONTAINERS:
            row["leaf"]["containers"] += t
        else:
            row["leaf"][leaf or "unscoped"] += t
            if not leaf:
                row["bare"][opcode] += t
        if opcode.startswith(("copy", "transpose")):
            row["copies"][leaf or "unscoped"] += t
        key = (identity[i], iname, opcode, leaf or "unscoped")
        by_op[key][0] += t
        by_op[key][1] += 1
        if by_op[key][2] is None:
            by_op[key][2] = shape_bytes(name.split("=", 1)[-1].split(
                opcode + "(", 1)[0]) if opcode else 0

    # engine.wait's tail behind a program's end
    waits = sorted((ev[0], ev[1]) for ev in host if ev[2] == "engine.wait")
    wait_ends = [w[1] for w in waits]
    tails = defaultdict(list)
    for i, r in enumerate(runs):
        end = r[1] + shift
        at = bisect.bisect_left(wait_ends, end)
        if at < len(waits) and waits[at][0] <= end:
            tails[identity[i]].append(waits[at][1] - end)

    def table(leaf_ns: dict, device_ns: float, n: int) -> dict:
        fam = defaultdict(float)
        for leaf, t in leaf_ns.items():
            fam[scope_family(leaf)] += t
        return {kind: {name: {"ms": t / n / 1e6,
                              "share": t / device_ns if device_ns else 0.0}
                       for name, t in sorted(src.items(),
                                             key=lambda kv: -kv[1])}
                for kind, src in (("leaf", leaf_ns), ("family", fam))}

    out["programs"] = []
    for (program, kind, bucket), row in sorted(
            rows.items(), key=lambda kv: -sum(kv[1]["device"])):
        total = sum(row["device"])
        row["leaf"]["idle_in_program"] = max(0.0, total - row["covered"])
        out["programs"].append({
            "program": program, "kind": kind, "bucket": bucket,
            "runs": row["runs"],
            "device_ms_median": statistics.median(row["device"]) / 1e6,
            "device_ms_mean": total / row["runs"] / 1e6,
            "device_s": total / 1e9,
            "wait_tail_ms": (statistics.median(tails[program, kind, bucket])
                             / 1e6 if tails[program, kind, bucket]
                             else None),
            "scopes": table(row["leaf"], total, row["runs"]),
            "copies": {leaf: t / row["runs"] / 1e6 for leaf, t in sorted(
                row["copies"].items(), key=lambda kv: -kv[1])},
            "unscoped_by_opcode": {
                opcode: t / row["runs"] / 1e6 for opcode, t in sorted(
                    row["bare"].items(), key=lambda kv: -kv[1])},
            "scoped_from_hlo": any(
                runs[i][2] in leaves for i in range(len(runs))
                if identity[i] == (program, kind, bucket))})
    out["top_ops"] = [
        {"op": iname, "opcode": opcode, "scope": leaf,
         "program": ident[0], "kind": ident[1], "bucket": ident[2],
         "self_s": t / 1e9, "events": n, "out_bytes": nbytes}
        for (ident, iname, opcode, leaf), (t, n, nbytes) in sorted(
            by_op.items(), key=lambda kv: -kv[1][0])[:TOP_OPS]]

    # the device's idle gaps, by the steps around and the host loop's part
    merged = union((s, e) for s, e, _ in ops) if ops else union(
        (r[0], r[1]) for r in runs)
    gaps = [(a[1], b[0]) for a, b in zip(merged, merged[1:])
            if b[0] - a[1] >= MIN_GAP_NS]
    loop = host_loop([(ev[0], ev[1], ev[2]) for ev in host])
    loop_starts = [seg[0] for seg in loop]
    cells = defaultdict(lambda: [0, 0.0])
    for gs, ge in gaps:
        left = bisect.bisect_right(starts, gs) - 1
        right = bisect.bisect_left(starts, ge)
        hs, he = gs + shift, ge + shift     # the gap on the host's clock
        kinds = "{}->{}".format(
            (identity[left][1] or identity[left][0]) if left >= 0 else "",
            (identity[right][1] or identity[right][0])
            if right < len(runs) else "")
        if left >= 0 and runs[left][1] >= ge:
            kinds = f"in {identity[left][1] or identity[left][0]}"
        part, most = "none", 0.0
        at = max(0, bisect.bisect_right(loop_starts, hs) - 1)
        for s, e, name in loop[at:]:
            if s >= he:
                break
            overlap = min(e, he) - max(s, hs)
            if overlap > most:
                part, most = name, overlap
        cells[kinds, part][0] += 1
        cells[kinds, part][1] += (ge - gs) / 1e9
    rows_out = [{"kinds": k, "part": p, "count": n, "seconds": t}
                for (k, p), (n, t) in sorted(cells.items(),
                                             key=lambda kv: -kv[1][1])]

    def margin(field):
        got = defaultdict(lambda: [0, 0.0])
        for r in rows_out:
            got[r[field]][0] += r["count"]
            got[r[field]][1] += r["seconds"]
        return {k: {"count": n, "seconds": t} for k, (n, t) in sorted(
            got.items(), key=lambda kv: -kv[1][1])}

    out["idle_gaps"] = {"count": len(gaps),
                        "seconds": sum(ge - gs for gs, ge in gaps) / 1e9,
                        "by_kinds": margin("kinds"),
                        "by_part": margin("part"), "rows": rows_out}
    out["dispatches"] = {"seen": len(dispatches),
                         "joined": sum(s is not None for s in joined),
                         "engine_runs": len(engine_runs)}
    return out


def top_level(summary: dict) -> dict:
    """What `POST /debug/profile` answers with beside the file's path:
    the device's seconds and one line a program, without its scopes."""
    return {"device": summary.get("device", {}),
            "programs": [{k: p[k] for k in (
                "program", "kind", "bucket", "runs", "device_ms_median")}
                for p in summary.get("programs", ())],
            "idle_gaps": {k: summary.get("idle_gaps", {}).get(k)
                          for k in ("count", "seconds", "by_part")}}


def _peak_rss_mb() -> Optional[float]:
    """This process's own peak resident memory. `VmHWM`, not
    `ru_maxrss`: a child started by fork + exec inherits the high-water
    mark of the image it was forked from (a capture's child read the
    serving process's 16 GB)."""
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmHWM:"):
                    return int(line.split()[1]) / 1024
    except OSError:
        pass
    return None


def reduce_trace(trace_dir: str) -> str:
    """Reduce the capture under `trace_dir`, with the programs' HLO texts
    in `programs/` beside its xplane, and write `SUMMARY` there; returns
    the file's path."""
    t0 = time.perf_counter()
    xplane = find_xplane(trace_dir)
    home = os.path.dirname(xplane)
    programs = load_programs(os.path.join(home, "programs"))
    summary = reduce_capture(load_planes(xplane), programs)
    summary["source"] = {
        "xplane": xplane, "programs": sorted(programs),
        "reduce_s": time.perf_counter() - t0,
        "reduce_max_rss_mb": _peak_rss_mb()}
    path = os.path.join(home, SUMMARY)
    with open(path, "w") as f:
        json.dump(summary, f, indent=1)
    return path


def main(argv=None) -> int:
    import argparse
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("trace_dir")
    args = p.parse_args(argv)
    path = reduce_trace(args.trace_dir)
    with open(path) as f:
        print(json.dumps({"summary": path, **top_level(json.load(f))}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
