"""Per-layer metrics as data. A metric is a file
`layer_metrics/<name>.json`:

  {"name", "layer", "unit", "better", "moves", "reader", "expr"}

One file holds an expression. A metric that reads what an accepted one
reads, in a cell the accepted one's list does not name (only a benchmark
PR may append to a list), says so instead of copying it:

  {"name", ..., "expr_of": "<the accepted metric's name>"}

and `load_metric` hands back that file's `expr` under this name. The
named file holds an `expr` of its own: no chains. The next benchmark PR
deletes such a file and appends its cell to the named metric's list.

`reader` names where the number comes from (`prom`, `engine`, `trace`,
`client`: recorded for the reader of the file; the evaluator below takes
every source alike) and `expr` is a small tree:

  {"prom": "<series>"}            delta over the window of the series'
                                  samples summed over labels (`/metrics`)
  {"prom_hist_mean": "<name>"}    delta of <name>_sum / delta of <name>_count
  {"prom_at_start": "<series>"}   the series as it stood when the window
                                  began: what set-up alone had done
  {"engine": "<field>"}           delta over the window of an
                                  `EngineMetrics` field
  {"trace": "<key>"}              a value of the reduced trace: window_s,
                                  busy_s, chips
  {"trace_module_median_s": "<pattern>"}   median duration of device-0
                                  program executions whose name matches
  {"trace_window_step_median_s": "<base>"}   seconds a device step of the
                                  LONGEST decode-window rung the traced
                                  slice holds: the median of `<base>_full`
                                  / run.decode_steps where the slice holds
                                  one, else of the longest `<base>_w<n>`
                                  / n (the rung is in the program's name)
  {"trace_window_rung_steps": "<base>"}   that rung's steps: which rung
                                  the leaf above read
  {"trace_op_share": "<pattern>"} time in ops matching / busy time
  {"client": "<key>"}             from the load generator's rows
  {"peak": "<key>"}               peaks.json for this device_kind
  {"run": "<key>"}                decode_steps, chips, decode_step_bytes
  {"const": x}
  {"op": "add"|"sub"|"mul"|"div", "args": [expr, expr]}

A leaf that finds nothing to read makes the whole metric None, and the
harness leaves it out of the line. A division by zero does too.
"""
from __future__ import annotations

import json
import os
import re
import statistics

READER_KINDS = ("prom", "engine", "trace", "client")
OPS = ("add", "sub", "mul", "div")


def parse_prom(text: str) -> dict:
    """Prometheus text -> {series name: sum of its samples over labels}."""
    out = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        head, _, value = line.rpartition(" ")
        name = head.partition("{")[0]
        try:
            out[name] = out.get(name, 0.0) + float(value)
        except ValueError:
            continue
    return out


def load_metric(name: str, root: str) -> dict:
    path = os.path.join(root, "layer_metrics", f"{name}.json")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"missing layer metric file: {path}")
    with open(path) as f:
        spec = json.load(f)
    if spec.get("reader") not in READER_KINDS:
        raise ValueError(f"{path}: unknown reader kind "
                         f"{spec.get('reader')!r} (known: {READER_KINDS})")
    if "expr_of" in spec:
        held = load_metric(spec["expr_of"], root)
        if "expr" in spec or "expr_of" in held:
            raise ValueError(f"{path}: `expr_of` stands where an `expr` "
                             f"would, and names a file that holds one")
        spec["expr"] = held["expr"]
    return spec


def window_rung(modules: dict, base: str, full_steps):
    """(steps, median seconds of one execution) of the longest rung of the
    decode-window ladder among `modules` ({program name: [durations_s]}),
    or None where it holds none. `<base>_full` is the ladder's top and
    runs `full_steps` device steps, `<base>_w<n>` runs n. A cell that is
    mostly mixed steps does not hold a full window in every 4 s slice
    (ledger, PR 46's notes): a shorter rung spreads the window's once-only
    gather over fewer steps, so its step reads longer, never shorter."""
    full, rung = re.compile(base + "_full"), re.compile(base + r"_w(\d+)$")
    by_steps = {}
    for name, ds in modules.items():
        if full.search(name):
            steps = full_steps
        else:
            m = rung.search(name)
            steps = int(m.group(1)) if m else None
        if steps and ds:
            by_steps.setdefault(int(steps), []).extend(ds)
    if not by_steps:
        return None
    steps = max(by_steps)
    return steps, statistics.median(by_steps[steps])


def _delta(pair, key):
    if pair is None:
        return None
    a, b = pair
    if key not in a or key not in b:
        return None
    return b[key] - a[key]


def evaluate(expr, ctx: dict):
    """`ctx`: prom (before, after), engine (before, after), trace, client,
    peak, run. Returns a float or None."""
    if "const" in expr:
        return float(expr["const"])
    if "prom" in expr:
        return _delta(ctx.get("prom"), expr["prom"])
    if "prom_at_start" in expr:
        before = (ctx.get("prom") or ({},))[0]
        return before.get(expr["prom_at_start"])
    if "prom_hist_mean" in expr:
        s = _delta(ctx.get("prom"), expr["prom_hist_mean"] + "_sum")
        n = _delta(ctx.get("prom"), expr["prom_hist_mean"] + "_count")
        return s / n if s is not None and n else None
    if "engine" in expr:
        return _delta(ctx.get("engine"), expr["engine"])
    for src in ("trace", "client", "peak", "run"):
        if src in expr:
            v = (ctx.get(src) or {}).get(expr[src])
            return float(v) if v is not None else None
    if "trace_module_median_s" in expr:
        pat = re.compile(expr["trace_module_median_s"])
        durs = [d for name, ds in ((ctx.get("trace") or {})
                                   .get("modules") or {}).items()
                if pat.search(name) for d in ds]
        return statistics.median(durs) if durs else None
    for leaf in ("trace_window_step_median_s", "trace_window_rung_steps"):
        if leaf in expr:
            found = window_rung(
                (ctx.get("trace") or {}).get("modules") or {}, expr[leaf],
                (ctx.get("run") or {}).get("decode_steps"))
            if found is None:
                return None
            steps, median = found
            return median / steps if leaf.endswith("_s") else float(steps)
    if "trace_op_share" in expr:
        tr = ctx.get("trace") or {}
        pat = re.compile(expr["trace_op_share"])
        if not tr.get("busy_s") or "all_ops" not in tr:
            return None
        return sum(t for n, t in tr["all_ops"] if pat.search(n)) \
            / tr["busy_s"]
    op = expr.get("op")
    if op not in OPS:
        raise ValueError(f"unknown expression {expr!r} (ops: {OPS})")
    vals = [evaluate(a, ctx) for a in expr["args"]]
    if any(v is None for v in vals):
        return None
    a, b = vals
    if op == "add":
        return a + b
    if op == "sub":
        return a - b
    if op == "mul":
        return a * b
    return a / b if b else None
