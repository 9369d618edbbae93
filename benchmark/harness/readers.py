"""Per-layer metrics as data. A metric is a file
`layer_metrics/<name>.json`:

  {"name", "layer", "unit", "better", "moves", "reader", "expr"}

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
    return spec


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
