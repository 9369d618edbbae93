"""Minimal Prometheus-compatible metrics registry.

Role-equivalent of the reference's prometheus crates usage (reference:
lib/llm/src/http/service/metrics.rs:24-130 — counters/gauges/histograms with
model/endpoint/status labels, exposed on GET /metrics in text exposition
format). Stdlib-only: the image has no prometheus_client, and the needs are
small (label vectors, histogram buckets, text rendering).
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

LabelKey = Tuple[str, ...]


# The names a `jax.named_scope` of a step program may carry: the closed
# list a device trace is read by (observability/profile.py puts every op's
# self time down to the innermost of these on its `op_name`;
# docs/OBSERVABILITY.md section 5 says what each covers). A leaf's FAMILY
# is what stands before its first dot, after the last slash
# (`attention.mla.absorb` -> `attention`, `block.parallel/ssm.conv` ->
# `ssm`). `step` and `layers.body` enclose a whole program and a whole
# layer body: what rests on them is the glue no narrower scope names
# (index plans, residual adds, reshapes). tests/test_step_tracing.py walks
# the jaxpr of every served program and fails on a scope outside this
# list and, inside a layer body, on an equation in none.
SCOPES = (
    # a program outside its layers
    "step", "step.unpack", "step.compact", "embed", "head", "sampler",
    "kv.write", "kv.window",
    # a layer body, whatever its kind
    "layers.body", "layers.lead", "layers.stack",
    "norm.attn", "norm.mlp", "norm.post",
    # softmax attention, latent attention among it
    "attention", "attention.qkv", "attention.rope", "attention.qk_norm",
    "attention.head_qk_norm", "attention.gather", "attention.window",
    "attention.full", "attention.out_gate", "attention.wo",
    "attention.mla.q", "attention.mla.latent", "attention.mla.absorb",
    "attention.mla.out", "attention.mla.gate",
    # the MLP, dense or experts
    "mlp", "mlp.dense_lead", "moe", "moe.route", "moe.route.groups",
    "moe.dispatch", "moe.experts", "moe.combine", "moe.shared",
    # linear attention (Kimi Delta) over state slots
    "linattn.in_proj", "linattn.gate", "linattn.conv", "linattn.chunk",
    "linattn.step", "linattn.out", "linattn.wo",
    # a parallel block: attention beside a state-space mixer
    "block.parallel/attention", "block.parallel/ssm.in_proj",
    "block.parallel/ssm.conv", "block.parallel/ssm.step",
    "block.parallel/ssm.chunk", "block.parallel/ssm.norm_out",
    "block.parallel/ssm.out_proj",
    # a gated short convolution
    "shortconv.in_proj", "shortconv.gate", "shortconv.taps",
    "shortconv.out_proj",
    # power retention: a matrix state a key-value head, no pages
    "retention.front", "retention.phi", "retention.step", "retention.chunk",
    "retention.out",
)


def scope_family(leaf: str) -> str:
    """`attention.mla.absorb` -> `attention`; `block.parallel/ssm.conv`
    -> `ssm`."""
    return leaf.rsplit("/", 1)[-1].split(".", 1)[0]


class PhaseTimer:
    """Cumulative wall-time attribution across named phases.

    The engine wraps each leg of a step in `with timer.phase(name):`, on
    every step kind alike (mixed, prefill, spec verify, decode window,
    pipelined window), from a fixed vocabulary:

      plan      scheduler.schedule() and the offload/onboard/pool-inject
                work before dispatch
      upload    sampling/penalty array assembly and every host->device
                staging of plan arrays
      dispatch  the jit call until it returns (a key's first dispatch is
                annotated `compile`, see `phase`)
      wait      blocked in device_get / block_until_ready
      commit    scheduler commits, postprocess, events, ledger record

    `phase` is the one call site and does three things: accumulates
    seconds and counts (always; two perf_counter() calls), records a span
    through the tracer's deferred recorder when `trace_scope` is set and
    DYN_TRACE is on (runtime/tracing.py `defer_phase`: branch-only when
    off), and opens a `jax.profiler.TraceAnnotation("<scope>.<name>")` so
    a profiler capture, whoever started it, sees the host loop on its own
    clock (a TraceMe outside a capture costs a branch). It is the only
    recording form allowed inside `# dynalint: hot-path-begin/end` regions
    (R13), which is where the engine's phase() calls live. Phases of one
    step are flat and contiguous: none encloses another, so a reducer
    that labels an idle gap by the host span overlapping it most
    (benchmark/harness/trace_reduce.py) names the phase, not a wrapper.

    `device_busy` is the engine's word on whether a dispatched program is
    still unfetched: it becomes true when a dispatch phase ends and the
    engine clears it after a wait that leaves nothing in flight. Time in
    any phase but `wait`, and between steps, while it is false is
    `exposed`: the host's own estimate of the device idle it causes.
    `stats`, when set (the engine passes its LedgerStats), receives the
    same sums as `host_<phase>_seconds`, `host_between_seconds`,
    `host_exposed_seconds` and, for the exposed part of `between` alone,
    `host_exposed_between_seconds`, which /metrics renders as
    `llm_engine_*`.

    `call` holds the phases of the step() call in progress, name ->
    [start (perf_counter), seconds]: the engine takes it at step()'s end
    (`take_call`) for the call's record in the StepLedger; the totals
    above are not touched by that.
    """

    PHASES = ("plan", "upload", "dispatch", "wait", "commit")
    _annotation = None      # jax.profiler.TraceAnnotation, on first use

    def __init__(self):
        self.seconds: Dict[str, float] = {}
        self.counts: Dict[str, int] = {}
        self.trace_scope: Optional[str] = None
        self.stats = None
        self.device_busy = False
        self.exposed = 0.0
        self.call: Dict[str, list] = {}

    def add(self, name: str, dt: float, t0: float = 0.0) -> None:
        self.seconds[name] = self.seconds.get(name, 0.0) + dt
        self.counts[name] = self.counts.get(name, 0) + 1
        mine = self.call.get(name)
        if mine is None:
            self.call[name] = [t0, dt]
        else:
            mine[1] += dt
        exposed = name != "wait" and not self.device_busy
        if exposed:
            self.exposed += dt
        s = self.stats
        if s is not None:
            field = _STAT_FIELD.get(name)
            if field is not None:
                setattr(s, field, getattr(s, field) + dt)
            if exposed:
                s.host_exposed_seconds += dt
                if name == "between":
                    s.host_exposed_between_seconds += dt

    def take_call(self) -> Dict[str, list]:
        """The phases of the call that just ended, and a clean slate for
        the next."""
        call, self.call = self.call, {}
        return call

    @contextlib.contextmanager
    def phase(self, name: str, annotation: Optional[str] = None,
              stats: Optional[dict] = None):
        """Time one phase. `annotation` renames the span a trace shows
        (the engine opens a program's first dispatch as `compile`); the
        seconds still accumulate under `name`. `stats` ride the
        annotation as TraceMe stats, beside its name and not in it (the
        engine gives a dispatch the identity of what it launched: a
        capture then joins the k-th dispatch to the k-th program the
        device ran, observability/profile.py); outside a capture they
        cost the dict."""
        cls = PhaseTimer._annotation
        if cls is None:
            from jax.profiler import TraceAnnotation as cls
            PhaseTimer._annotation = cls
        label = annotation or name
        t0 = time.perf_counter()
        try:
            with cls(f"{self.trace_scope or 'phase'}.{label}",
                     **(stats or {})):
                yield
        finally:
            dt = time.perf_counter() - t0
            self.add(name, dt, t0)
            if name == "dispatch":
                self.device_busy = True
            if self.trace_scope is not None:
                from dynamo_tpu.runtime.tracing import TRACER
                TRACER.defer_phase(self.trace_scope, label, dt)

    def reset(self) -> None:
        self.seconds.clear()
        self.counts.clear()
        self.call.clear()
        self.exposed = 0.0


_STAT_FIELD = {name: f"host_{name}_seconds"
               for name in PhaseTimer.PHASES + ("between",)}


def _fmt_value(v: float) -> str:
    if v == float("inf"):
        return "+Inf"
    if float(v).is_integer():
        return str(int(v))
    return repr(float(v))


def _esc(v: str) -> str:
    """Escape a label value per the Prometheus exposition format."""
    return str(v).replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


def _fmt_labels(names: Sequence[str], values: LabelKey,
                extra: Optional[Dict[str, str]] = None) -> str:
    parts = [f'{n}="{_esc(v)}"' for n, v in zip(names, values)]
    if extra:
        parts += [f'{n}="{_esc(v)}"' for n, v in extra.items()]
    return "{" + ",".join(parts) + "}" if parts else ""


class _Metric:
    kind = "untyped"

    def __init__(self, name: str, help_: str, label_names: Sequence[str] = ()):
        self.name = name
        self.help = help_
        self.label_names = tuple(label_names)
        self._lock = threading.Lock()
        self._values: Dict[LabelKey, float] = {}

    def _check(self, labels: LabelKey):
        if len(labels) != len(self.label_names):
            raise ValueError(
                f"{self.name}: expected labels {self.label_names}, got {labels}")

    def remove(self, *labels: str) -> None:
        """Drop one label series (e.g. a departed worker instance)."""
        self._check(labels)
        with self._lock:
            self._values.pop(labels, None)

    def render(self) -> List[str]:
        out = [f"# HELP {self.name} {self.help}",
               f"# TYPE {self.name} {self.kind}"]
        for labels, v in sorted(self._values.items()):
            out.append(f"{self.name}"
                       f"{_fmt_labels(self.label_names, labels)} {_fmt_value(v)}")
        if not self._values and not self.label_names:
            out.append(f"{self.name} 0")
        return out


class Counter(_Metric):
    kind = "counter"

    def inc(self, *labels: str, value: float = 1.0) -> None:
        self._check(labels)
        with self._lock:
            self._values[labels] = self._values.get(labels, 0.0) + value

    def get(self, *labels: str) -> float:
        return self._values.get(labels, 0.0)


class Gauge(_Metric):
    kind = "gauge"

    def set(self, *labels: str, value: float) -> None:
        self._check(labels)
        with self._lock:
            self._values[labels] = float(value)

    def inc(self, *labels: str, value: float = 1.0) -> None:
        self._check(labels)
        with self._lock:
            self._values[labels] = self._values.get(labels, 0.0) + value

    def dec(self, *labels: str, value: float = 1.0) -> None:
        self.inc(*labels, value=-value)

    def get(self, *labels: str) -> float:
        return self._values.get(labels, 0.0)


DEFAULT_BUCKETS = (0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0,
                   10.0, 30.0, 60.0, float("inf"))


def _bucket_quantile(buckets, counts, total: int, q: float) -> float:
    """Shared estimator under Histogram.quantile/quantile_all; see
    quantile() for semantics. `counts` are per-bucket (not cumulative)."""
    if total <= 0 or not counts:
        return float("nan")
    if not 0.0 < q <= 1.0:
        raise ValueError(f"quantile q must be in (0, 1], got {q}")
    target = q * total
    cum = 0.0
    for i, hi in enumerate(buckets):
        prev = cum
        cum += counts[i]
        if cum >= target:
            if hi == float("inf"):
                # cannot extrapolate: largest finite bound (or NaN when
                # the ladder somehow has no finite rung)
                return buckets[i - 1] if i else float("nan")
            lo = buckets[i - 1] if i else 0.0
            if counts[i] <= 0:
                return hi
            return lo + (hi - lo) * (target - prev) / counts[i]
    return float("nan")   # unreachable: last bucket is +Inf


class Histogram(_Metric):
    kind = "histogram"

    def __init__(self, name, help_, label_names=(),
                 buckets: Sequence[float] = DEFAULT_BUCKETS):
        super().__init__(name, help_, label_names)
        bl = sorted(set(buckets))
        if bl[-1] != float("inf"):
            bl.append(float("inf"))
        self.buckets = tuple(bl)
        self._counts: Dict[LabelKey, List[int]] = {}
        self._sums: Dict[LabelKey, float] = {}
        self._totals: Dict[LabelKey, int] = {}

    def observe(self, *labels: str, value: float) -> None:
        self._check(labels)
        with self._lock:
            counts = self._counts.setdefault(labels, [0] * len(self.buckets))
            for i, b in enumerate(self.buckets):
                if value <= b:
                    counts[i] += 1
                    break
            self._sums[labels] = self._sums.get(labels, 0.0) + value
            self._totals[labels] = self._totals.get(labels, 0) + 1

    def count(self, *labels: str) -> int:
        return self._totals.get(labels, 0)

    def quantile(self, q: float, *labels: str) -> float:
        """Estimate the q-quantile (0 < q <= 1) from the bucket counts —
        the promql `histogram_quantile` estimator: find the bucket the
        rank lands in, interpolate linearly inside it. Exact at bucket
        boundaries (a rank landing exactly on a bucket's cumulative
        count returns that bucket's upper bound); a rank inside the
        +Inf bucket returns the largest finite bound (the estimator
        cannot extrapolate past the ladder). NaN with no observations.
        Used by the SLO evaluator (observability/slo.py), the fleet
        rollup's serving/* series, and trace_explain --summary."""
        self._check(labels)
        with self._lock:
            counts = list(self._counts.get(labels, ()))
            total = self._totals.get(labels, 0)
        return _bucket_quantile(self.buckets, counts, total, q)

    def quantile_all(self, q: float) -> float:
        """quantile() over the SUM of every label series' buckets (the
        per-model TTFT histogram viewed fleet-wide)."""
        with self._lock:
            agg = [0] * len(self.buckets)
            for counts in self._counts.values():
                for i, c in enumerate(counts):
                    agg[i] += c
            total = sum(self._totals.values())
        return _bucket_quantile(self.buckets, agg, total, q)

    def label_values(self, label_name: str) -> List[str]:
        """Distinct observed values of one label dimension (e.g. the
        QoS classes llm_ttft_seconds has series for)."""
        i = self.label_names.index(label_name)
        with self._lock:
            return sorted({key[i] for key in self._counts})

    def quantile_label(self, q: float, label_name: str,
                       label_value: str) -> float:
        """quantile() over the sum of every series matching ONE label
        value (the per-QoS-class view of a {model, qos} histogram —
        what the fleet rollup's qos/{class}/... series record)."""
        i = self.label_names.index(label_name)
        with self._lock:
            agg = [0] * len(self.buckets)
            total = 0
            for key, counts in self._counts.items():
                if key[i] != label_value:
                    continue
                for j, c in enumerate(counts):
                    agg[j] += c
                total += self._totals.get(key, 0)
        return _bucket_quantile(self.buckets, agg, total, q)

    def render(self) -> List[str]:
        out = [f"# HELP {self.name} {self.help}",
               f"# TYPE {self.name} {self.kind}"]
        for labels in sorted(self._counts):
            cum = 0
            for i, b in enumerate(self.buckets):
                cum += self._counts[labels][i]
                lab = _fmt_labels(self.label_names, labels,
                                  {"le": _fmt_value(b)})
                out.append(f"{self.name}_bucket{lab} {cum}")
            plain = _fmt_labels(self.label_names, labels)
            out.append(f"{self.name}_sum{plain} "
                       f"{_fmt_value(self._sums[labels])}")
            out.append(f"{self.name}_count{plain} {self._totals[labels]}")
        return out


class MetricsRegistry:
    def __init__(self):
        self._metrics: Dict[str, _Metric] = {}
        self._lock = threading.Lock()

    def counter(self, name: str, help_: str = "", label_names=()) -> Counter:
        return self._get_or_make(Counter, name, help_, label_names)

    def gauge(self, name: str, help_: str = "", label_names=()) -> Gauge:
        return self._get_or_make(Gauge, name, help_, label_names)

    def histogram(self, name: str, help_: str = "", label_names=(),
                  buckets: Sequence[float] = DEFAULT_BUCKETS) -> Histogram:
        return self._get_or_make(Histogram, name, help_, label_names, buckets)

    def _get_or_make(self, cls, name, help_, label_names, *args):
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = cls(name, help_, label_names, *args)
                self._metrics[name] = m
            elif not isinstance(m, cls):
                raise TypeError(f"{name} already registered as {m.kind}")
            return m

    def render(self) -> str:
        lines: List[str] = []
        for name in sorted(self._metrics):
            lines.extend(self._metrics[name].render())
        return "\n".join(lines) + "\n"
