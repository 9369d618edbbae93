"""Serving-path latency histograms (the reference's request-duration
plane: `nv_llm_http_service_request_duration_seconds` and friends,
http/service/metrics.rs:24-130 — here as TTFT/ITL/queue/schedule/
transfer splits).

Before this module the `Histogram` class in observability/metrics.py had
zero call sites outside its module and TTFT/ITL existed solely inside
bench.py: when a chaos storm or a disagg handoff went wrong the only
evidence was fleet-wide gauges. These histograms are observed AT the
serving path (pipeline frame loop, router schedule, transfer backends,
admission gate) on one process-global registry, and every exposition
surface — the frontend's GET /metrics and the standalone
observability/exporter.py — appends `SERVING.render()` to its own
registry's output, the same render-time-fold pattern as the
fault/integrity/drain/cp gauges.

Observation cost is one bucket scan under a lock per event — no device
syncs, nothing on the engine step path (observations happen in the
asyncio layers around it). docs/OBSERVABILITY.md documents each series
and its bucket rationale.
"""
from __future__ import annotations

from typing import Optional

from dynamo_tpu.observability.metrics import MetricsRegistry

# Buckets sized to the quantity measured (the DEFAULT_BUCKETS ladder
# starts at 5ms — useless for a 100µs schedule decision):
TTFT_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
                1.0, 2.5, 5.0, 10.0, 30.0, float("inf"))
ITL_BUCKETS = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
               0.5, 1.0, float("inf"))
QUEUE_BUCKETS = (0.0001, 0.0005, 0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0,
                 5.0, 30.0, float("inf"))
SCHEDULE_BUCKETS = (0.00005, 0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005,
                    0.01, 0.05, 0.1, float("inf"))
# engine-side first-token split: a queue wait is sub-millisecond on an idle
# engine and tens of seconds on a full one; a prefill is one step to dozens
ENGINE_TTFT_BUCKETS = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                       0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, float("inf"))
TRANSFER_BUCKETS = (0.0005, 0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0,
                    float("inf"))


class ServingMetrics:
    """The serving-path histograms on one registry.

    - llm_ttft_seconds{model, qos}: request start -> first token frame
      (llm/pipeline._drive_n, per choice stream), partitioned by the
      request's QoS class (runtime/qos.py; unclassed requests label as
      the policy default) — the per-tenant-class series the fleet
      rollup's `qos/{class}/...` series and the per-class SloSpecs
      evaluate, so the watchdog pages per tenant class.
    - llm_itl_seconds{model, qos}: gap between successive token-carrying
      frames of one choice stream (commit-boundary ITL, the same
      boundary bench.py's churn phase measures).
    - llm_queue_wait_seconds{qos}: admission-gate wait at the frontend
      (AdmissionControl.acquire) — shed requests never observe.
    - llm_schedule_seconds: one KvRouter.schedule decision (or the
      reliability layer's fallback pick when no router is wired).
    - llm_kv_transfer_seconds: one disagg page transfer, send side
      (local or remote backend), staging -> last ack.
    - llm_engine_queue_wait_seconds: engine.add_request -> the first step
      whose plan holds a row of the request (waiting for a slot and for
      the scheduler's turn), observed by the engine's step thread.
    - llm_engine_prefill_seconds: that step's start -> the request's
      first sampled token (its prompt riding mixed / prefill steps).
      With the frontend's llm_ttft_seconds the two split a first-token
      time into queueing, prefill and everything above the engine.
    """

    def __init__(self, registry: Optional[MetricsRegistry] = None):
        self.registry = registry or MetricsRegistry()
        r = self.registry
        self.ttft = r.histogram(
            "llm_ttft_seconds", "time to first token frame",
            ("model", "qos"), buckets=TTFT_BUCKETS)
        self.itl = r.histogram(
            "llm_itl_seconds",
            "inter-token latency at the frame boundary",
            ("model", "qos"), buckets=ITL_BUCKETS)
        self.queue_wait = r.histogram(
            "llm_queue_wait_seconds",
            "admission-gate wait before the request runs", ("qos",),
            buckets=QUEUE_BUCKETS)
        self.schedule = r.histogram(
            "llm_schedule_seconds", "worker-selection decision time",
            buckets=SCHEDULE_BUCKETS)
        self.kv_transfer = r.histogram(
            "llm_kv_transfer_seconds",
            "disagg KV page transfer, send side (stage -> last ack)",
            buckets=TRANSFER_BUCKETS)

        self.engine_queue_wait = r.histogram(
            "llm_engine_queue_wait_seconds",
            "engine: add_request until a step first plans the request",
            buckets=ENGINE_TTFT_BUCKETS)
        self.engine_prefill = r.histogram(
            "llm_engine_prefill_seconds",
            "engine: first planned step until the first sampled token",
            buckets=ENGINE_TTFT_BUCKETS)

    def render(self) -> str:
        return self.registry.render()

    def reset(self) -> None:
        """Fresh registry + histograms (test isolation helper). Call
        sites read SERVING.<name> at observation time, so re-pointing
        the attributes is enough."""
        self.__init__()


SERVING = ServingMetrics()


def ttft_quantile(q: float, qos: str = "") -> float:
    """Live TTFT quantile with per-class refinement: the per-QoS-class
    view when that class has observations, the fleet-wide view
    otherwise; NaN only when the histogram is completely empty. This is
    the hedging trigger's adaptive delay source
    (frontend/reliability.py): a hedge fires when the primary exceeds
    the q-th percentile of what the fleet is ACTUALLY serving, not a
    hand-tuned constant that rots as traffic shifts."""
    v = float("nan")
    if qos:
        v = SERVING.ttft.quantile_label(q, "qos", qos)
    if not (v == v):
        v = SERVING.ttft.quantile_all(q)
    return v
