"""Resource-telemetry plane units (ISSUE 10 tentpole layers 1-3).

Everything here is deterministic and virtual-clocked: the TimeSeries
ring, the TransferCostModel EWMAs, the Histogram quantile estimator
(exactness at bucket boundaries and +Inf), the per-step ledger ring
discipline, and — the acceptance bar — the SLO burn-rate watchdog's
fire -> clear transition replayed from a seeded storm plan
(slo.seeded_storm_plan) with identical events on every run. The live
engine's ledger samples are covered in test_ledger_live_engine below
(one tiny engine, compile-cached); the live fleet rollup smoke is in
tests/test_fleet.py.
"""
import math

import pytest

from dynamo_tpu.observability.ledger import (
    LedgerStats, StepLedger,
)
from dynamo_tpu.observability.metrics import Histogram
from dynamo_tpu.observability.slo import (
    SloSpec, SloWatchdog, seeded_storm_plan,
)
from dynamo_tpu.observability.timeseries import Ewma, SeriesStore, TimeSeries

# -- TimeSeries ----------------------------------------------------------------


def test_timeseries_bucketing_and_window():
    s = TimeSeries(interval_s=1.0, capacity=8)
    s.record(1.0, ts=10.2)
    s.record(2.0, ts=10.9)       # same bucket, reduce=last wins
    s.record(5.0, ts=12.5)       # gap at bucket 11
    assert s.latest() == 5.0
    assert s.window(3.0, ts=12.9) == [2.0, 5.0]   # gap absent, not zero
    assert s.avg(3.0, ts=12.9) == pytest.approx(3.5)
    assert s.max(3.0, ts=12.9) == 5.0


def test_timeseries_wraparound_hides_stale_buckets():
    s = TimeSeries(interval_s=1.0, capacity=4)
    for t in range(8):
        s.record(float(t), ts=float(t))
    # capacity 4: only buckets 4..7 survive; bucket 3's ring slot was
    # overwritten by bucket 7 and must not leak into a window read
    assert s.window(10.0, ts=7.5) == [4.0, 5.0, 6.0, 7.0]


def test_timeseries_reduce_modes_and_frac():
    mx = TimeSeries(interval_s=1.0, capacity=8, reduce="max")
    sm = TimeSeries(interval_s=1.0, capacity=8, reduce="sum")
    for v in (1.0, 3.0, 2.0):
        mx.record(v, ts=0.5)
        sm.record(v, ts=0.5)
    assert mx.latest() == 3.0
    assert sm.latest() == 6.0
    s = TimeSeries(interval_s=1.0, capacity=8)
    for t, v in ((0, 1.0), (1, 9.0), (2, 9.0), (3, 1.0)):
        s.record(v, ts=float(t))
    assert s.frac_where(lambda v: v > 5.0, 4.0, ts=3.5) == 0.5
    # below min_samples: no verdict, never "all good"
    assert s.frac_where(lambda v: v > 5.0, 4.0, ts=3.5,
                        min_samples=5) is None


def test_series_store_get_or_make_and_names():
    st = SeriesStore(interval_s=1.0, capacity=16)
    st.record("worker/w0/kv", 3.0, ts=1.0)
    st.record("fleet/live", 8.0, ts=1.0)
    assert st.names("worker/") == ["worker/w0/kv"]
    assert st.get("fleet/live").latest() == 8.0
    assert st.get("absent") is None
    assert len(st) == 2


def test_ewma_none_until_first_sample():
    e = Ewma(alpha=0.5)
    assert e.value is None
    e.update(10.0)
    e.update(20.0)
    assert e.value == pytest.approx(15.0)
    assert e.samples == 2


# -- TransferCostModel ---------------------------------------------------------


def test_transfer_cost_model_ewma_and_estimate():
    from dynamo_tpu.observability.fleet import TransferCostModel
    m = TransferCostModel(alpha=0.5, default_bytes_per_s=1e9)
    # unmeasured link: the default
    assert m.bandwidth_bytes_per_s("w9") == 1e9
    assert not m.measured("w9")
    m.observe("w0", nbytes=10_000_000, seconds=0.01)   # 1 GB/s
    m.observe("w0", nbytes=5_000_000, seconds=0.01)    # 0.5 GB/s
    assert m.measured("w0")
    assert m.bandwidth_bytes_per_s("w0") == pytest.approx(7.5e8)
    assert m.estimate_s("w0", 75_000_000) == pytest.approx(0.1)
    # degenerate samples are dropped, not divided by
    m.observe("w0", nbytes=0, seconds=1.0)
    m.observe("w0", nbytes=100, seconds=0.0)
    assert m.snapshot()["w0"]["samples"] == 2
    assert m.links() == ["w0"]


def test_transfer_cost_model_cold_start_fleet_median():
    """ISSUE 11 satellite pin: a never-measured link estimates at the
    fleet-median bandwidth with cold=True — neither free (zero cost)
    nor infinitely penalized."""
    from dynamo_tpu.observability.fleet import TransferCostModel
    m = TransferCostModel(default_bytes_per_s=1e9)
    # nothing measured anywhere: the default prior, still cold
    est = m.estimate("ghost", 1_000_000)
    assert est.cold and est.seconds == pytest.approx(1e-3)
    m.observe("slow", 1_000_000, 1.0)     # 1 MB/s
    m.observe("mid", 10_000_000, 1.0)     # 10 MB/s
    m.observe("fast", 100_000_000, 1.0)   # 100 MB/s
    assert m.fleet_median_bytes_per_s() == pytest.approx(1e7)
    est = m.estimate("ghost", 10_000_000)
    assert est.cold
    assert est.seconds == pytest.approx(1.0)      # finite, median-priced
    assert est.seconds > 0.0                      # never free
    assert not m.estimate("fast", 1).cold
    # estimate_s stays the scalar view of the same cold-aware answer
    assert m.estimate_s("ghost", 10_000_000) == pytest.approx(1.0)


def test_transfer_cost_model_backlog_and_estimator_error():
    from dynamo_tpu.observability.fleet import TransferCostModel
    m = TransferCostModel(alpha=0.5)
    m.observe("w0", 10_000_000, 1.0)      # believes 10 MB/s
    # estimator error records BEFORE each subsequent sample folds in:
    # a transfer at the believed speed -> ~0 error; a 2x-slower one ->
    # under-estimate (negative signed error)
    m.observe("w0", 10_000_000, 1.0)
    assert m.est_err_frac("w0") == pytest.approx(0.0, abs=1e-6)
    m.observe("w0", 10_000_000, 2.0)
    assert m.est_err_frac("w0") < 0.0
    assert m.mean_abs_est_err() > 0.0
    assert "est_err_frac" in m.snapshot()["w0"]
    # in-flight backlog: queue_s prices the unfinished bytes at the
    # link's bandwidth and drains back to zero on completion
    m.note_inflight("w0", 5_000_000)
    assert m.backlog_bytes("w0") == 5_000_000
    assert m.queue_s("w0") > 0.0
    m.note_done("w0", 5_000_000)
    assert m.backlog_bytes("w0") == 0
    assert m.queue_s("w0") == 0.0


# -- Histogram.quantile --------------------------------------------------------


def test_quantile_boundary_exactness_and_interpolation():
    h = Histogram("q", "h", buckets=(1.0, 2.0, 4.0, float("inf")))
    for v in (0.5, 1.5, 1.5, 3.0):
        h.observe(value=v)
    # rank lands EXACTLY on bucket 1's cumulative count (1 of 4) ->
    # that bucket's upper bound, exactly
    assert h.quantile(0.25) == 1.0
    # rank 3 of 4 lands exactly on bucket 2's cumulative -> 2.0
    assert h.quantile(0.75) == 2.0
    # interpolation inside bucket (1, 2]: rank 2 of 4, one of two
    # samples into the bucket -> midpoint
    assert h.quantile(0.5) == pytest.approx(1.5)
    assert h.quantile(1.0) == 4.0


def test_quantile_inf_bucket_reports_largest_finite_bound():
    h = Histogram("q2", "h", buckets=(1.0, float("inf")))
    h.observe(value=50.0)
    assert h.quantile(0.5) == 1.0
    assert h.quantile(0.99) == 1.0


def test_quantile_empty_and_labels_and_all():
    h = Histogram("q3", "h", ("model",), buckets=(1.0, 2.0, float("inf")))
    assert math.isnan(h.quantile(0.5, "m"))
    h.observe("a", value=0.5)
    h.observe("b", value=1.5)
    assert h.quantile(0.5, "a") == pytest.approx(0.5)
    assert h.quantile(0.5, "b") == pytest.approx(1.5)
    # aggregate across label sets: 2 samples, p100 in bucket (1, 2]
    assert h.quantile_all(1.0) == 2.0
    with pytest.raises(ValueError):
        h.quantile(0.0, "a")


# -- StepLedger ----------------------------------------------------------------


def _sample(ledger, kind="decode", useful=4, padded=16, recomp=0):
    ledger.record_step(kind, rows=4, rows_live=2, useful=useful,
                       padded=padded, kv_used=3, kv_total=32,
                       host_used=0, host_total=0, disk_used=0,
                       disk_total=0, waiting=1, recompiles=recomp)


def test_ledger_ring_bounds_and_drain_order():
    st = LedgerStats()
    led = StepLedger(capacity=4, enabled=True, stats=st)
    for i in range(6):
        _sample(led, useful=i)
    assert len(led) == 4
    assert led.dropped == 2
    recs = led.drain()
    assert [r["tokens_useful"] for r in recs] == [2, 3, 4, 5]  # oldest first
    assert len(led) == 0               # drain clears
    assert st.steps_total == 6
    assert st.samples_dropped == 2


def test_ledger_disabled_is_branch_only():
    st = LedgerStats()
    led = StepLedger(capacity=8, enabled=False, stats=st)
    _sample(led)
    assert len(led) == 0
    assert led.steps == 0
    assert st.steps_total == 0


def test_ledger_per_kind_padding_attribution_and_pad_fraction():
    st = LedgerStats()
    led = StepLedger(capacity=32, enabled=True, stats=st)
    _sample(led, kind="prefill", useful=10, padded=16)
    _sample(led, kind="mixed", useful=6, padded=32)
    _sample(led, kind="decode", useful=4, padded=16, recomp=2)
    assert st.useful_tokens_prefill == 10
    assert st.padded_tokens_mixed == 32
    assert st.recompiles == 2
    assert led.pad_fraction() == pytest.approx(1.0 - 20 / 64)
    s = led.summary()
    assert s["steps_by_kind"] == {"prefill": 1, "mixed": 1, "decode": 1}
    assert s["recompiles"] == 2


def test_ledger_jsonl_write_policy(tmp_path):
    led = StepLedger(capacity=8, enabled=True, stats=LedgerStats())
    _sample(led)
    _sample(led)
    path = str(tmp_path / "LEDGER_test.jsonl")
    assert led.write_jsonl(path) == 2
    import json
    rows = [json.loads(line) for line in open(path)]
    assert rows[0]["kind"] == "decode"
    assert set(rows[0]) >= {"ts", "dt", "kind", "tokens_useful",
                            "tokens_padded", "kv_used", "recompiles",
                            "tok_s", "stall"}
    assert "mfu" not in rows[0]


# -- SLO watchdog --------------------------------------------------------------


def _run_plan(seed, spec_kw=None, degraded_fn=None):
    store = SeriesStore(interval_s=1.0, capacity=600)
    for ts, v in seeded_storm_plan(seed, n_intervals=120, storm_start=40,
                                   storm_len=40, good_value=0.05,
                                   bad_value=2.0):
        store.record("serving/ttft_p95", v, ts)
    kw = dict(name="ttft_p95", series="serving/ttft_p95", objective=0.5,
              target=0.9, short_window_s=10, long_window_s=30,
              burn_threshold=2.0)
    kw.update(spec_kw or {})
    wd = SloWatchdog(store, [SloSpec(**kw)],
                     degraded_fn=degraded_fn or (lambda: False))
    events = []
    for t in range(120):
        events.extend(wd.evaluate(float(t)))
    return wd, events


def test_slo_fire_clear_transition_is_deterministic_from_seeded_plan():
    """THE acceptance smoke: the seeded plan produces exactly one fire
    during the storm and one clear after recovery, at identical
    timestamps on every run (same seed => same events)."""
    runs = [_run_plan(7) for _ in range(2)]
    for wd, events in runs:
        kinds = [e["event"] for e in events]
        assert kinds == ["fire", "clear"]
        fire, clear = events
        assert 40 <= fire["ts"] < 80          # inside the storm window
        assert clear["ts"] > 80               # after recovery
        assert not wd.firing()
        assert wd.states["ttft_p95"].transitions == 2
    assert runs[0][1] == runs[1][1]           # bit-identical timelines


def test_slo_short_spike_alone_does_not_fire():
    """Multi-window: a burst shorter than the long window's threshold
    share never pages (the blip-protection half of the method)."""
    store = SeriesStore(interval_s=1.0, capacity=600)
    for t in range(120):
        bad = 50 <= t < 54                    # 4s spike
        store.record("s", 2.0 if bad else 0.05, float(t))
    wd = SloWatchdog(store, [SloSpec(
        name="x", series="s", objective=0.5, target=0.9,
        short_window_s=4, long_window_s=60, burn_threshold=2.0)],
        degraded_fn=lambda: False)
    events = []
    for t in range(120):
        events.extend(wd.evaluate(float(t)))
    assert events == []
    # the short window DID burn hot at the spike — the long window held
    assert wd.states["x"].transitions == 0


def test_slo_missing_data_yields_no_verdict():
    store = SeriesStore(interval_s=1.0, capacity=600)
    wd = SloWatchdog(store, [SloSpec(
        name="x", series="s", objective=0.5, target=0.9,
        short_window_s=5, long_window_s=10, min_samples=3)],
        degraded_fn=lambda: False)
    assert wd.evaluate(10.0) == []
    st = wd.states["x"]
    assert st.burn_short is None and st.burn_long is None
    assert not st.firing


def test_slo_degraded_exempt_freezes_state():
    """A degraded_exempt spec must not fire during the storm while the
    sanctioned degraded mode is up — and counts the suppressions."""
    degraded = {"on": False}
    store = SeriesStore(interval_s=1.0, capacity=600)
    for ts, v in seeded_storm_plan(3, storm_start=40, storm_len=40,
                                   good_value=0.05, bad_value=2.0):
        store.record("s", v, ts)
    wd = SloWatchdog(store, [SloSpec(
        name="lag", series="s", objective=0.5, target=0.9,
        short_window_s=10, long_window_s=30, burn_threshold=2.0,
        degraded_exempt=True)], degraded_fn=lambda: degraded["on"])
    events = []
    for t in range(120):
        degraded["on"] = 35 <= t < 95   # degraded covers the burn span
        events.extend(wd.evaluate(float(t)))
    assert events == []                 # never fired despite the burn
    assert wd.states["lag"].suppressed > 0


def test_slo_below_mode_and_gauges_render():
    store = SeriesStore(interval_s=1.0, capacity=600)
    for t in range(40):
        store.record("bw", 2e7 if t >= 20 else 1e9, float(t))
    wd = SloWatchdog(store, [SloSpec(
        name="bw_floor", series="bw", objective=1e8, mode="below",
        target=0.9, short_window_s=5, long_window_s=15,
        burn_threshold=2.0)], degraded_fn=lambda: False)
    for t in range(40):
        wd.evaluate(float(t))
    assert wd.firing() == ["bw_floor"]
    body = wd.render()
    assert 'llm_slo_firing{slo="bw_floor"} 1' in body
    assert "# HELP llm_slo_burn_rate_short" in body


def test_slo_alert_event_shape_and_on_alert():
    seen = []
    wd, events = _run_plan(11)
    wd2, _ = _run_plan(11)
    ev = events[0]
    assert set(ev) >= {"event", "slo", "ts", "series", "objective",
                       "burn_short", "burn_long", "threshold"}
    # on_alert callback receives each event as it happens
    store = SeriesStore(interval_s=1.0, capacity=600)
    for ts, v in seeded_storm_plan(11):
        store.record("serving/ttft_p95", v, ts)
    wd3 = SloWatchdog(store, [SloSpec(
        name="ttft_p95", series="serving/ttft_p95", objective=0.5,
        target=0.9, short_window_s=10, long_window_s=30)],
        on_alert=seen.append, degraded_fn=lambda: False)
    for t in range(120):
        wd3.evaluate(float(t))
    assert [e["event"] for e in seen] == ["fire", "clear"]


def test_slo_duplicate_names_rejected():
    store = SeriesStore()
    spec = SloSpec(name="a", series="s", objective=1.0)
    with pytest.raises(ValueError):
        SloWatchdog(store, [spec, SloSpec(name="a", series="t",
                                          objective=2.0)])


# -- prometheus text parsing + fleet_top rendering ----------------------------


def test_parse_prometheus_text_families_and_histograms():
    from dynamo_tpu.observability.fleet import parse_prometheus_text
    text = "\n".join([
        "# HELP llm_workers Live worker instances",
        "# TYPE llm_workers gauge",
        "llm_workers 3",
        "# HELP llm_ttft_seconds ttft",
        "# TYPE llm_ttft_seconds histogram",
        'llm_ttft_seconds_bucket{model="m",le="+Inf"} 2',
        'llm_ttft_seconds_sum{model="m"} 0.5',
        'llm_ttft_seconds_count{model="m"} 2',
        "# HELP llm_empty_family no series yet",
        "# TYPE llm_empty_family gauge",
    ])
    fams = parse_prometheus_text(text)
    assert fams["llm_workers"][""] == 3.0
    assert "llm_empty_family" in fams          # presence without series
    assert 'llm_ttft_seconds' in fams          # suffixes rolled up
    assert all(not k.endswith(("_bucket", "_sum", "_count"))
               for k in fams)


def test_fleet_top_renders_committed_artifact():
    """The committed FLEET_r10.json renders offline: the storm phase
    shows the burn, the timeline shows fire then clear, and every
    contract reads PASS (golden over the committed evidence)."""
    import json
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(root, "FLEET_r10.json")
    import sys
    sys.path.insert(0, os.path.join(root, "tools"))
    from fleet_top import render_artifact, render_summary
    report = json.load(open(path))
    out = render_artifact(report)
    assert "fleet_availability" in out
    assert " fire " in out and " clear " in out
    assert "FAIL" not in out and "PASS" in out
    # the storm-phase rollup alone renders through render_summary
    storm = render_summary(report["rollup"]["storm"],
                           slo=report["slo_states"]["storm"])
    assert "FIRING" in storm
    assert "kv-transfer links" in storm


def test_trace_explain_summary_uses_bucket_quantiles():
    """tools/trace_explain.py --summary over the committed disagg trace:
    per-span-name p50/p95/p99 through Histogram.quantile (the estimator
    satellite's second consumer)."""
    import os
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(root, "tools"))
    from trace_explain import load_spans, summarize
    spans = load_spans(os.path.join(root, "TRACE_DISAGG_r08.jsonl"))
    out = summarize(spans)
    assert "p95 ms" in out and "http.request" in out
    assert "kv.transfer" in out
    assert "decode.emit" in out and "instant" in out
    # ordered by total time: the root request dominates
    lines = [ln for ln in out.splitlines() if "http.request" in ln
             or "kv.transfer " in ln]
    assert lines[0].strip().startswith("http.request")
    # the pre-ISSUE-11 artifact carries no est_s attrs: the estimator
    # table must NOT appear (old goldens render unchanged)
    assert "estimator" not in out


def test_trace_explain_link_estimator_table():
    """ISSUE 11 satellite: kv.transfer spans carrying the sender's
    pre-send est_s attr render a per-link estimated-vs-actual column —
    a stale-fast EWMA (under-estimate) shows as negative err%."""
    import os
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(root, "tools"))
    from trace_explain import link_estimator_table, summarize

    def span(link, est, dur, cold=False):
        return {"trace_id": "t", "span_id": link + str(est), "ts": 0.0,
                "dur": dur, "name": "kv.transfer",
                "attrs": {"engine_id": link, "est_s": est,
                          "bytes": 1000, "est_cold": cold}}

    spans = [span("fast", 0.010, 0.010),
             span("stale", 0.010, 0.100),     # 10x under-estimated
             span("coldlink", 0.020, 0.030, cold=True)]
    table = "\n".join(link_estimator_table(spans))
    assert "stale" in table and "fast" in table
    stale_row = next(ln for ln in table.splitlines() if "stale" in ln)
    assert "-90.0" in stale_row          # (est - act)/act = -90%
    cold_row = next(ln for ln in table.splitlines() if "coldlink" in ln)
    assert cold_row.rstrip().endswith("1")   # cold estimate counted
    # the table folds into --summary output
    assert "estimator" in summarize(spans)


def test_fleet_r10_artifact_contracts():
    """The committed evidence itself: fire -> clear present, per-link
    EWMAs measured, ledger samples from a live engine attached."""
    import json
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    report = json.load(open(os.path.join(root, "FLEET_r10.json")))
    assert report["ok"] is True
    assert all(report["contracts"].values())
    kinds = [(e["event"], e["slo"]) for e in report["alerts"]]
    assert ("fire", "fleet_availability") in kinds
    assert ("clear", "fleet_availability") in kinds
    assert len(report["rollup"]["storm"]["links"]) >= 8
    led = report["ledger"]
    assert led["samples"] > 0 and led["written"] == led["samples"]
    ledger_path = os.path.join(root, "LEDGER_r10.jsonl")
    rows = [json.loads(line) for line in open(ledger_path)]
    assert len(rows) == led["written"]
    assert {r["kind"] for r in rows} >= {"prefill", "decode"}
