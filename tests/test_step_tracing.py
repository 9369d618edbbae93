"""The engine's step loop on the profiler's clock (ISSUE 24).

- every engine program lowers to a stable XLA module name, the decode
  window by ladder rung (a device trace no longer says `jit__unknown`);
- every step kind passes through one phase vocabulary
  (plan / upload / dispatch / wait / commit), each phase at most once a
  step, and phases + `between` account for the loop's wall time;
- a profiler capture holds the phases as flat `engine.<phase>` host
  events, and the benchmark's reducer labels idle gaps by them;
- `jax_compiles` counts what XLA really compiled;
- `engine.queue` / `engine.prefill` split a request's first-token time
  under its `worker.generate` span, one histogram observation each;
- every series, field and module a listed layer metric reads exists;
- the worker's bounded `capture_profile`, the table its child process
  leaves, and its `/debug/profile` route;
- one record a `step()` call (ISSUE 35): periods by kind tile the busy
  wall time, the worker's marks partition `between`, every gap between
  a stream's commits goes whole to one class;
- a dispatch says what it launched as the annotation's stats, beside its
  name (ISSUE 52), the two exposures partition `host_exposed_seconds`
  with the steady loop's rest, and a stalled period is counted.
"""
import asyncio
import contextlib
import json
import os
import re
import sys
import time

import jax
import jax.numpy as jnp
import pytest

from dynamo_tpu.engine.config import EngineConfig, ModelConfig
from dynamo_tpu.engine.engine import NativeEngine
from dynamo_tpu.engine.scheduler import EngineRequest, SamplingParams
from dynamo_tpu.observability.ledger import LEDGER_STATS, LedgerStats
from dynamo_tpu.observability.metrics import PhaseTimer
from dynamo_tpu.observability.serving import SERVING
from dynamo_tpu.runtime.engine import Context
from dynamo_tpu.runtime.tracing import TRACER, TraceContext

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark"))
from harness import readers, trace_reduce  # noqa: E402

CFG = ModelConfig(dtype="float32", max_model_len=512)
PHASES = PhaseTimer.PHASES
WINDOWS = ("engine_decode_window_full", "engine_decode_window_w2",
           "engine_decode_window_w1")


def make_engine(**kw):
    defaults = dict(page_size=8, num_pages=64, max_slots=4,
                    max_prefill_chunk=32, prefill_buckets=(8, 16, 32),
                    max_model_len=512, decode_steps=8)
    defaults.update(kw)
    return NativeEngine(CFG, EngineConfig(**defaults), seed=0)


def sampled(max_tokens, seed=3):
    return SamplingParams(max_tokens=max_tokens, temperature=0.7,
                          top_p=0.95, seed=seed)


PHRASE = [11, 12, 13, 14, 15, 16]


@contextlib.contextmanager
def spec_engine():
    """A speculating engine whose proposer always has a draft: a
    random-weight model never repeats itself, so the real n-gram proposer
    goes silent after the first token (tests/test_spec_decode.py). At a
    1-step window any draft passes the cost gate."""
    import dynamo_tpu.engine.spec as spec_mod
    real = spec_mod.ngram_propose
    spec_mod.ngram_propose = lambda tokens, k, *a, **kw: [7] * min(k, 2)
    try:
        yield make_engine(spec_decode="ngram", spec_k=4, pipeline_depth=1,
                          decode_steps=1)
    finally:
        spec_mod.ngram_propose = real


# -- (a) module names ----------------------------------------------------------

def _record_lowered(fns: dict, names: set):
    """Wrap each jitted program so that a dispatch also records the name
    of the XLA module the same arguments lower to."""
    for key, fn in list(fns.items()):
        def spy(*a, _fn=fn, **k):
            text = _fn.lower(*a, **k).as_text()
            names.add(re.search(r"module @(\w+)", text).group(1))
            return _fn(*a, **k)
        fns[key] = spy


@pytest.fixture(scope="module")
def lowered_names():
    names: set = set()
    eng = make_engine(pipeline_depth=1)
    _record_lowered(eng._step_fns, names)
    _record_lowered(eng._decode_fns, names)
    # prefill 1 + full rung 8 + rung 2, then prefill 1 + 8 + rung 1
    eng.generate(list(range(10, 30)), sampled(11), "r2")
    eng.generate(list(range(40, 60)), sampled(10), "r1")
    with spec_engine() as spec:
        verify = {"verify": spec._verify_fn}
        _record_lowered(verify, names)
        spec._verify_fn = verify["verify"]
        spec.generate(PHRASE * 4, SamplingParams(max_tokens=6,
                                                 temperature=0.0), "s")
        assert spec.spec_steps > 0
    return names


@pytest.mark.parametrize("program", ("engine_step", "engine_verify_step")
                         + WINDOWS)
def test_program_lowers_to_its_module_name(lowered_names, program):
    assert f"jit_{program}" in lowered_names
    assert not any("unknown" in n for n in lowered_names)


def test_window_names_follow_the_ladder():
    eng = make_engine(decode_steps=16)
    assert [eng._window_name(w) for w in eng._window_sizes] == [
        "engine_decode_window_full", "engine_decode_window_w4",
        "engine_decode_window_w1"]


# -- (b) one phase vocabulary, counted once a step -----------------------------

def _drive(eng, arrivals):
    """Step `eng` to completion; `arrivals` maps a step index to the
    request added before it. Returns per step (kinds committed, phase
    count deltas) and the loop's wall time against the timer's sums."""
    kinds = ("prefill", "mixed", "decode", "spec")
    steps = []
    eng.phases.reset()
    t0 = time.perf_counter()
    i = 0
    while eng.has_work() or i in arrivals:
        if i in arrivals:
            eng.add_request(arrivals[i])
        before = dict(eng.phases.counts)
        k0 = {k: getattr(LEDGER_STATS, "steps_" + k) for k in kinds}
        eng.step()
        delta = {p: eng.phases.counts.get(p, 0) - before.get(p, 0)
                 for p in PHASES + ("between",)}
        kind = [k for k in kinds
                if getattr(LEDGER_STATS, "steps_" + k) > k0[k]]
        steps.append((kind, delta))
        i += 1
    wall = time.perf_counter() - t0
    return steps, wall, sum(eng.phases.seconds.values())


@pytest.fixture(scope="module")
def driven():
    arrivals = {0: EngineRequest("a", list(range(10, 40)), sampled(30)),
                3: EngineRequest("b", list(range(50, 90)), sampled(20, 5))}
    out = {}
    out["sync"] = _drive(make_engine(pipeline_depth=1), arrivals)
    out["pipelined"] = _drive(make_engine(pipeline_depth=2), arrivals)
    with spec_engine() as spec:
        out["spec"] = _drive(spec, {0: EngineRequest(
            "s", PHRASE * 4, SamplingParams(max_tokens=12,
                                            temperature=0.0))})
    return out


@pytest.mark.parametrize("run,kind", [
    ("sync", "prefill"), ("sync", "mixed"), ("sync", "decode"),
    ("spec", "spec")])
def test_every_phase_once_per_synchronous_step(driven, run, kind):
    steps = [d for k, d in driven[run][0] if k == [kind]]
    assert steps, f"the drive made no {kind} step"
    for i, d in enumerate(steps):
        assert {p: d[p] for p in PHASES} == dict.fromkeys(PHASES, 1), (i, d)
        assert d["between"] <= 1


def test_pipelined_steps_keep_the_phases_flat(driven):
    """A primed window has no wait or commit of its own, a chained one
    no upload; no phase is ever entered twice in one step, and over the
    run every dispatched window is waited for and committed once."""
    steps, _, _ = driven["pipelined"]
    primed = [d for k, d in steps if not k and d["dispatch"] == 1]
    chained = [d for k, d in steps if k == ["decode"] and d["upload"] == 0]
    assert primed and chained
    for d in primed:
        assert (d["plan"], d["upload"], d["wait"], d["commit"]) == (1, 1, 0, 0)
    for d in chained:
        assert (d["plan"], d["wait"], d["commit"]) == (1, 1, 1)
        assert d["dispatch"] in (0, 1)
    assert all(v <= 1 for _, d in steps for v in d.values())
    total = {p: sum(d[p] for _, d in steps) for p in PHASES}
    assert total["wait"] == total["commit"]
    assert total["plan"] == len(steps)


def test_upload_opens_once_a_step_and_encloses_the_staging():
    """Every trip of host operands to the device (`_stage_operands`, the
    one place that puts them: PR 30) happens while `upload`, and nothing
    else, is open, and no step opens `upload` twice."""
    eng = make_engine(pipeline_depth=2)
    open_now, staged_under, uploads_a_step = [], [], []
    real_phase, real_stage = eng.phases.phase, eng._stage_operands

    @contextlib.contextmanager
    def phase(name, annotation=None, stats=None):
        open_now.append(name)
        try:
            with real_phase(name, annotation, stats):
                yield
        finally:
            open_now.pop()

    def stage(small, own=(), **kw):
        staged_under.append(tuple(open_now))
        return real_stage(small, own, **kw)

    eng.phases.phase, eng._stage_operands = phase, stage
    arrivals = {0: EngineRequest("a", list(range(10, 40)), sampled(30)),
                3: EngineRequest("b", list(range(50, 90)), sampled(20, 5))}
    i = 0
    while eng.has_work() or i in arrivals:
        if i in arrivals:
            eng.add_request(arrivals[i])
        before = eng.phases.counts.get("upload", 0)
        eng.step()
        uploads_a_step.append(eng.phases.counts.get("upload", 0) - before)
        i += 1
    assert eng.mixed_steps and eng.decode_windows and eng.pipeline_windows
    assert len(staged_under) >= 4
    assert set(staged_under) == {("upload",)}
    assert set(uploads_a_step) == {0, 1}
    assert sum(uploads_a_step) >= len(staged_under)


@pytest.mark.parametrize("run", ["sync", "pipelined", "spec"])
def test_phases_and_between_sum_to_the_wall_time(driven, run):
    """Tolerance: 3 % of the loop's wall time plus 0.2 ms a step (the
    unphased glue between two `with` blocks)."""
    steps, wall, accounted = driven[run]
    assert accounted <= wall
    assert wall - accounted <= 0.03 * wall + 2e-4 * len(steps)


def test_exposed_excludes_wait_and_overlapped_commits():
    """Synchronous steps expose everything but `wait`; a commit that runs
    while a follow-up window is in flight is not exposed."""
    sync = make_engine(pipeline_depth=1)
    sync.generate(list(range(10, 40)), sampled(30), "a")
    t = sync.phases
    assert t.exposed == pytest.approx(
        sum(s for n, s in t.seconds.items() if n != "wait"), rel=1e-9)
    piped = make_engine(pipeline_depth=2)
    piped.generate(list(range(10, 40)), sampled(30), "a")
    t = piped.phases
    assert piped.pipeline_overlapped > 0
    assert t.exposed < sum(s for n, s in t.seconds.items() if n != "wait")
    assert LEDGER_STATS.host_exposed_seconds >= t.exposed


# -- (g) one record a step() call (ISSUE 35) -----------------------------------

def _private_stats(eng):
    """Give `eng` a LedgerStats of its own, so a test reads its sums and
    not the process's."""
    stats = LedgerStats()
    eng.ledger.stats = eng.phases.stats = stats
    return stats


def _drive_records(eng, arrivals):
    """Step `eng` to completion on a LedgerStats of its own. Returns the
    stats, the loop's wall time, the ledger's call records, and per
    stream the perf_counter of each of its commits with the tokens it
    got (the clock read is the ledger's own, seen through a shim)."""
    import dynamo_tpu.observability.ledger as ledger_mod
    stats = _private_stats(eng)
    seen = []

    class Clock:
        monotonic = staticmethod(time.monotonic)

        @staticmethod
        def perf_counter():
            seen.append(time.perf_counter())
            return seen[-1]

    real, ledger_mod.time = ledger_mod.time, Clock
    commits = {}
    try:
        t0 = time.perf_counter()
        i = 0
        while eng.has_work() or i in arrivals:
            if i in arrivals:
                eng.add_request(arrivals[i])
            del seen[:]
            got = {}
            for ev in eng.step():
                if ev.token is not None:
                    got[ev.request_id] = got.get(ev.request_id, 0) + 1
            assert len(seen) <= 1, "more than one commit in a step() call"
            for rid, n in got.items():
                commits.setdefault(rid, []).append((seen[0], n))
            i += 1
        wall = time.perf_counter() - t0
    finally:
        ledger_mod.time = real
    return stats, wall, eng.ledger.calls(), commits


ARRIVALS = {0: EngineRequest("a", list(range(10, 40)), sampled(30)),
            3: EngineRequest("b", list(range(50, 90)), sampled(20, 5))}


@pytest.fixture(scope="module")
def recorded():
    out = {}
    out["sync"] = _drive_records(make_engine(pipeline_depth=1), ARRIVALS)
    out["pipelined"] = _drive_records(make_engine(pipeline_depth=2),
                                      ARRIVALS)
    with spec_engine() as spec:
        out["spec"] = _drive_records(spec, {0: EngineRequest(
            "s", PHRASE * 4, SamplingParams(max_tokens=12,
                                            temperature=0.0))})
    # a long prompt's chunks beside one short stream: every gap is a
    # mixed step's
    out["mixed-only"] = _drive_records(make_engine(pipeline_depth=1), {
        0: EngineRequest("a", list(range(10, 30)), sampled(6)),
        1: EngineRequest("b", [3 + i % 200 for i in range(250)],
                         sampled(1, 5))})
    out["window-only"] = _drive_records(make_engine(pipeline_depth=1), {
        0: EngineRequest("a", list(range(10, 30)), sampled(21))})
    # no mixed steps: a prefill step between two windows is a program a
    # running stream sits out
    out["alternating"] = _drive_records(
        make_engine(pipeline_depth=1, mixed_token_budget=0), ARRIVALS)
    return out


@pytest.mark.parametrize("run", ["sync", "pipelined", "spec"])
def test_periods_by_kind_tile_the_busy_wall_time(recorded, run):
    """Every call's period (the `between` before it + its time inside
    step()) goes to one kind, and together they are the loop's wall time
    but for what precedes the first call (tolerance as above)."""
    stats, wall, calls, _ = recorded[run]
    by_kind = (stats.period_mixed_seconds + stats.period_decode_seconds
               + stats.period_other_seconds)
    assert by_kind == pytest.approx(stats.period_seconds, rel=1e-9)
    assert stats.period_seconds <= wall
    assert wall - stats.period_seconds <= 0.03 * wall + 2e-4 * len(calls)
    # the records tile it too: a call starts where the last one's ended
    for prev, rec in zip(calls, calls[1:]):
        assert rec["t_entry"] - rec["between"] == pytest.approx(
            prev["t_exit"], abs=1e-9)
    assert sum(r["between"] + r["t_exit"] - r["t_entry"] for r in calls) \
        == pytest.approx(stats.period_seconds, rel=1e-9)
    assert stats.period_seconds == pytest.approx(
        calls[-1]["t_exit"] - calls[0]["t_entry"], rel=1e-9)
    for kind in ("mixed", "decode"):
        mine = sum(r["between"] + r["t_exit"] - r["t_entry"]
                   for r in calls if r["kind"] == kind)
        assert mine == pytest.approx(
            getattr(stats, f"period_{kind}_seconds"), rel=1e-9)
    # each record holds this call's own phases, inside its interval
    for rec in calls:
        for name, (start, dt) in rec["phases"].items():
            if name != "between":
                assert rec["t_entry"] <= start
                assert start + dt <= rec["t_exit"] + 1e-9
        assert rec["between"] == pytest.approx(
            rec["phases"].get("between", (0, 0.0))[1])


def test_a_pipelined_window_is_counted_once(recorded):
    """A call that only primes or chains the pipeline has a record of
    the kind it dispatched and commits nothing; the window counts where
    it commits: windows, device steps and tokens agree with the calls
    that committed."""
    stats, _, calls, commits = recorded["pipelined"]
    primed = [r for r in calls if not r["dev_steps"]]
    assert primed and all(
        r["tokens"] == 0 and "commit" not in r["phases"]
        and "dispatch" in r["phases"] for r in primed)
    # a window that was only primed or chained. The mixed step that
    # takes `b` in has no call of its own: the call that commits the
    # window in front of it plans, uploads and dispatches it first
    # (NativeEngine._launch_ahead), and has the kind of what it committed
    assert {r["kind"] for r in primed} == {"decode"}
    handed = [r for r, nxt in zip(calls, calls[1:])
              if r["kind"] == "decode" and r["dev_steps"]
              and nxt["kind"] == "mixed"]
    assert handed and all(
        {"plan", "upload", "dispatch", "wait", "commit"} <= set(r["phases"])
        and r["bucket"] == 8 for r in handed)
    assert all(r["bucket"] == 8 for r in primed if r["kind"] == "decode")
    windows = [r for r in calls if r["kind"] == "decode" and r["dev_steps"]]
    assert stats.steps_decode == len(windows)
    assert stats.window_steps_total == sum(r["dev_steps"] for r in windows)
    assert stats.steps_total == len(calls) - len(primed)
    assert sum(r["tokens"] for r in calls) == sum(
        n for times in commits.values() for _, n in times) == 50
    mixed = [r for r in calls if r["kind"] == "mixed"]
    assert mixed and all(r["dev_steps"] <= 1 and len(r["bucket"]) == 2
                         for r in mixed)
    assert stats.steps_mixed == sum(r["dev_steps"] for r in mixed)
    # the synchronous loop ran the same programs, with no call between
    sync_stats, _, sync_calls, _ = recorded["sync"]
    assert all(r["dev_steps"] for r in sync_calls)
    assert sync_stats.window_steps_total == stats.window_steps_total


@pytest.mark.parametrize("run", ["mixed-only", "window-only", "pipelined",
                                 "sync", "alternating"])
def test_gap_classes_partition_every_gap(recorded, run):
    """For every stream the classes' seconds sum to its last commit less
    its first, each commit after the first is one timed gap, and the
    other tokens of a commit are `burst`."""
    stats, _, calls, commits = recorded[run]
    timed = sum(len(times) - 1 for times in commits.values())
    spans = sum(times[-1][0] - times[0][0] for times in commits.values())
    tokens = sum(n for times in commits.values() for _, n in times)
    classes = ("mixed", "window", "multi")
    assert stats.gap_total == timed == sum(
        getattr(stats, f"gap_{c}_total") for c in classes)
    assert sum(getattr(stats, f"gap_{c}_seconds") for c in classes) \
        == pytest.approx(spans, rel=1e-9)
    assert stats.gap_burst_total == tokens - timed - len(commits)
    assert tokens == sum(r["tokens"] for r in calls)
    for c in classes:
        assert (getattr(stats, f"gap_{c}_seconds") > 0) \
            == (getattr(stats, f"gap_{c}_total") > 0)
    if run == "mixed-only":
        assert stats.gap_mixed_total == timed > 0
    elif run == "window-only":
        assert stats.gap_window_total == timed > 0
        assert stats.gap_burst_total > 0
    elif run == "pipelined":
        # a priming call is no program of its own: with one a window the
        # gaps between two windows' commits would all read `multi`
        assert any(not r["dev_steps"] for r in calls)
        assert stats.gap_multi_total == 0
        assert stats.gap_window_total > 0 and stats.gap_mixed_total > 0
    elif run == "alternating":
        assert stats.steps_mixed == 0 and stats.gap_multi_total > 0


def test_an_aborted_stream_leaves_no_last_commit():
    eng = make_engine(pipeline_depth=1)
    _private_stats(eng)
    eng.add_request(EngineRequest("gone", list(range(10, 30)), sampled(40)))
    eng.step()
    assert "gone" in eng.ledger._last_commit
    eng.abort("gone")
    assert not eng.ledger._last_commit
    eng.generate(list(range(40, 60)), sampled(4), "kept")
    assert not eng.ledger._last_commit


def test_a_sample_no_call_has_closed_has_no_clock_yet():
    """The sample of the call in progress has no clock until the call is
    closed: the ring and `drain()` list it with `phases` None. (The
    stretch form of `calls()`, which chose a capture's records for
    `steps.jsonl`, went with that file in PR 52.)"""
    from dynamo_tpu.observability.ledger import StepLedger
    led = StepLedger(capacity=8, enabled=True, stats=LedgerStats())
    sample = ("mixed", 4, 3, 5, 64) + (0,) * 8
    led.record_step(*sample)
    led.close_call("", [4, 16], 10.0, 10.5, 0.25, (0.1, 0.1, 0.03, 0.02),
                   {"commit": [10.4, 0.05]})
    led.record_step(*sample)
    assert [r["phases"] is None for r in led.calls()] == [False, True]
    assert len(led.drain(clear=False)) == len(led) == 2
    assert [r["t_exit"] for r in led.calls()] == [10.5, 0.0]


# -- the two exposures and the stalls (ISSUE 52) --------------------------------

@pytest.mark.parametrize("run", ["sync", "pipelined", "alternating",
                                 "mixed-only", "window-only"])
def test_the_two_exposures_never_exceed_the_exposed_sum(recorded, run):
    """What accrued in a drain's period and in a hand-over's are parts of
    `host_exposed_seconds`: neither is negative, together they do not
    pass it, and with the steady loop's rest (what is left, no third
    counter) they sum to it."""
    stats = recorded[run][0]
    drain, handover = (stats.host_exposed_drain_seconds,
                       stats.host_exposed_handover_seconds)
    assert drain >= 0.0 and handover >= 0.0
    rest = stats.host_exposed_seconds - drain - handover
    assert rest >= -1e-9
    assert drain + handover + rest == pytest.approx(
        stats.host_exposed_seconds)


def test_a_handover_charges_its_own_period_alone(recorded):
    """A synchronous loop that alternates prefill steps and windows hands
    over at every change of kind mixed <-> window; one that only runs
    windows never does, and charges nothing."""
    assert recorded["window-only"][0].host_exposed_handover_seconds == 0.0
    assert recorded["sync"][0].host_exposed_handover_seconds > 0.0


def _closed(ledger, phases, period, **kw):
    """Close one made-up call of `period` seconds that committed a step."""
    ledger.record_step("decode", 4, 4, 4, 4, 0, 1, 0, 0, 0, 0, 0, 0)
    ledger.close_call("decode", 8, 10.0, 10.0 + period, 0.0,
                      (0.0,) * 4, phases, **kw)


def test_a_made_up_long_wait_counts_one_stall():
    from dynamo_tpu.observability.ledger import STALL_PERIOD_S, StepLedger
    assert STALL_PERIOD_S == 0.5
    stats = LedgerStats()
    ledger = StepLedger(stats=stats, enabled=True)
    _closed(ledger, {"wait": [10.0, 0.8], "commit": [10.8, 0.01]}, 0.81)
    assert stats.period_stalls_total == 1
    assert stats.period_stall_seconds == pytest.approx(0.81)
    assert stats.period_stall_wait_seconds == pytest.approx(0.8)
    assert ledger.calls()[-1]["stall"] is True
    # a sound period is none
    _closed(ledger, {"wait": [11.0, 0.2]}, 0.21)
    assert stats.period_stalls_total == 1
    assert ledger.calls()[-1]["stall"] is False


def test_a_first_dispatch_is_no_stall():
    from dynamo_tpu.observability.ledger import StepLedger
    stats = LedgerStats()
    ledger = StepLedger(stats=stats, enabled=True)
    _closed(ledger, {"dispatch": [10.0, 2.5], "wait": [12.5, 0.1]}, 2.6,
            first_dispatch=True)
    assert stats.period_stalls_total == 0
    assert stats.period_stall_seconds == 0
    # and a call that committed nothing has no period to stall
    ledger.close_call("decode", 8, 20.0, 21.0, 0.0, (0.0,) * 4,
                      {"dispatch": [20.0, 1.0]})
    assert stats.period_stalls_total == 0


def test_the_exposures_go_to_the_call_they_accrued_in():
    """`exposed` is the engine's cumulative sum: what it grew by since the
    last close goes to the drain from the call AFTER one that ended a row
    up to and with the first that launches a program again, else to the
    hand-over where the call launches a step of another kind than the
    launch before it (mixed against window), else nowhere."""
    from dynamo_tpu.observability.ledger import StepLedger
    stats = LedgerStats()
    ledger = StepLedger(stats=stats, enabled=True)
    _closed(ledger, {}, 0.1, exposed=1.0, launched="mixed")      # steady
    _closed(ledger, {}, 0.1, exposed=1.5, launched="window")     # handed over
    _closed(ledger, {}, 0.1, exposed=1.75, launched="window",
            ended_row=True)                                      # steady
    # the drained window's commit, nothing launched behind it ...
    _closed(ledger, {}, 0.1, exposed=2.75)
    # ... and the plan, upload and dispatch of what comes next: a mixed
    # step, but the period is the drain's
    _closed(ledger, {}, 0.1, exposed=2.875, launched="mixed")
    _closed(ledger, {}, 0.1, exposed=3.0, launched="mixed")      # steady
    # a prefill between a window and a mixed step is no hand-over
    _closed(ledger, {}, 0.1, exposed=3.25, launched="prefill")
    _closed(ledger, {}, 0.1, exposed=3.5, launched="window")
    assert stats.host_exposed_handover_seconds == pytest.approx(0.5)
    assert stats.host_exposed_drain_seconds == pytest.approx(1.125)


def test_a_served_engine_marks_its_calls(recorded):
    """The engine hands close_call its own marks: a compile (every
    program of these short runs is first dispatched inside them) is no
    stall, however long, and every record says so."""
    for run in ("sync", "pipelined"):
        stats, _, calls, _ = recorded[run]
        assert stats.period_stalls_total == 0
        assert all(c["stall"] is False for c in calls)


def _serve_two(worker):
    async def main():
        await worker.start()
        try:
            await asyncio.gather(
                _generate(worker, "p1", list(range(10, 30)), Context("p1"),
                          max_tokens=12),
                _generate(worker, "p2", list(range(40, 100)), Context("p2"),
                          max_tokens=9))
        finally:
            await worker.stop()
    asyncio.run(main())


@pytest.mark.parametrize("clock", ["real", "stubbed"])
def test_the_workers_marks_partition_between(monkeypatch, clock):
    """Four marks in `_step_loop` split the time between two step()
    calls: within float rounding on the real clock, and to the digit on
    a clock that ticks whole seconds."""
    from dynamo_tpu.llm.worker import NativeEngineWorker
    eng = make_engine(pipeline_depth=2)
    eng.generate(list(range(10, 40)), sampled(12), "warm")
    stats = _private_stats(eng)
    eng.ledger.calls(clear=True)
    if clock == "stubbed":
        import itertools
        ticks = itertools.count(1000)
        monkeypatch.setattr(time, "perf_counter",
                            lambda: float(next(ticks)))
    _serve_two(NativeEngineWorker(eng))
    monkeypatch.undo()
    parts = [stats.host_resume_seconds, stats.host_emit_seconds,
             stats.host_apply_pending_seconds, stats.host_submit_seconds]
    assert all(p > 0 for p in parts)
    assert stats.host_between_seconds > 0
    if clock == "stubbed":
        assert sum(parts) == stats.host_between_seconds
    else:
        assert sum(parts) == pytest.approx(stats.host_between_seconds,
                                           rel=1e-9)
    assert 0 < stats.host_exposed_between_seconds \
        <= stats.host_between_seconds
    for rec in eng.ledger.calls():
        mine = [rec[k] for k in ("resume", "emit", "apply_pending",
                                 "submit")]
        assert all(p >= 0 for p in mine)
        if clock == "stubbed":
            assert sum(mine) == rec["between"]
        else:
            assert sum(mine) == pytest.approx(rec["between"], abs=1e-9)


def test_a_bare_step_loop_charges_no_parts():
    """Without the worker's marks `between` is still counted whole, and
    after `note_idle` none of it is."""
    eng = make_engine(pipeline_depth=1)
    stats = _private_stats(eng)
    eng.generate(list(range(10, 40)), sampled(12), "a")
    assert stats.host_between_seconds > 0
    assert stats.host_resume_seconds == stats.host_submit_seconds == 0
    eng.note_idle()
    eng.note_between(1.0, 2.0, 3.0)
    before = stats.host_between_seconds
    eng.add_request(EngineRequest("b", list(range(50, 70)), sampled(2)))
    eng.step()
    assert stats.host_between_seconds == before
    assert stats.host_resume_seconds == 0


# -- (c) the phases in a profiler capture --------------------------------------

@pytest.fixture(scope="module")
def captured_planes(tmp_path_factory):
    eng = make_engine(pipeline_depth=1)
    eng.generate(list(range(10, 40)), sampled(12), "warm")
    out = str(tmp_path_factory.mktemp("capture"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=opts)
    try:
        eng.generate(list(range(10, 40)), sampled(12, 9), "traced")
    finally:
        jax.profiler.stop_trace()
    return trace_reduce.load_planes(trace_reduce.find_xplane(out))


def _engine_events(planes):
    """[(line, [(start, end, name)])] of `engine.*` host events."""
    out = []
    for pname, lines in planes:
        if not trace_reduce.HOST_PLANE.match(pname):
            continue
        for lname, evs in lines:
            mine = sorted(e for e in evs if e[2].startswith("engine."))
            if mine:
                out.append((lname, mine))
    return out


@pytest.mark.parametrize("phase", PHASES)
def test_capture_holds_the_phase_as_a_host_event(captured_planes, phase):
    names = {n for _, evs in _engine_events(captured_planes)
             for _, _, n in evs}
    assert f"engine.{phase}" in names


def test_captured_phases_do_not_enclose_each_other(captured_planes):
    lines = _engine_events(captured_planes)
    assert lines
    for lname, evs in lines:
        for (s0, e0, n0), (s1, e1, n1) in zip(evs, evs[1:]):
            assert e0 <= s1, (lname, n0, n1)


def _ms(x):
    return int(x * 1e6)


def test_reducer_labels_idle_gaps_by_phase():
    """A synthetic step loop: the device runs 100 ms programs with 8 ms
    between them; on the host the gap holds wait's tail, commit,
    worker.emit, plan, upload (with `shard_args` inside it) and dispatch
    (with `PjitFunction` inside it). The gap goes to the phase that
    overlaps it most, never to the C++ TraceMe nested in a phase."""
    ops, host = [], []
    for i in range(5):
        t = i * 108.0
        ops.append((_ms(t), _ms(t + 100), "%fusion.1"))
        g = t + 100
        host += [(_ms(t + 3), _ms(g + 0.5), "engine.wait"),
                 (_ms(g + 0.5), _ms(g + 1.5), "engine.commit"),
                 (_ms(g + 1.6), _ms(g + 2.4), "worker.emit"),
                 (_ms(g + 2.6), _ms(g + 3.4), "engine.plan"),
                 (_ms(g + 3.4), _ms(g + 6.4), "engine.upload"),
                 (_ms(g + 3.6), _ms(g + 6.2), "shard_args"),
                 (_ms(g + 6.4), _ms(g + 11), "engine.dispatch"),
                 (_ms(g + 6.5), _ms(g + 10.9),
                  "PjitFunction(engine_step)")]
    planes = [("/device:TPU:0", [
        ("XLA Ops", ops),
        ("XLA Modules", [(s, e, "jit_engine_step(123)")
                         for s, e, _ in ops])]),
        ("/host:CPU", [("engine-thread", host)])]
    red = trace_reduce.reduce_planes(planes)
    labels = [n for n, _ in red["idle_gaps"]]
    assert labels == ["engine.upload"]
    assert red["idle_gaps"][0][1] == pytest.approx(4 * 0.008)
    assert list(red["modules"]) == ["jit_engine_step"]
    ctx = {"trace": red, "run": {"decode_steps": 8}}
    assert readers.evaluate(
        {"trace_module_median_s": "engine_step"}, ctx) == pytest.approx(0.1)
    assert readers.evaluate(
        {"trace_module_median_s": "engine_decode_window_full"}, ctx) is None


# -- (d) real compile counts ---------------------------------------------------

@pytest.mark.parametrize("case,moves", [
    ("new shape", True), ("repeat", False), ("another shape", True)])
def test_jax_compiles_counts_first_dispatches_only(case, moves):
    from dynamo_tpu.observability.ledger import install_jax_listeners
    install_jax_listeners()
    n = {"new shape": 3, "repeat": 3, "another shape": 5}[case]
    f = jax.jit(lambda x: jnp.tanh(x) * 3.0 + float(n))
    if case == "repeat":
        f(jnp.ones((n,))).block_until_ready()
    c0, s0 = LEDGER_STATS.jax_compiles, LEDGER_STATS.jax_compile_seconds
    f(jnp.ones((n,))).block_until_ready()
    assert (LEDGER_STATS.jax_compiles > c0) is moves
    assert (LEDGER_STATS.jax_compile_seconds > s0) is moves


def test_a_warm_engine_compiles_nothing():
    eng = make_engine(pipeline_depth=1)
    eng.generate(list(range(10, 40)), sampled(12), "a")
    c0, r0 = LEDGER_STATS.jax_compiles, eng.ledger.recompiles_total
    # another prompt of the same length: the same one would hit the prefix
    # cache, prefill a shorter chunk, and rightly compile that bucket
    eng.generate(list(range(50, 80)), sampled(12, 9), "b")
    assert LEDGER_STATS.jax_compiles == c0
    assert eng.ledger.recompiles_total == r0


# -- (e) the engine-side split of first-token time -----------------------------

def _generate(worker, rid, prompt, ctx, max_tokens=6):
    from dynamo_tpu.protocols.common import (
        PreprocessedRequest, SamplingOptions, StopConditions,
    )
    pre = PreprocessedRequest(
        request_id=rid, token_ids=prompt,
        sampling=SamplingOptions(temperature=0.0),
        stop=StopConditions(max_tokens=max_tokens, ignore_eos=True))

    async def consume():
        async for _ in worker.generate(pre.model_dump(), ctx):
            pass
    return consume()


@pytest.fixture(scope="module")
def traced_requests():
    """Two requests through a NativeEngineWorker, each under its own
    `worker.generate` span as runtime/component.py opens it."""
    from dynamo_tpu.llm.worker import NativeEngineWorker
    SERVING.reset()
    TRACER.configure(enabled=True, sample_rate=1.0, seed=0)
    TRACER.drain()

    async def main():
        worker = await NativeEngineWorker(
            make_engine(pipeline_depth=1)).start()
        spans = {}
        try:
            for rid, prompt in (("q1", list(range(10, 30))),
                                ("q2", list(range(40, 100)))):
                ctx = Context(rid)
                with TRACER.span("worker.generate",
                                 TraceContext(f"trace-{rid}"),
                                 request_id=rid) as span:
                    ctx.trace = span.context()
                    await _generate(worker, rid, prompt, ctx)
                spans[rid] = span.span_id
        finally:
            await worker.stop()
        return spans

    try:
        parents = asyncio.run(main())
        recorded = TRACER.drain()
    finally:
        TRACER.configure(enabled=False)
    counts = (SERVING.engine_queue_wait.count(),
              SERVING.engine_prefill.count())
    return parents, recorded, counts


@pytest.mark.parametrize("rid", ["q1", "q2"])
@pytest.mark.parametrize("name", ["engine.queue", "engine.prefill"])
def test_first_token_split_is_a_child_of_worker_generate(
        traced_requests, rid, name):
    parents, recorded, _ = traced_requests
    mine = [s for s in recorded if s["name"] == name
            and s["trace_id"] == f"trace-{rid}"]
    assert len(mine) == 1
    assert mine[0]["parent_id"] == parents[rid]
    assert mine[0]["dur"] >= 0.0
    if name == "engine.prefill":
        # a 60-token prompt rides two 32-token chunks, a 20-token one one
        assert mine[0]["attrs"]["steps"] == (2 if rid == "q2" else 1)


def test_first_token_histograms_observe_once_a_request(traced_requests):
    _, recorded, counts = traced_requests
    assert counts == (2, 2)
    # the phases ride the same tracer under scope:engine; a program's
    # first dispatch is recorded as `compile`
    phases = {s["name"] for s in recorded
              if s["trace_id"] == "scope:engine"}
    assert phases >= {"plan", "upload", "wait", "commit"}
    assert phases & {"dispatch", "compile"}


def test_an_aborted_request_leaves_no_mark():
    eng = make_engine()
    eng.add_request(EngineRequest("gone", list(range(10, 30)), sampled(4)))
    assert "gone" in eng._first_token_marks
    eng.abort("gone")
    assert not eng._first_token_marks
    eng.generate(list(range(10, 30)), sampled(4), "kept")
    assert not eng._first_token_marks


# -- (f) what the listed layer metrics read exists -----------------------------

def _kept_metrics():
    """PR 48's `per_layer` (benchmark/tests/fixtures/per_layer_pr48.json:
    128 entries, each with its file's `expr`): the KEPT list the guard
    below is parametrised over, so that a fold of today's entries takes
    no case away (ROADMAP M12 (i))."""
    with open(os.path.join(REPO, "benchmark", "tests", "fixtures",
                           "per_layer_pr48.json")) as f:
        return json.load(f)["per_layer"]


def _todays_metrics():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return {m["name"]: readers.load_metric(
            m["name"], os.path.join(REPO, "benchmark"))["expr"]
            for m in json.load(f)["per_layer"]}


# what `trace_window_step_median_s` took the place of (PR 49), as
# benchmark/tests/test_benchmark_lists.py::with_the_leaf has it
_OLD_STEP = {"op": "div", "args": [
    {"trace_module_median_s": "engine_decode_window_full"},
    {"run": "decode_steps"}]}


def _with_the_leaf(expr):
    if expr == _OLD_STEP:
        return {"trace_window_step_median_s": "engine_decode_window"}
    if "args" in expr:
        return {**expr, "args": [_with_the_leaf(a) for a in expr["args"]]}
    return expr


def _leaves(expr, out):
    for key in ("prom", "prom_at_start", "engine", "trace_module_median_s",
                "trace_window_step_median_s", "trace_window_rung_steps"):
        if key in expr:
            out.append((key, expr[key]))
    if "prom_hist_mean" in expr:
        out += [("prom", expr["prom_hist_mean"] + "_sum"),
                ("prom", expr["prom_hist_mean"] + "_count")]
    for arg in expr.get("args", ()):
        _leaves(arg, out)
    return out


@pytest.fixture(scope="module")
def served_sources():
    """`/metrics` of a tiny in-process service after one chat request, an
    `EngineMetrics` snapshot, and the module names of its programs."""
    from dynamo_tpu.frontend.service import HttpService
    from dynamo_tpu.llm.model_card import ModelDeploymentCard
    from dynamo_tpu.llm.pipeline import LocalPipeline
    from dynamo_tpu.llm.worker import NativeEngineWorker
    from tests.http_client import request

    async def main():
        eng = make_engine()
        worker = await NativeEngineWorker(eng).start()
        card = ModelDeploymentCard(
            name="tiny-model", arch="tiny", tokenizer_kind="byte",
            context_length=512, eos_token_ids=[2])
        svc = await HttpService("127.0.0.1", 0).start()
        svc.models.add("tiny-model", LocalPipeline(card, worker), "both")
        try:
            status, _ = await request(
                "127.0.0.1", svc.port, "POST", "/v1/chat/completions",
                {"model": "tiny-model", "max_tokens": 4,
                 "messages": [{"role": "user", "content": "hi"}]})
            assert status == 200
            _, text = await request("127.0.0.1", svc.port, "GET",
                                    "/metrics")
            fields = await worker.submit(lambda e: vars(e.metrics()))
        finally:
            await svc.stop()
            await worker.stop()
        programs = [fn.__wrapped__.__name__ for fns in
                    (eng._step_fns, eng._decode_fns) for fn in fns.values()]
        return (readers.parse_prom(text.decode()), fields,
                {f"jit_{n}" for n in programs})

    return asyncio.run(main())


def _reads_something(metric, expr, served_sources):
    prom, fields, modules = served_sources
    for kind, what in _leaves(expr, []):
        if kind in ("prom", "prom_at_start"):
            assert what in prom, f"{metric}: no series {what} on /metrics"
        elif kind == "engine":
            assert what in fields, f"{metric}: no EngineMetrics.{what}"
        else:
            # a window leaf names the ladder's base: `<base>_full` or a
            # `<base>_w<n>` rung is what it reads
            pat = re.compile(what + ("" if kind.endswith("median_s")
                                     and "window" not in kind
                                     else r"_(full|w\d+)$"
                                     if "window" in kind else ""))
            assert any(pat.search(m) for m in modules), \
                f"{metric}: no program matches {what!r}"


@pytest.mark.parametrize("former", _kept_metrics(),
                         ids=lambda m: m["name"])
def test_listed_layer_metric_reads_something_that_exists(
        served_sources, former):
    """The guard that a rename cannot silently turn a metric into None,
    over the KEPT list: each of PR 48's 128 names is looked up through
    today's entry of the same expression (whatever it is called and
    whichever cells it lists), and what that expression reads exists."""
    want = json.dumps(_with_the_leaf(former["expr"]), sort_keys=True)
    found = [name for name, expr in _todays_metrics().items()
             if json.dumps(expr, sort_keys=True) == want]
    assert found, f"{former['name']}: no entry of today reads its expression"
    _reads_something(found[0], json.loads(want), served_sources)


def test_every_entry_of_today_reads_something_that_exists(served_sources):
    """The same guard over what later PRs listed (a rung's steps, a conv
    layer's state): one case, so that the count above is the kept list's."""
    for name, expr in _todays_metrics().items():
        _reads_something(name, expr, served_sources)


# the expressions that wait for the `benchmark` PR with room in `per_layer`
# (ISSUE 52: 125 of 128 are taken): it lifts each into
# `benchmark/layer_metrics/<name>.json` unchanged, layer "engine host
# loop", keyless (every engine exports the counters, so every cell's)
WAITING_METRICS = {
    # ms of exposed host time a hand-over (a committed step of another
    # kind than the one before it); moves output_tok_s
    "host.exposed_handover_ms": {"op": "mul", "args": [{"const": 1000}, {
        "op": "div", "args": [
            {"prom": "llm_engine_host_exposed_handover_seconds"},
            {"op": "add", "args": [{"engine": "handovers"},
                                   {"const": 1e-9}]}]}]},
    # ms of exposed host time a drain (the call after a commit that
    # ended a row under a window in flight): S17 (c) by itself; moves
    # output_tok_s
    "host.exposed_drain_ms": {"op": "mul", "args": [{"const": 1000}, {
        "op": "div", "args": [
            {"prom": "llm_engine_host_exposed_drain_seconds"},
            {"op": "add", "args": [{"engine": "pipeline_fallbacks"},
                                   {"const": 1e-9}]}]}]},
    # % of the busy wall time inside stalled periods; moves itl_p95_ms
    "step.stall_share": {"op": "mul", "args": [{"const": 100}, {
        "op": "div", "args": [
            {"prom": "llm_engine_period_stall_seconds"},
            {"prom": "llm_engine_period_seconds"}]}]},
}


@pytest.mark.parametrize("name", sorted(WAITING_METRICS))
def test_a_waiting_metric_evaluates_on_what_is_served(served_sources, name):
    """Each waiting expression through the benchmark's own evaluator: its
    leaves exist on the tiny service, and over a window in which the
    counters moved it is the number its comment says."""
    prom, fields, _ = served_sources
    expr = WAITING_METRICS[name]
    _reads_something(name, expr, served_sources)
    before = {**{k: 0.0 for k in prom}, **{k: 0 for k in fields}}
    after = dict(before)
    after.update(llm_engine_host_exposed_handover_seconds=0.06,
                 handovers=20, llm_engine_host_exposed_drain_seconds=0.15,
                 pipeline_fallbacks=10, llm_engine_period_stall_seconds=2.0,
                 llm_engine_period_seconds=50.0)
    ctx = {"prom": (before, after), "engine": (before, after)}
    assert readers.evaluate(expr, ctx) == pytest.approx({
        "host.exposed_handover_ms": 3.0, "host.exposed_drain_ms": 15.0,
        "step.stall_share": 4.0}[name])
    # and on the service's own scrape against itself: no division by
    # zero where no hand-over or drain fell in the window
    same = {"prom": (prom, prom), "engine": (fields, fields)}
    got = readers.evaluate(expr, same)
    assert got is None or got == 0.0


def test_full_window_pattern_matches_one_rung_only(served_sources):
    _, _, modules = served_sources
    pat = re.compile("engine_decode_window_full")
    assert [m for m in modules if pat.search(m)] \
        == ["jit_engine_decode_window_full"]
    assert sum("engine_decode_window" in m for m in modules) == 3


# -- the bounded capture -------------------------------------------------------

def test_capture_profile_is_bounded_and_refuses_a_second(tmp_path):
    from dynamo_tpu.llm.worker import NativeEngineWorker
    from dynamo_tpu.observability import profile

    async def main():
        worker = await NativeEngineWorker(make_engine()).start()
        try:
            first = asyncio.create_task(
                worker.capture_profile(0.3, str(tmp_path / "one")))
            await asyncio.sleep(0.05)
            with pytest.raises(RuntimeError, match="already running"):
                await worker.capture_profile(0.1, str(tmp_path / "two"))
            got = await first
            # and again, once the first has stopped
            await worker.capture_profile(0.1, str(tmp_path / "two"))
            return got
        finally:
            await worker.stop()
    got = asyncio.run(main())
    assert got["trace_dir"] == str(tmp_path / "one")
    for d in ("one", "two"):
        xplane = trace_reduce.find_xplane(str(tmp_path / d))
        assert os.path.isfile(os.path.join(os.path.dirname(xplane),
                                           profile.SUMMARY))
    assert got["summary"] == os.path.join(
        os.path.dirname(trace_reduce.find_xplane(got["trace_dir"])),
        profile.SUMMARY)


@pytest.fixture(scope="module")
def captured_serving(tmp_path_factory):
    """A `capture_profile` of a worker serving two requests: what it
    answered, the planes of its xplane, and the processes the worker
    started meanwhile."""
    from dynamo_tpu.llm.worker import NativeEngineWorker
    from dynamo_tpu.observability import profile
    eng = make_engine(pipeline_depth=2)
    eng.generate(list(range(10, 40)), sampled(12), "warm")
    out = str(tmp_path_factory.mktemp("steps"))
    children = []
    spawn = asyncio.create_subprocess_exec

    async def spy(*argv, **kw):
        children.append((argv, kw.get("env", {})))
        return await spawn(*argv, **kw)

    async def main():
        worker = await NativeEngineWorker(eng).start()

        def serve(tag, shift):
            # other words of the same lengths, and none that a request
            # before began with: no prefix to reuse, so both rounds run
            # the same programs and the capture holds no compile
            return asyncio.gather(
                _generate(worker, f"{tag}1", list(range(shift, shift + 20)),
                          Context(f"{tag}1"), max_tokens=20),
                _generate(worker, f"{tag}2", list(range(shift, shift + 60)),
                          Context(f"{tag}2"), max_tokens=12))
        asyncio.create_subprocess_exec = spy
        try:
            await serve("w", 70)
            capture = asyncio.create_task(worker.capture_profile(1.0, out))
            await asyncio.sleep(0.1)
            await serve("c", 130)
            return await capture
        finally:
            asyncio.create_subprocess_exec = spawn
            await worker.stop()
    got = asyncio.run(main())
    xplane = profile.find_xplane(out)
    return got, profile.load_planes(xplane), children, eng


def test_a_dispatch_says_what_it_launched_beside_its_name(captured_serving):
    """The annotation's NAME stays `engine.dispatch` (the benchmark's
    reducer labels a gap by it); what it launched rides as stats: kind,
    bucket, a running `seq`, and `ahead` where a program was in flight."""
    _, planes, _, _ = captured_serving
    events = [ev for pname, lines in planes if pname.startswith("/host:")
              for _, evs in lines for ev in evs
              if ev[2].startswith("engine.")]
    names = {ev[2] for ev in events}
    assert names <= {f"engine.{p}" for p in PHASES} | {"engine.compile"}
    launches = sorted((ev for ev in events if ev[2] == "engine.dispatch"),
                      key=lambda ev: ev[0])
    assert len(launches) >= 4
    for ev in launches:
        stats = ev[3]
        assert stats["kind"] in ("mixed", "prefill", "window")
        assert set(stats) == {"kind", "seq", "ahead", "rows"} | (
            {"rung"} if stats["kind"] == "window" else {"chunk"})
    seqs = [ev[3]["seq"] for ev in launches]
    assert seqs == list(range(seqs[0], seqs[0] + len(seqs)))
    assert {"mixed", "window"} <= {ev[3]["kind"] for ev in launches}
    assert 1 in {ev[3]["ahead"] for ev in launches}     # two deep
    # no other phase carries stats (a key's first dispatch, named
    # `engine.compile`, is a dispatch too)
    assert all(not ev[3] for ev in events
               if ev[2] not in ("engine.dispatch", "engine.compile"))


def test_the_summary_is_written_by_a_child(captured_serving):
    """The reduction runs `python -m dynamo_tpu.observability.profile` in
    a child that is held off the chip; the answer is the file's path and
    top level; beside the xplane lie the programs launched meanwhile."""
    from dynamo_tpu.observability import profile
    got, _, children, eng = captured_serving
    (argv, env), = children
    assert argv[1:3] == ("-m", "dynamo_tpu.observability.profile")
    assert argv[3] == got["trace_dir"] and env["JAX_PLATFORMS"] == "cpu"
    home = os.path.dirname(got["summary"])
    assert os.path.basename(got["summary"]) == profile.SUMMARY
    with open(got["summary"]) as f:
        summary = json.load(f)
    # a CPU capture has no device plane: the table says so by what it lacks
    assert summary["device"]["chips"] == 0
    assert got["device"] == summary["device"]
    assert summary["source"]["reduce_s"] > 0
    texts = sorted(os.listdir(os.path.join(home, "programs")))
    assert texts and all(t.endswith(".hlo.txt") for t in texts)
    assert any(t.startswith("engine_step-") for t in texts)
    assert any(t.startswith("engine_decode_window_") for t in texts)
    assert len(texts) <= len(eng._programs)
    assert not os.path.exists(os.path.join(home, "steps.jsonl"))


def test_program_texts_compiles_nothing():
    """`program_texts` lowers a dispatched program again from its first
    dispatch's shapes: jax hands back the executable it holds."""
    eng = make_engine()
    eng.generate(list(range(10, 40)), sampled(12), "one")
    eng.generate(list(range(10, 40)), sampled(12), "two")
    before = LEDGER_STATS.jax_compiles
    texts = eng.program_texts()
    assert LEDGER_STATS.jax_compiles == before
    assert len(texts) == len(eng._programs) >= 2
    for text in texts.values():
        assert 'op_name="jit(engine_' in text
    # a capture that began after the last launch holds no program
    assert eng.program_texts(since=eng._dispatch_seq) == {}


@pytest.mark.parametrize("env,query,status", [
    (False, "seconds=0.2", 404), (True, "seconds=0.2", 200),
    (True, "seconds=abc", 400), (True, "seconds=600", 400)])
def test_debug_profile_route(tmp_path, monkeypatch, env, query, status):
    from dynamo_tpu.frontend.service import HttpService
    from dynamo_tpu.llm.model_card import ModelDeploymentCard
    from dynamo_tpu.llm.pipeline import LocalPipeline
    from dynamo_tpu.llm.worker import NativeEngineWorker
    from tests.http_client import request
    if env:
        monkeypatch.setenv("DYN_JAX_PROFILE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("DYN_JAX_PROFILE_DIR", raising=False)

    async def main():
        worker = await NativeEngineWorker(make_engine()).start()
        card = ModelDeploymentCard(
            name="tiny-model", arch="tiny", tokenizer_kind="byte",
            context_length=512, eos_token_ids=[2])
        svc = await HttpService("127.0.0.1", 0).start()
        svc.models.add("tiny-model", LocalPipeline(card, worker), "both")
        try:
            return await request("127.0.0.1", svc.port, "POST",
                                 f"/debug/profile?{query}")
        finally:
            await svc.stop()
            await worker.stop()

    got, body = asyncio.run(main())
    assert got == status
    if status == 200:
        answer = json.loads(body)
        out = answer["trace_dir"]
        assert out.startswith(str(tmp_path))
        assert trace_reduce.find_xplane(out)
        # the summary's path and its top level
        assert os.path.isfile(answer["summary"])
        assert answer["summary"].startswith(out)
        assert {"device", "programs", "idle_gaps", "seconds", "cost_s"} <= set(answer)


def test_no_whole_life_profile_hook_is_left():
    """`jax.profiler.start_trace` lives in capture_profile alone, and the
    worker has no process-wide owner any more."""
    import dynamo_tpu.llm.worker as worker_mod
    assert not hasattr(worker_mod, "_PROFILE_OWNER")
    hits = []
    for root, _, files in os.walk(os.path.join(REPO, "dynamo_tpu")):
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(root, name)) as f:
                    if "jax.profiler.start_trace" in f.read():
                        hits.append(name)
    assert hits == ["worker.py"]


def test_the_older_attribution_harness_is_gone():
    """`tools/decode_profile.py`, its artifact, the engine's
    `profile_sync` branch and `PhaseTimer.split()` went with ISSUE 35:
    one record a step() call replaced them, and nothing names them."""
    assert not hasattr(make_engine(), "profile_sync")
    assert not hasattr(PhaseTimer, "split")
    for gone in ("tools/decode_profile.py", "DECODE_PROFILE.jsonl"):
        assert not os.path.exists(os.path.join(REPO, gone))
    names = ("decode_profile", "DECODE_PROFILE", "profile_sync")
    hits = []
    for top in ("dynamo_tpu", "tools", "docs", "benchmark", "tests",
                "bench.py", "BASELINE.json", "README.md"):
        path = os.path.join(REPO, top)
        files = [path] if os.path.isfile(path) else [
            os.path.join(root, name) for root, _, found in os.walk(path)
            for name in found if name.endswith((".py", ".md", ".json"))]
        for name in files:
            if os.path.abspath(name) == os.path.abspath(__file__):
                continue
            with open(name, errors="replace") as f:
                text = f.read()
            hits += [(os.path.relpath(name, REPO), n) for n in names
                     if n in text]
    assert hits == []
