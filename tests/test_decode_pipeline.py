"""Overlapped decode pipeline (engine two-deep host/device loop).

Exactness bar: pipelined streams must be TOKEN-IDENTICAL to the
synchronous loop — greedy and seeded-sampling, including a row that ends
mid-window (a stop id, its length) with a follow-up window in flight
behind it: the reconciliation fallback, after which the follow-up is
COMMITTED for the rows that live on and the engine re-plans (it is never
run twice, and dropped only when no row lives on), and abort-mid-window.
Invariant bar (the CPU microbench): the pipelined loop issues exactly one
blocking host sync per committed window, and steady-state windows upload
zero plan arrays. docs/PERF.md has the design and exactness argument.

The two engines (depth=1 reference, depth=2 pipelined) are module-scoped
and reused across tests — engine rebuilds recompile every jitted program
(~4s each on CPU), and serving-realism-wise a reused engine IS the
scenario the pipeline must survive: counter assertions therefore diff
against a snapshot instead of assuming zero.
"""
import dataclasses
import json
import os

import numpy as np
import pytest

from dynamo_tpu.engine.config import EngineConfig, ModelConfig
from dynamo_tpu.engine.engine import NativeEngine
from dynamo_tpu.engine.scheduler import EngineRequest, SamplingParams

CFG = ModelConfig(dtype="float32", max_model_len=512)


def make_engine(depth, **kw):
    defaults = dict(
        page_size=64, num_pages=32, max_slots=4, max_prefill_chunk=32,
        prefill_buckets=(8, 16, 32), max_model_len=512, decode_steps=4,
        pipeline_depth=depth)
    defaults.update(kw)
    return NativeEngine(CFG, EngineConfig(**defaults), seed=0)


@pytest.fixture(scope="module")
def eng_sync():
    return make_engine(1)


@pytest.fixture(scope="module")
def eng_pipe():
    return make_engine(2)


def snap(eng):
    return {k: getattr(eng, k) for k in (
        "decode_windows", "pipeline_windows", "pipeline_overlapped",
        "pipeline_fallbacks", "window_steps_reconciled",
        "window_steps_discarded", "decode_host_syncs",
        "decode_plan_uploads")}


def delta(eng, before):
    return {k: getattr(eng, k) - v for k, v in before.items()}


def drive(eng, prompts, params_list, tag):
    got = {}
    for i, (pr, p) in enumerate(zip(prompts, params_list)):
        eng.add_request(EngineRequest(f"{tag}{i}", pr, p))
        got[f"{tag}{i}"] = []
    done = set()
    while len(done) < len(prompts):
        for ev in eng.step():
            if ev.token is not None:
                got[ev.request_id].append(ev.token)
            if ev.finished:
                done.add(ev.request_id)
    return [got[f"{tag}{i}"] for i in range(len(prompts))]


def test_pipelined_token_identity_greedy_and_sampled(eng_sync, eng_pipe):
    """depth=2 streams match depth=1 exactly, greedy and seeded-sampled,
    with concurrent requests of different budgets (mid-window finishes
    exercise the reconciliation fallback)."""
    prompts = [list(range(3, 19)), list(range(40, 50))]
    for tag, params in (
        ("g", [SamplingParams(max_tokens=13, temperature=0.0,
                              ignore_eos=True),
               SamplingParams(max_tokens=6, temperature=0.0,
                              ignore_eos=True)]),
        ("s", [SamplingParams(max_tokens=9, temperature=0.9, top_k=12,
                              seed=7, ignore_eos=True),
               SamplingParams(max_tokens=9, temperature=0.7, top_p=0.8,
                              seed=3, ignore_eos=True)]),
    ):
        before = snap(eng_pipe)
        sync = drive(eng_sync, prompts, params, f"id_{tag}_s")
        pipe = drive(eng_pipe, prompts, params, f"id_{tag}_p")
        assert pipe == sync
        # the pipeline actually engaged: windows committed while their
        # follow-up executed on device
        d = delta(eng_pipe, before)
        assert d["pipeline_windows"] > 0
        assert d["pipeline_overlapped"] > 0


def test_stop_mid_window_fallback_token_identity(eng_sync, eng_pipe):
    """A hidden stop id sampled mid-window changes slot membership at
    commit, under an in-flight follow-up (the fallback counter): the
    stream must still equal the synchronous loop's. The row was the
    plan's only one, so the follow-up has no one to be committed for and
    is dropped, and counted."""
    prompt = list(range(10, 26))
    ref = eng_sync.generate(
        prompt, SamplingParams(max_tokens=12, ignore_eos=True), "probe")
    stop = ref[5]  # mid-second-window (windows of 4; ref[0] is prefill's)
    p = SamplingParams(max_tokens=12, ignore_eos=True,
                       stop_token_ids=(stop,))
    sync = eng_sync.generate(prompt, p, "stop_s")
    before = snap(eng_pipe)
    pipe = eng_pipe.generate(prompt, p, "stop_p")
    assert pipe == sync == ref[:5]
    d = delta(eng_pipe, before)
    assert d["pipeline_fallbacks"] >= 1
    assert d["window_steps_discarded"] == eng_pipe.cfg.decode_steps
    assert d["window_steps_reconciled"] == 0
    assert eng_pipe._pipeline is None and not eng_pipe.has_work()


# -- a row ends under an in-flight follow-up: commit it for the rest --------

SAMPLING = {"greedy": dict(temperature=0.0),
            "sampled": dict(temperature=0.8, top_k=20, seed=11)}
both_samplings = pytest.mark.parametrize("mode", list(SAMPLING))


def fresh_prompts(seed, lens, vocab=CFG.vocab_size):
    rng = np.random.default_rng(seed)
    return [rng.integers(2, vocab, n).tolist() for n in lens]


def hidden_stop(stream, after=9):
    """A token the stream generates for the first time at its `after`-th
    step or later: as a stop id it ends the row mid-window, in the
    pipeline's steady state, where no plan foresees it."""
    return next(t for i, t in enumerate(stream)
                if i >= after and t not in stream[:i])


def stop_case(sync, pipe, prompts, samp, tag, max_tokens=30):
    """Row 0 of `prompts` ends on a stop id that only the commit sees;
    (synchronous streams, pipelined streams, the pipelined counters)."""
    def params(stop=()):
        return [SamplingParams(max_tokens=max_tokens, ignore_eos=True,
                               stop_token_ids=stop if i == 0 else (),
                               **samp) for i in range(len(prompts))]
    free = drive(sync, prompts, params(), f"{tag}_free")
    stop = (hidden_stop(free[0]),)
    want = drive(sync, prompts, params(stop), f"{tag}_s")
    assert len(want[0]) < max_tokens == len(want[1])
    before = snap(pipe)
    got = drive(pipe, prompts, params(stop), f"{tag}_p")
    return want, got, delta(pipe, before)


def assert_committed_not_rerun(d):
    """The follow-up that ran under the commit which ended a row was
    committed for the rows still live: none dropped, and every window
    program dispatched was fetched and committed (a re-run of the
    follow-up's positions would be a dispatch without a commit)."""
    assert d["pipeline_fallbacks"] >= 1, "the fallback was not exercised"
    assert d["window_steps_reconciled"] > 0
    assert d["window_steps_discarded"] == 0
    assert d["decode_windows"] == d["decode_host_syncs"]


@both_samplings
def test_a_row_ending_on_a_stop_id_keeps_the_follow_up(eng_sync, eng_pipe,
                                                       mode):
    """The KV twin of test_ling_state's
    test_a_discarded_follow_up_window_is_committed_not_rerun: three rows,
    one ends mid-window on a stop id no plan foresees. The window in
    flight behind that commit is committed for the two rows that live on
    and not run again: depth 2 equals depth 1 token for token."""
    prompts = fresh_prompts(5, (12, 9, 14))
    want, got, d = stop_case(eng_sync, eng_pipe, prompts, SAMPLING[mode],
                             f"stopid_{mode}")
    assert got == want
    assert_committed_not_rerun(d)


@both_samplings
def test_a_row_ending_by_length_keeps_the_follow_up(eng_sync, eng_pipe,
                                                    mode):
    """The benchmark's case (every request carries ignore_eos): a row's
    budget runs out inside a FULL rung (the scheduler keeps the full rung
    while the smallest remaining budget is 3 or more: 11 tokens after
    the prefill's are two rungs of 4 and 3 of the third) while the
    follow-up runs, in which the row is dead on the device from max_pos."""
    prompts = fresh_prompts(6, (10, 13, 11))
    tag = f"length_{mode}"
    params = [SamplingParams(max_tokens=n, ignore_eos=True, **SAMPLING[mode])
              for n in (12, 30, 30)]
    want = drive(eng_sync, prompts, params, f"{tag}_s")
    before = snap(eng_pipe)
    got = drive(eng_pipe, prompts, params, f"{tag}_p")
    assert got == want and [len(o) for o in got] == [12, 30, 30]
    d = delta(eng_pipe, before)
    assert_committed_not_rerun(d)
    assert d["window_steps_reconciled"] % eng_pipe.cfg.decode_steps == 0


@both_samplings
def test_the_freed_slot_goes_to_a_waiting_request(eng_sync, eng_pipe, mode):
    """Every slot is busy; a request arrives while the reconciled
    follow-up is in flight and waits for the slot the finished row freed.
    The follow-up is committed first (the arrival's prefill is dispatched
    behind it), then the re-plan admits the arrival: its stream is its
    solo stream, the survivors' the synchronous loop's, and every page
    comes back exactly once."""
    tag = f"slot_{mode}"
    prompts = fresh_prompts(7, (10, 13, 11, 9, 15))
    params = [SamplingParams(max_tokens=n, ignore_eos=True, **SAMPLING[mode])
              for n in (12, 30, 30, 30, 20)]
    want = [eng_sync.generate(pr, p, f"{tag}_solo{i}")
            for i, (pr, p) in enumerate(zip(prompts, params))]
    eng = eng_pipe
    assert len(prompts) - 1 == eng.cfg.max_slots
    before = snap(eng)
    got = {f"{tag}{i}": [] for i in range(len(prompts))}
    for i in range(4):
        eng.add_request(EngineRequest(f"{tag}{i}", prompts[i], params[i]))
    arrived = False
    while eng.has_work():
        for ev in eng.step():
            if ev.token is not None:
                got[ev.request_id].append(ev.token)
        pend = eng._pipeline
        if not arrived and pend is not None and pend.get("reconciled"):
            # the commit just ended row 0 under this follow-up
            assert eng.scheduler.running.count(None) == 1
            eng.add_request(EngineRequest(f"{tag}4", prompts[4], params[4]))
            arrived = True
    assert arrived
    assert [got[f"{tag}{i}"] for i in range(len(prompts))] == want
    assert_committed_not_rerun(delta(eng, before))
    assert eng.scheduler.allocator.num_free == eng.cfg.num_pages


def _bench_config(name, **change):
    from dynamo_tpu.models.loader import config_from_hf
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "..", "benchmark", "configs", name,
                           "config.json")) as f:
        hf = json.load(f)
    return dataclasses.replace(config_from_hf(dict(hf, **change), name=name),
                               dtype="float32", max_model_len=512)


CACHE_KINDS = {
    # per-row scales beside the int8 rows: a row's write touches its own
    # scale alone
    "int8-pool": lambda: (dataclasses.replace(CFG, kv_quant="int8"), {}),
    # ONE leaf of latents a token (DeepseekV3), expert layers behind it
    "latent-leaf": lambda: (_bench_config("rehearsal-tiny-moonlight"), {}),
    # a second pool for the sliding layers, cut from the front at every
    # commit: a 16-token window over 4-token pages, so that a commit
    # releases pages while the follow-up is in flight against the old
    # table (the file's own window, 1024, is never left in 40 tokens)
    "window-pool": lambda: (
        _bench_config("rehearsal-tiny-mellum", sliding_window=16),
        dict(page_size=4, num_pages=128)),
}


@pytest.mark.parametrize("kind", list(CACHE_KINDS))
def test_the_follow_up_is_kept_in_every_cache_kind(kind):
    """Over the cache kinds the exactness argument names (docs/PERF.md
    section 3) that a CPU can build: row 0 ends by length at each of
    eight successive positions (small pages chain one follow-up a plan,
    so only some of them fall under one) and once on a stop id; depth 2
    equals depth 1 token for token every time, the follow-ups are
    committed and not run again, and both pools come back whole."""
    cfg, kw = CACHE_KINDS[kind]()

    def build(depth):
        return NativeEngine(cfg, EngineConfig(**dict(dict(
            page_size=16, num_pages=64, max_slots=4, max_prefill_chunk=32,
            prefill_buckets=(8, 16, 32), max_model_len=512, decode_steps=4,
            pipeline_depth=depth), **kw)), seed=0)
    sync, pipe = build(1), build(2)
    prompts = fresh_prompts(8, (12, 9, 14), cfg.vocab_size)
    before = snap(pipe)
    for n in range(8, 16):
        params = [SamplingParams(max_tokens=m, ignore_eos=True,
                                 **SAMPLING["greedy"])
                  for m in (n, 30, 30)]
        want = drive(sync, prompts, params, f"{kind}{n}_s")
        assert drive(pipe, prompts, params, f"{kind}{n}_p") == want
    want, got, _ = stop_case(sync, pipe, prompts, SAMPLING["sampled"], kind)
    assert got == want
    assert_committed_not_rerun(delta(pipe, before))
    sch = pipe.scheduler
    assert sch.allocator.num_free == pipe.cfg.num_pages
    if kind == "window-pool":
        assert sch.window_released > 0
        assert sch.window_alloc.num_free == sch.window_alloc.num_pages


def test_abort_mid_window_drops_cleanly(eng_sync, eng_pipe):
    """Aborting a request while its window is in flight must drop its
    tokens without corrupting the surviving request's stream (the commit
    identity guard) or the allocator (no double-free)."""
    p = SamplingParams(max_tokens=24, temperature=0.0, ignore_eos=True)
    prompts = [list(range(3, 19)), list(range(40, 50))]
    solo = eng_sync.generate(prompts[0], p, "ab_solo")

    eng = eng_pipe
    for i, pr in enumerate(prompts):
        eng.add_request(EngineRequest(f"ab{i}", pr, p))
    got = {"ab0": [], "ab1": []}
    aborted = False
    finished = set()
    while eng.has_work():
        if eng._pipeline is not None and not aborted \
                and len(got["ab1"]) >= 2:
            # a window is in flight and ab1 has streamed: abort it now
            assert eng.abort("ab1")
            aborted = True
        for ev in eng.step():
            got[ev.request_id].append(ev.token)
            if ev.finished:
                finished.add(ev.request_id)
    assert aborted
    assert "ab0" in finished and "ab1" not in finished
    # survivor is exact; victim never emitted again after the abort
    assert [t for t in got["ab0"] if t is not None] == solo
    free = eng.scheduler.allocator.num_free
    # ab0 finished too, so every page is back exactly once
    assert free == eng.cfg.num_pages


def test_microbench_one_sync_per_window_zero_uploads(eng_pipe,
                                                     monkeypatch):
    """Regression guard on the overlap invariant: with a stable slot set
    whose pages are fully allocated at the first decode plan, the
    pipelined loop issues exactly ONE blocking host sync per committed
    window and uploads plan arrays exactly once."""
    import jax

    eng = eng_pipe
    p = SamplingParams(max_tokens=32, temperature=0.0, ignore_eos=True)
    eng.add_request(EngineRequest("micro", list(range(10, 30)), p))
    while eng.scheduler.waiting:
        eng.step()
    before = snap(eng)

    syncs = {"n": 0}
    real_get = jax.device_get

    def counting_get(x):
        syncs["n"] += 1
        return real_get(x)

    monkeypatch.setattr(jax, "device_get", counting_get)
    while eng.has_work():
        eng.step()
    d = delta(eng, before)
    windows_committed = d["pipeline_windows"]
    assert windows_committed == 32 // eng.cfg.decode_steps
    # <= 1 host sync per window, measured at the jax boundary
    assert syncs["n"] <= windows_committed
    assert d["decode_host_syncs"] == windows_committed
    # prompt(20) + max_tokens(32) fit one 64-token page: allocation never
    # grows mid-request, so only the FIRST window staged host arrays
    assert d["decode_plan_uploads"] == 1
    # and every window after the first committed while its follow-up ran
    assert d["pipeline_overlapped"] >= windows_committed - 2


def test_pipeline_counters_on_metrics(eng_pipe):
    """EngineMetrics carries the pipeline occupancy counters and they
    ADVANCE across a run (the /metrics source of truth; the exporter
    gauge rendering is covered in test_metrics_exporter.py)."""
    eng = eng_pipe
    m0 = eng.metrics()
    p = SamplingParams(max_tokens=16, temperature=0.0, ignore_eos=True)
    eng.generate(list(range(5, 21)), p, "metrics")
    m1 = eng.metrics()
    assert m1.decode_windows > m0.decode_windows
    assert m1.pipeline_windows > m0.pipeline_windows
    assert m1.pipeline_overlapped > m0.pipeline_overlapped
    assert m1.decode_host_syncs > m0.decode_host_syncs
    assert m1.decode_plan_uploads > m0.decode_plan_uploads
    # the wire path keeps them: WorkerMetrics.from_dict round-trip
    import dataclasses

    from dynamo_tpu.kv_router.scoring import WorkerMetrics
    w = WorkerMetrics.from_dict(dataclasses.asdict(m1))
    assert w.pipeline_overlapped == m1.pipeline_overlapped
    assert w.decode_plan_uploads == m1.decode_plan_uploads


def test_ledger_on_is_token_identical_and_samples_every_step(eng_sync,
                                                             eng_pipe):
    """Identity-matrix extension for the step ledger (ISSUE 10): with
    the ledger FORCED ON for the pipelined engine and FORCED OFF for
    the reference, greedy and seeded-sampled streams stay
    token-identical — the ledger only reads host state — while every
    committed window/prefill lands one sample with honest padding and
    occupancy accounting."""
    from dynamo_tpu.observability.ledger import LedgerStats
    prompts = [list(range(3, 19)), list(range(40, 50))]
    stats = LedgerStats()
    old = (eng_pipe.ledger.enabled, eng_pipe.ledger.stats,
           eng_sync.ledger.enabled)
    eng_pipe.ledger.configure(enabled=True)
    eng_pipe.ledger.stats = stats
    eng_sync.ledger.configure(enabled=False)
    try:
        before_len = len(eng_pipe.ledger)
        for tag, params in (
            ("lg", [SamplingParams(max_tokens=11, temperature=0.0,
                                   ignore_eos=True),
                    SamplingParams(max_tokens=5, temperature=0.0,
                                   ignore_eos=True)]),
            ("ls", [SamplingParams(max_tokens=7, temperature=0.9,
                                   top_k=12, seed=7, ignore_eos=True),
                    SamplingParams(max_tokens=7, temperature=0.7,
                                   top_p=0.8, seed=3, ignore_eos=True)]),
        ):
            sync = drive(eng_sync, prompts, params, f"{tag}_s")
            pipe = drive(eng_pipe, prompts, params, f"{tag}_p")
            assert pipe == sync
        recs = eng_pipe.ledger.drain(clear=False)[before_len:]
        assert recs, "ledger recorded nothing with recording enabled"
        kinds = {r["kind"] for r in recs}
        assert "prefill" in kinds and "decode" in kinds
        for r in recs:
            # padding charge is never below the useful tokens, and
            # occupancy reads the real allocator
            assert r["tokens_padded"] >= r["tokens_useful"] > 0
            assert 0 <= r["kv_used"] <= r["kv_total"] == \
                eng_pipe.cfg.num_pages
        # steady-state invariant: re-driving the SAME workload shape
        # dispatches no new (program, bucket) keys — zero recompile
        # events on the ledger (what the llm_engine_recompiles gauge
        # staying flat means in production)
        mark = len(eng_pipe.ledger.drain(clear=False))
        drive(eng_pipe, prompts,
              [SamplingParams(max_tokens=11, temperature=0.0,
                              ignore_eos=True),
               SamplingParams(max_tokens=5, temperature=0.0,
                              ignore_eos=True)], "lg2_p")
        warm = eng_pipe.ledger.drain(clear=False)[mark:]
        assert warm
        assert sum(r["recompiles"] for r in warm) == 0
        m = eng_pipe.metrics()
        assert m.engine_steps == eng_pipe.ledger.steps > 0
        assert m.engine_pad_frac == pytest.approx(
            eng_pipe.ledger.pad_fraction(), abs=1e-4)   # rounded field
    finally:
        eng_pipe.ledger.enabled, eng_pipe.ledger.stats = old[0], old[1]
        eng_sync.ledger.enabled = old[2]


def test_depth_one_is_fully_synchronous(eng_sync):
    """pipeline_depth=1 keeps the old loop: no deferred commits, no
    pipeline counters, events in the same step as the dispatch."""
    eng = eng_sync
    before = snap(eng)
    p = SamplingParams(max_tokens=8, temperature=0.0, ignore_eos=True)
    out = eng.generate(list(range(5, 21)), p, "d1")
    assert len(out) == 8
    assert eng._pipeline is None
    d = delta(eng, before)
    assert d["pipeline_windows"] == 0
    assert d["decode_host_syncs"] == d["decode_windows"] > 0
