"""The mixed chain (NativeEngine._chain_step, docs/PERF.md section 3): a
mixed step is dispatched before the mixed step in front of it is fetched.

Bar: against the synchronous loop ON THE SAME ENGINE (the chain refused
by `_chain_ok`, nothing else touched, so both sides run the same
programs), every request's stream is token for token and event for
event the same, greedy and seeded-sampled, whatever happens under a step
in flight: a row ends by length (the host plans that), a row ends on a
stop id (the host could not), a request is aborted, a request arrives, a
prompt's last chunk gives a first token that goes on decoding, the
engine is left alone with a step in flight. Over a dense model, a
dropless-MoE model, a model with a window pool and the recurrent-state
model, whose state a step advances in place and nobody may run twice.

An arrival joins the first step PLANNED after it, which under the chain
is one step later than in the synchronous loop; rows do not see each
other, so the streams are compared per request, not interleaved.
"""
import numpy as np
import pytest

from dynamo_tpu.engine.config import EngineConfig, ModelConfig
from dynamo_tpu.engine.engine import NativeEngine
from dynamo_tpu.engine.scheduler import (
    EngineRequest, SamplingParams, Scheduler,
)
from dynamo_tpu.observability.ledger import (
    LEDGER_STATS, install_jax_listeners,
)
from tests import test_ling, test_mellum, test_olmoe

KW = dict(page_size=16, num_pages=96, max_slots=4, max_prefill_chunk=16,
          prefill_buckets=(8, 16), max_model_len=256, decode_steps=4,
          pipeline_depth=2, max_prefill_batch=2)
MODELS = {
    "dense": (ModelConfig(dtype="float32", max_model_len=256), KW),
    "moe": (test_olmoe.TINY, KW),
    "window_pool": (test_mellum.TINY,
                    dict(KW, page_size=4, num_pages=384)),
    "recurrent": (test_ling.TINY, KW),
}
# (prompt length, max_tokens): more requests than slots and prompts of
# several 16-token chunks, so the queue holds work while rows decode and
# the steps stay mixed; the short budgets end inside the chain
REQUESTS = ((70, 7), (21, 2), (37, 12), (40, 1), (45, 3), (33, 2), (62, 4),
            (28, 1), (52, 3), (36, 2), (50, 1), (44, 5))


@pytest.fixture(scope="module", params=list(MODELS))
def eng(request):
    cfg, kw = MODELS[request.param]
    eng = NativeEngine(cfg, EngineConfig(**kw), seed=0)
    # the first program an engine runs is handed the cache as its init
    # left it, every later one a program's output, and jax tells the two
    # apart: serve one prompt twice, so that no test below meets the
    # first kind and counts its second compile
    for tag in ("w0", "w1"):
        eng.generate([3, 4, 5], SamplingParams(max_tokens=2), tag)
    return eng


def params_for(sampled: bool, i: int, n: int, **kw) -> SamplingParams:
    if sampled:
        kw.update(temperature=0.8, top_k=20, top_p=0.9, seed=1000 + i)
    return SamplingParams(max_tokens=n, **kw)


def prompts_for(eng, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(2, eng.model_cfg.vocab_size, n).tolist()
            for n, _ in REQUESTS]


def run(eng, tag, requests, chained, monkeypatch, arrive=None, aborts=None,
        spy=None):
    """Serve `requests` [(prompt, params)]: request i is added before
    call `arrive[i]` (default 0; "flight": alone, before the first call
    that finds a step in flight, and `arrive[i]` then holds that call);
    `aborts(eng)` names, before each call, the
    requests to abort now. Returns
    {i: [(token, finished, finish_reason)]}, and the engine drained."""
    arrive = arrive if arrive is not None else {}
    # no run finds the last one's prompts in the prefix cache: every page
    # is taken blank and handed back
    alloc = eng.scheduler.allocator
    for pid in [alloc.allocate() for _ in range(alloc.num_pages)]:
        alloc.free(pid)
    with monkeypatch.context() as m:
        if not chained:
            m.setattr(eng, "_chain_ok", lambda seqs=(): False)
        if spy is not None:
            stage = eng._stage_step
            m.setattr(eng, "_stage_step", lambda *a, **k: (
                spy(*a, **k), stage(*a, **k))[1])
        got = {i: [] for i in range(len(requests))}
        open_ = set(got)
        ids = {f"{tag}{i}": i for i in got}
        for call in range(600):
            for i, (prompt, p) in enumerate(requests):
                if arrive.get(i, 0) == "flight" and eng._flight is not None \
                        and call not in arrive.values():
                    arrive[i] = call
                if arrive.get(i, 0) == call:
                    eng.add_request(EngineRequest(f"{tag}{i}", prompt, p))
            for rid in aborts(eng) if aborts else ():
                assert eng.abort(rid)
                open_.discard(ids[rid])
            if not open_ and not eng.has_work():
                break
            for ev in eng.step():
                i = ids[ev.request_id]
                assert i in open_, f"event for closed request {i}"
                got[i].append((ev.token, ev.finished, ev.finish_reason))
                if ev.finished:
                    open_.discard(i)
        else:
            raise AssertionError("the engine did not drain")
    assert eng._flight is None and eng._pipeline is None
    sch = eng.scheduler
    assert not sch.waiting and not any(sch.running) and not sch.params
    assert sch.allocator.num_free == sch.allocator.num_pages
    if sch.window_alloc is not None:
        assert sch.window_alloc.num_free == sch.window_alloc.num_pages
    if sch.state_slots is not None:
        assert sch.state_slots.used == 0
    return got


def counters(eng):
    return (eng.mixed_steps, eng.mixed_steps_chained,
            eng.mixed_steps_replanned)


@pytest.mark.parametrize("sampled", [False, True],
                         ids=["greedy", "sampled"])
def test_rows_that_end_by_length_inside_a_chain(eng, monkeypatch, sampled):
    """Every ending here is by `max_tokens`: the host plans each, so the
    chain is never broken, nothing is planned twice, and with every
    request queued up front the chained loop plans the very steps the
    synchronous one does: no program, no bucket, no variant more."""
    reqs = [(p, params_for(sampled, i, n))
            for i, (p, (_, n)) in enumerate(zip(prompts_for(eng), REQUESTS))]
    sync = run(eng, f"ls{sampled}", reqs, False, monkeypatch)
    assert [len(v) for v in sync.values()] == [n for _, n in REQUESTS]
    assert all(v[-1][1:] == (True, "length") for v in sync.values())
    seen = set(eng._seen_programs)
    before = counters(eng)
    install_jax_listeners()
    compiles = LEDGER_STATS.jax_compiles
    chained = run(eng, f"lc{sampled}", reqs, True, monkeypatch)
    assert chained == sync
    steps, linked, replanned = np.subtract(counters(eng), before)
    assert linked >= 0.7 * steps and steps > 10 and replanned == 0
    # the one program set: the variants are the parent's eight, and the
    # chained run dispatched no new static key and had XLA build nothing
    # (a step handed the tokens of the one before it and a step handed
    # none are one program)
    assert sorted(eng._step_fns) == sorted(
        (rp, lp, mm) for rp in (False, True) for lp in (False, True)
        for mm in (False, True))
    assert set(eng._seen_programs) == seen
    assert LEDGER_STATS.jax_compiles == compiles


@pytest.mark.parametrize("sampled", [False, True],
                         ids=["greedy", "sampled"])
def test_a_stop_id_the_host_could_not_foresee(eng, monkeypatch, sampled):
    """Two rows end on a token they sample: the step behind each is in
    flight with the row in it, and is committed for the rest."""
    prompts = prompts_for(eng, seed=1)
    plain = [(p, params_for(sampled, i, 12))
             for i, p in enumerate(prompts)]
    free = run(eng, f"sf{sampled}", plain, False, monkeypatch)
    stops = {2: free[2][5][0], 5: free[5][8][0]}
    reqs = [(p, params_for(sampled, i, 12, stop_token_ids=(stops[i],))
             if i in stops else q) for i, (p, q) in enumerate(plain)]
    sync = run(eng, f"ss{sampled}", reqs, False, monkeypatch)
    for i, tok in stops.items():
        assert sync[i][-1] == (None, True, "stop")
        assert len(sync[i]) <= free[i].index((tok, False, None)) + 1
    before = counters(eng)
    assert run(eng, f"sc{sampled}", reqs, True, monkeypatch) == sync
    assert np.subtract(counters(eng), before)[1] > 0


def test_an_abort_with_a_step_in_flight(eng, monkeypatch):
    """A decode row and a prompt mid-prefill are aborted between two
    calls, the step that holds them in flight: they get no event more,
    give back all they held, and no other stream moves."""
    reqs = [(p, params_for(False, i, n))
            for i, (p, (_, n)) in enumerate(zip(prompts_for(eng, 2),
                                                REQUESTS))]
    whole = run(eng, "a", reqs, False, monkeypatch)
    gone = []

    def aborts(eng):
        """Once: a decode row with tokens still to make and a prompt with
        chunks still to go, both rows of the step in flight."""
        plan = eng._flight["plan"] if eng._flight and not gone else None
        rows = [(s, plan.is_decode[i]) for i, s in enumerate(
            plan.seqs if plan else ()) if s is not None and (
                len(s.output) + 2 < eng.scheduler.params[
                    s.request_id].max_tokens
                if plan.is_decode[i] else not plan.is_last_chunk[i])]
        if {d for _, d in rows} == {True, False}:
            gone.extend(next(s.request_id for s, d in rows if d is kind)
                        for kind in (True, False))
            return list(gone)
        return ()

    got = run(eng, "a", reqs, True, monkeypatch, aborts=aborts)
    assert len(gone) == 2
    for i in got:
        if f"a{i}" in gone:
            assert got[i] == whole[i][:len(got[i])]
            assert len(got[i]) < len(whole[i])
        else:
            assert got[i] == whole[i]


def test_an_arrival_with_a_step_in_flight(eng, monkeypatch):
    """Requests that arrive while a step is in flight join the next
    step planned, and nothing is drained for them."""
    reqs = [(p, params_for(True, i, n))
            for i, (p, (_, n)) in enumerate(zip(prompts_for(eng, 3),
                                                REQUESTS))]
    arrive = {i: "flight" for i in range(4, len(reqs))}
    before = counters(eng)
    chained = run(eng, "vc", reqs, True, monkeypatch, arrive=arrive)
    assert np.subtract(counters(eng), before)[2] == 0
    # each arrived before a call of its own, the calls the chained run
    # made them at; the synchronous loop is handed the same calls
    calls = [arrive[i] for i in range(4, len(reqs))]
    assert all(isinstance(c, int) for c in calls) \
        and sorted(set(calls)) == calls
    assert run(eng, "vs", reqs, False, monkeypatch, arrive=arrive) == chained


def test_a_first_token_goes_on_decoding_from_the_device(eng, monkeypatch):
    """A prompt's last chunk rides step N; in N+1, dispatched before N
    is fetched, the same sequence is a decode row that reads N's token
    on the device (`src` names its row of N)."""
    reqs = [(p, params_for(False, i, n))
            for i, (p, (_, n)) in enumerate(zip(prompts_for(eng, 4),
                                                REQUESTS))]
    sync = run(eng, "fs", reqs, False, monkeypatch)
    carried = []

    def spy(plan, reqs_, mixed=False, after=None):
        if after is None:
            return
        last = {id(s) for i, s in enumerate(after["plan"].seqs)
                if s is not None and after["plan"].is_last_chunk[i]}
        carried.extend(
            s.output[-1] for i, s in enumerate(plan.seqs)
            if s is not None and plan.is_decode[i] and id(s) in last)

    assert run(eng, "fc", reqs, True, monkeypatch, spy=spy) == sync
    # every such row had no token on the host when its step was staged
    assert carried and set(carried) == {-1}


def test_an_engine_left_idle_with_a_step_in_flight(eng, monkeypatch):
    """The last mixed step of a burst is in flight when the queue runs
    dry: `has_work()` counts it, and the next call fetches it."""
    prompts = prompts_for(eng, 5)
    eng.add_request(EngineRequest("i0", prompts[0], params_for(False, 0, 9)))
    events, idle = [], 0
    for _ in range(40):
        if not eng.has_work():
            break
        events += eng.step()
        if len(events) == 1 and "i1" not in eng.scheduler.params:
            # 37 tokens: three chunks beside i0's decode row
            eng.add_request(EngineRequest("i1", prompts[2],
                                          params_for(False, 1, 2)))
        if eng._flight is not None and not eng.scheduler.waiting:
            idle += 1
            assert eng.has_work()
    assert idle > 0 and eng._flight is None
    assert sum(e.finished for e in events) == 2
    assert sum(e.token is not None for e in events) == 11


def test_what_the_chain_does_not_carry_stays_synchronous(eng, monkeypatch):
    """A request that wants logprobs or a penalty history keeps every
    step synchronous while the engine holds it, queued or running."""
    prompts = prompts_for(eng, 6)
    for tag, extra in (("kl", dict(logprobs=2)),
                       ("kp", dict(repetition_penalty=1.3))):
        reqs = [(p, params_for(False, i, 4, **(extra if i == 5 else {})))
                for i, p in enumerate(prompts)]
        held = []

        def spy(plan, reqs_, mixed=False, after=None):
            if after is not None:
                held.append(f"{tag}5" in eng.scheduler.params)

        got = run(eng, tag, reqs, True, monkeypatch, spy=spy)
        assert [len(v) for v in got.values()] == [4] * len(reqs)
        # steps chained before and after it, none while it was held
        assert held and not any(held)


def test_the_packed_operands_and_the_fed_tokens_have_one_shape(eng):
    """`src` rides the packed buffer of every step and `prev_tokens` has
    the row ladder's cap whatever the two steps' buckets."""
    from dynamo_tpu.engine.engine import STEP_OPERANDS
    assert STEP_OPERANDS[-1] == "src"
    cap = eng.cfg.max_slots + max(1, eng.cfg.max_prefill_batch)
    assert eng._no_prev.shape == (cap,)
    assert np.all(np.asarray(eng._no_prev) == -1)
    assert eng._no_prev.sharding == eng._replicated


def test_schedule_ahead_never_preempts():
    """Planning behind a step in flight gives up where a running row's
    next token needs a page that only an eviction would free; the
    ordinary planner, with nothing in flight, preempts for it."""
    sch = Scheduler(EngineConfig(**dict(KW, num_pages=4, max_slots=2)))
    sch.add_request(EngineRequest("a", list(range(2, 33)),
                                  SamplingParams(max_tokens=40)))
    for _ in range(2):                       # 31 tokens: two chunks
        sch.commit_prefill_row(sch.schedule(), 0, 5)
    a = sch.running[0]
    assert len(a.pages) == 2 and a.total_len == 32
    sch.add_request(EngineRequest("b", list(range(40, 50)),
                                  SamplingParams(max_tokens=4)))
    # a's next token opens its third page, b's chunk wants one too
    plan = sch.schedule_ahead()
    assert plan is not None and plan.is_decode[:2] == [True, False]
    sch.commit_decode_token(a, 7)
    sch.commit_prefill_row(plan, 1, 9)
    a.output.extend([7] * 15)               # a: its fourth page is next
    a.num_cached += 15
    sch.add_request(EngineRequest("c", list(range(60, 70)),
                                  SamplingParams(max_tokens=2)))
    assert sch.allocator.num_free == 0
    assert sch.schedule_ahead() is None
    assert sch.running[0] is a and a.slot == 0 and len(sch.waiting) == 1
    sch.schedule()                          # b, the youngest, makes room
    assert [s.request_id for s in sch.running if s is not None] == ["a"]
    assert len(a.pages) == 4 and len(sch.waiting) == 2
