"""The two hand-overs between the chains (NativeEngine._chain_step,
_pipeline_step, docs/PERF.md section 3): the first decode window is
dispatched before the last mixed step is fetched, and the first mixed
step before the drained window is.

Bar, as tests/test_mixed_chain.py's: against the synchronous loop ON THE
SAME ENGINE (`_chain_ok` refusing, nothing else touched, so both sides
run the same programs), every request's stream is token for token and
event for event the same, greedy and seeded-sampled, whatever happens
under the step in flight: a row ends by length inside the window (the
host plans that), a row ends on a stop id inside it (the host could
not: the window is cut back, the step behind it committed for the rows
still live), a request is aborted, a request arrives. Over a dense
model, a dropless-MoE model, a model with a window pool and the
recurrent-state model, whose state a step advances in place and nobody
may run twice.

An arrival joins the first step PLANNED after it, which under a
hand-over is one step sooner or later than in the synchronous loop; rows
do not see each other, so the streams are compared per request.
"""
import numpy as np
import pytest

from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.engine import NativeEngine
from dynamo_tpu.engine.scheduler import (
    PENDING_TOKEN, DecodePlan, EngineRequest, SamplingParams, Scheduler,
)
from dynamo_tpu.observability.ledger import (
    LEDGER_STATS, install_jax_listeners,
)
from tests.test_mixed_chain import KW, MODELS, params_for

# (prompt length, max_tokens): three requests up front, the rest arrive
# one at a time while a step is in flight, so the engine goes from mixed
# steps to windows and back for every one of them; budgets that are no
# multiple of the 4-step window end rows inside windows
REQUESTS = ((40, 14), (21, 9), (37, 23), (30, 11), (45, 18), (33, 6),
            (52, 13), (28, 21), (19, 10))
FIRST = 3


@pytest.fixture(scope="module", params=list(MODELS))
def eng(request):
    cfg, kw = MODELS[request.param]
    eng = NativeEngine(cfg, EngineConfig(**kw), seed=0)
    # the first program an engine runs is handed the cache as its init
    # left it (tests/test_mixed_chain.py): serve one prompt twice
    for tag in ("w0", "w1"):
        eng.generate([3, 4, 5], SamplingParams(max_tokens=6), tag)
    return eng


def prompts_for(eng, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(2, eng.model_cfg.vocab_size, n).tolist()
            for n, _ in REQUESTS]


def requests_for(eng, sampled, seed=0, extra=None):
    extra = extra or {}
    return [(p, params_for(sampled, i, n, **extra.get(i, {})))
            for i, (p, (_, n)) in enumerate(zip(prompts_for(eng, seed),
                                                REQUESTS))]


def in_flight(eng) -> str:
    return "window" if eng._pipeline is not None \
        else "mixed" if eng._flight is not None else ""


def room(eng) -> bool:
    """A decode slot that no queued prompt will take: as a closed loop's
    next request finds one, the one its last request left."""
    sch = eng.scheduler
    return sum(s is None for s in sch.running) > len(sch.waiting)


def run(eng, tag, requests, chained, monkeypatch, arrive=None, aborts=None,
        watch=None):
    """Serve `requests` [(prompt, params)]: request i is added before
    call `arrive[i]` (default 0; "window" / "mixed": alone, before the
    first call that finds a step of that kind in flight and a slot to
    spare, and `arrive[i]` then holds that call); `aborts(eng)` names, before each call, the
    requests to abort now; `watch(eng)` runs after every call. Returns
    {i: [(token, finished, finish_reason)]}, and the engine drained."""
    arrive = arrive if arrive is not None else {}
    alloc = eng.scheduler.allocator
    for pid in [alloc.allocate() for _ in range(alloc.num_pages)]:
        alloc.free(pid)
    with monkeypatch.context() as m:
        if not chained:
            m.setattr(eng, "_chain_ok", lambda seqs=(): False)
        got = {i: [] for i in range(len(requests))}
        open_ = set(got)
        ids = {f"{tag}{i}": i for i in got}
        for call in range(900):
            for i, (prompt, p) in enumerate(requests):
                if arrive.get(i, 0) == in_flight(eng) and room(eng) \
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
                assert ev.token != PENDING_TOKEN
                got[i].append((ev.token, ev.finished, ev.finish_reason))
                if ev.finished:
                    open_.discard(i)
            if watch is not None:
                watch(eng)
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


def spy_on(m, eng, name, note):
    """`note(result, *args)` after every call of the engine's `name`."""
    fn = getattr(eng, name)
    m.setattr(eng, name, lambda *a, **k: (
        lambda out: (note(out, *a, **k), out)[1])(fn(*a, **k)))


def both(eng, tag, reqs, monkeypatch, arrive, **kw):
    """The chained run, then the synchronous one handed the calls the
    chained run's arrivals fell on."""
    chained = run(eng, tag + "c", reqs, True, monkeypatch, arrive=arrive,
                  **kw)
    calls = [arrive[i] for i in sorted(arrive)]
    assert all(isinstance(c, int) for c in calls)
    sync = run(eng, tag + "s", reqs, False, monkeypatch, arrive=arrive)
    return chained, sync


def counters(eng):
    return np.array((eng.handovers, eng.handovers_chained,
                     eng.mixed_steps_replanned, eng.window_steps_discarded))


def alternate(kinds):
    return {i: kinds[(i - FIRST) % len(kinds)]
            for i in range(FIRST, len(REQUESTS))}


@pytest.mark.parametrize("sampled", [False, True],
                         ids=["greedy", "sampled"])
def test_a_window_behind_the_last_mixed_step_and_a_mixed_step_behind_a_window(
        eng, monkeypatch, sampled):
    """Every ending is by `max_tokens`, several of them inside a window
    with the mixed step that admits an arrival already behind it: the
    host plans each, so nothing is planned twice, nothing is thrown
    away, and no program, bucket or variant joins the set."""
    reqs = requests_for(eng, sampled)
    arrive = alternate(("window", "mixed"))
    # once each way first, so that every program the traffic needs has
    # been met (the synchronous loop is handed the calls the chained
    # run's arrivals fell on: it never has a mixed step in flight)
    install_jax_listeners()
    built = []
    with monkeypatch.context() as m:
        # the two helpers were compiled when the engine was built: no
        # call of either has XLA build anything, the first ones included
        for name in ("_carry_fn", "_prev_fn"):
            def spied(*a, fn=getattr(eng, name)):
                n = LEDGER_STATS.jax_compiles
                out = fn(*a)
                built.append(LEDGER_STATS.jax_compiles - n)
                return out
            m.setattr(eng, name, spied)
        first = both(eng, f"p{sampled}", reqs, monkeypatch, arrive)
    assert built and not any(built)
    seen = set(eng._seen_programs)
    fns = (sorted(eng._step_fns), sorted(eng._decode_fns))
    compiles = LEDGER_STATS.jax_compiles
    before = counters(eng)
    kinds = []

    def watch(eng):
        kinds.append(in_flight(eng))

    inside = []
    with monkeypatch.context() as m:
        # rows whose budget ended before an opened window's last step
        spy_on(m, eng, "_open_window", lambda after, pend: inside.extend(
            row[4] for row in after["rows"]
            if row[5] and row[4] < pend["staged"]["nw"]))
        chained = run(eng, f"lc{sampled}", reqs, True, monkeypatch,
                      arrive=arrive, watch=watch)
    assert inside
    moved = counters(eng) - before
    sync = run(eng, f"ls{sampled}", reqs, False, monkeypatch, arrive=arrive)
    assert first == (chained, sync) and chained == sync
    assert [len(v) for v in sync.values()] == [n for _, n in REQUESTS]
    assert all(v[-1][1:] == (True, "length") for v in sync.values())
    # a call found a step of one kind in flight and left one of the other
    changes = {(a, b) for a, b in zip(kinds, kinds[1:]) if a and b}
    assert {("mixed", "window"), ("window", "mixed")} <= changes
    # every arrival is a round trip between the kinds; those not made
    # ahead are the windows that may not enter the pipeline (_pipeline_ok:
    # a follow-up would outgrow the 16-token pages' base). Nothing is
    # thrown away, and the synchronous loop makes none ahead
    assert moved[0] >= len(REQUESTS) - FIRST
    assert moved[1] >= 3 and moved[3] == 0
    assert (counters(eng) - before)[1] == moved[1]
    assert (sorted(eng._step_fns), sorted(eng._decode_fns)) == fns
    assert set(eng._seen_programs) == seen
    assert LEDGER_STATS.jax_compiles == compiles


@pytest.mark.parametrize("sampled", [False, True],
                         ids=["greedy", "sampled"])
def test_a_stop_id_inside_a_window_with_a_step_behind_it(eng, monkeypatch,
                                                         sampled):
    """Rows end on a token they sample inside a window: the window is
    cut back to that token, and the step dispatched behind it, which
    holds the row, is committed for the rest."""
    plain = requests_for(eng, sampled, seed=1)
    arrive = alternate(("window",))
    # the stops are tokens that the run without them placed before the
    # last step of a window with a mixed step behind it (the first such
    # token of a request, if its stream has it nowhere sooner)
    placed = []
    with monkeypatch.context() as m:
        spy_on(m, eng, "_place_token", lambda ev, row, step, tok:
               placed.append((int(row[1].request_id[3:]), row[3] + step,
                              tok)) if step < row[4] - 1
               and in_flight(eng) == "mixed" else None)
        free = run(eng, f"sf{int(sampled)}", plain, True, monkeypatch,
                   arrive=arrive)
    stops = {}
    for i, at, tok in placed:
        if i not in stops and [t for t, _, _ in free[i]].index(tok) == at:
            stops[i] = tok
    assert stops
    reqs = requests_for(eng, sampled, seed=1, extra={
        i: dict(stop_token_ids=(tok,)) for i, tok in stops.items()})
    before = counters(eng)
    cut = []
    with monkeypatch.context() as m:
        # a stop before its row's last token of an opened window, and
        # what was in flight behind the window then
        spy_on(m, eng, "_place_token", lambda ev, row, step, tok: cut.append(
            in_flight(eng)) if ev.finished and step < row[4] - 1 else None)
        chained, sync = both(eng, f"ss{sampled}", reqs, monkeypatch, arrive)
    assert chained == sync and "mixed" in cut
    for i in stops:
        assert sync[i][-1] == (None, True, "stop")
        assert len(sync[i]) < len(free[i])
    assert (counters(eng) - before)[1] > 0


def test_an_abort_with_either_step_in_flight(eng, monkeypatch):
    """A decode row is aborted between two calls, once with a window in
    flight and once with a mixed step: it gets no event more, gives back
    all it held, and no other stream moves."""
    reqs = requests_for(eng, False, seed=2)
    arrive = alternate(("window", "mixed"))
    _, whole = both(eng, "aw", reqs, monkeypatch, arrive)
    gone = {}

    def aborts(eng):
        """Once a kind: a row with tokens still to make."""
        kind = in_flight(eng)
        if not kind or kind in gone or len(gone) == 2:
            return ()
        plan = (eng._pipeline or eng._flight)["plan"]
        for i, s in enumerate(plan.seqs):
            if s is None or s.slot < 0 or s.request_id in gone.values() \
                    or (kind == "mixed" and not plan.is_decode[i]):
                continue
            if len(s.output) + 6 < eng.scheduler.params[
                    s.request_id].max_tokens:
                gone[kind] = s.request_id
                return [s.request_id]
        return ()

    got = run(eng, "a", reqs, True, monkeypatch, arrive=arrive,
              aborts=aborts)
    assert set(gone) == {"window", "mixed"}
    for i in got:
        if f"a{i}" in gone.values():
            assert got[i] == whole[i][:len(got[i])]
            assert len(got[i]) < len(whole[i])
        else:
            assert got[i] == whole[i]


def test_what_the_chain_does_not_carry_keeps_the_hand_over_synchronous(
        eng, monkeypatch):
    """A request that wants logprobs keeps every step synchronous while
    the engine holds it, queued or running: steps are made ahead until
    it arrives, its own admission is a round trip between the kinds
    with nothing made ahead."""
    last = len(REQUESTS) - 1
    reqs = requests_for(eng, False, seed=3, extra={last: dict(logprobs=2)})
    at_arrival = []

    def watch(eng):
        if f"k{last}" in eng.scheduler.params:
            if not at_arrival:
                at_arrival.append(counters(eng))
            step = eng._pipeline or eng._flight
            assert step is None or not step["ahead"]

    before = counters(eng)
    got = run(eng, "k", reqs, True, monkeypatch,
              arrive=alternate(("window",)), watch=watch)
    assert [len(v) for v in got.values()] == [n for _, n in REQUESTS]
    assert (at_arrival[0] - before)[1] > 0
    held = counters(eng) - at_arrival[0]
    assert held[0] >= 2 and held[1] == 0


def test_a_first_token_is_read_from_the_step_in_front_on_the_device(
        eng, monkeypatch):
    """The window dispatched behind a chain's last mixed step is staged
    with every row's last token unknown to the host, and the mixed step
    behind a window with its decode rows' likewise."""
    reqs = requests_for(eng, False, seed=4)
    pending = {"window": [], "mixed": []}
    stage_w, stage_s = eng._stage_window, eng._stage_step

    def window(plan, *a, after=None, **k):
        if after is not None:
            pending["window"].extend(
                s.output[-1] for s in plan.seqs if s is not None)
        return stage_w(plan, *a, after=after, **k)

    def step(plan, reqs_, mixed=False, after=None):
        if after is not None and "at" in after:   # a window's open commit
            pending["mixed"].extend(
                s.output[-1] for i, s in enumerate(plan.seqs)
                if s is not None and plan.is_decode[i])
        return stage_s(plan, reqs_, mixed=mixed, after=after)

    with monkeypatch.context() as m:
        m.setattr(eng, "_stage_window", window)
        m.setattr(eng, "_stage_step", step)
        chained, sync = both(eng, "f", reqs, monkeypatch,
                             alternate(("window", "mixed")))
    assert chained == sync
    for kind, toks in pending.items():
        assert toks and set(toks) == {PENDING_TOKEN}, kind


def test_schedule_decode_ahead_never_preempts():
    """Planning a window behind a step in flight gives up where a row's
    window needs a page that only an eviction would free, and where a
    prompt waits; the ordinary planner, with nothing in flight,
    preempts for it."""
    sch = Scheduler(EngineConfig(**dict(KW, num_pages=6, max_slots=2)))
    for rid, lo in (("a", 2), ("b", 40)):
        sch.add_request(EngineRequest(rid, list(range(lo, lo + 30)),
                                      SamplingParams(max_tokens=40)))
    while sch.waiting:
        plan = sch.schedule()
        for i, seq in enumerate(plan.seqs):
            if seq is not None:
                sch.commit_prefill_row(plan, i, 5)
    a, b = sch.running
    assert a.total_len == b.total_len == 31 and sch.allocator.num_free == 2
    # the window's four tokens open a third page each: there are two
    plan = sch.schedule_decode_ahead()
    assert isinstance(plan, DecodePlan) and plan.n_window == 4
    assert sch.allocator.num_free == 0
    for seq in (a, b):
        for _ in range(14):
            sch.commit_decode_token(seq, PENDING_TOKEN)
    # the next window's open a fourth, which only an eviction would free
    assert a.total_len == 45 and sch.schedule_decode_ahead() is None
    assert sch.running == [a, b] and not sch.waiting
    sch.add_request(EngineRequest("c", [2, 3, 4],
                                  SamplingParams(max_tokens=2)))
    assert sch.schedule_decode_ahead() is None     # a prompt waits
    del sch.waiting[0]
    assert isinstance(sch._schedule_decode(), DecodePlan)
    assert sum(s is not None for s in sch.running) == 1 \
        and len(sch.waiting) == 1


def test_a_page_of_pending_tokens_is_sealed_when_they_are_known():
    """While a window's commit is open its tokens stand as
    PENDING_TOKEN: a page they fill is not sealed by that content."""
    sch = Scheduler(EngineConfig(**dict(KW, num_pages=8, max_slots=2)))
    sch.add_request(EngineRequest("a", list(range(2, 16)),
                                  SamplingParams(max_tokens=40)))
    sch.commit_prefill_row(sch.schedule(), 0, 5)
    a = sch.running[0]
    assert sch._ensure_pages(a, 32)
    for _ in range(4):
        sch.commit_decode_token(a, PENDING_TOKEN)
    assert a.num_cached == 18 and a.page_hashes == []
    a.output[1:] = [6, 7, 8, 9]
    sch._seal_full_pages(a)
    assert len(a.page_hashes) == 1
    other = Scheduler(EngineConfig(**dict(KW, num_pages=8, max_slots=2)))
    other.add_request(EngineRequest("a", list(range(2, 16)),
                                    SamplingParams(max_tokens=40)))
    other.commit_prefill_row(other.schedule(), 0, 5)
    for tok in (6, 7, 8, 9):
        other.commit_decode_token(other.running[0], tok)
    assert other.running[0].page_hashes == a.page_hashes


def test_the_helpers_outputs_have_a_windows_own_sharding(eng):
    """What a window dispatched behind a mixed step is handed as its
    carry, and a mixed step behind a window as the tokens before it,
    are put as a window's own carry and a step's own tokens are."""
    s, cap = eng.cfg.max_slots, eng._no_prev.shape[0]
    carry = np.zeros((s, 4), np.int32)
    carry[:, 0], carry[:, 1], carry[:, 2] = 9, np.arange(s), 3
    carry[:, 3] = -1
    carry[1, 3] = 2
    prev = np.full((cap,), -1, np.int32)
    prev[2] = 77
    import jax
    out = eng._carry_fn(jax.device_put(prev, eng._replicated),
                        jax.device_put(carry, eng._replicated))
    assert out.sharding == eng._replicated and out.shape == (s, 3)
    want = carry[:, :3].copy()
    want[1, 0] = 77
    assert np.array_equal(np.asarray(out), want)
    laid = eng._prev_fn(out)
    assert laid.sharding == eng._replicated and laid.shape == (cap,)
    assert np.array_equal(np.asarray(laid)[:s], want[:, 0]) \
        and np.all(np.asarray(laid)[s:] == -1)
