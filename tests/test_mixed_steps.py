"""Fused prefill+decode steps (MixedPlan, docs/PERF.md).

Exactness bar: with the mixed-step scheduler ON (the default), greedy
AND seeded-sampled streams must be TOKEN-IDENTICAL to the legacy
alternating scheduler, with requests admitted mid-stream, at every
pipeline depth. Anti-stall bar: while a long prompt prefills, a running
stream's next token is never delayed by more than one mixed step, and
decode_stall_steps stays 0 (the alternating baseline pays > 0).

Engines are module-scoped and reused across tests (engine rebuilds
recompile every jitted program — the tier-1 budget is tight), and the
alternating ORACLE is the same engine with its runtime-flippable
`scheduler.mixed_token_budget` set to 0, so no third engine build is
paid; scheduler-level tests construct bare Schedulers and cost no
compiles at all.
"""
import numpy as np
import pytest

from dynamo_tpu.engine.config import EngineConfig, ModelConfig
from dynamo_tpu.engine.engine import NativeEngine
from dynamo_tpu.engine.scheduler import (
    DecodePlan, EngineRequest, MixedPlan, PrefillPlan, SamplingParams,
    Scheduler, next_bucket,
)

CFG = ModelConfig(dtype="float32", max_model_len=512)

ENGINE_KW = dict(
    page_size=16, num_pages=64, max_slots=2, max_prefill_chunk=32,
    prefill_buckets=(8, 16, 32), max_model_len=512, decode_steps=4)


def make_engine(depth, budget, **kw):
    defaults = dict(ENGINE_KW, pipeline_depth=depth,
                    mixed_token_budget=budget)
    defaults.update(kw)
    return NativeEngine(CFG, EngineConfig(**defaults), seed=0)


@pytest.fixture(scope="module")
def eng_mixed():
    return make_engine(1, 512)


@pytest.fixture(scope="module")
def eng_mixed_pipe():
    return make_engine(2, 512)


def drive_alternating(eng, tag, params, prompts):
    """Reference drive: legacy alternating scheduler on the SAME engine
    (budget flipped to 0 for the drive, restored after)."""
    budget = eng.scheduler.mixed_token_budget
    eng.scheduler.mixed_token_budget = 0
    try:
        return drive_with_admissions(eng, tag, params, prompts)
    finally:
        eng.scheduler.mixed_token_budget = budget


def drive_with_admissions(eng, tag, params, prompts):
    """Run 3 requests with B admitted after A streams 2 tokens and C
    after B's first token — admissions land mid-decode, so the mixed
    engines take fused steps (and the pipelined engine must drain +
    re-prime around them)."""
    got = {f"{tag}A": []}
    eng.add_request(EngineRequest(f"{tag}A", prompts[0], params[0]))
    done, added_b, added_c = set(), False, False
    steps = 0
    while len(done) < 3 and steps < 400:
        steps += 1
        for ev in eng.step():
            if ev.token is not None:
                got[ev.request_id].append(ev.token)
            if ev.finished:
                done.add(ev.request_id)
        if not added_b and len(got[f"{tag}A"]) >= 2:
            got[f"{tag}B"] = []
            eng.add_request(EngineRequest(f"{tag}B", prompts[1], params[1]))
            added_b = True
        if added_b and not added_c and got[f"{tag}B"]:
            got[f"{tag}C"] = []
            eng.add_request(EngineRequest(f"{tag}C", prompts[2], params[2]))
            added_c = True
    assert len(done) == 3, (sorted(done), steps)
    return [got[f"{tag}{x}"] for x in "ABC"]


# B is multi-chunk (68 > max_prefill_chunk=32: 3 chunks) so admissions
# land mid-decode across several fused steps; kept short — every extra
# chunk is tier-1 budget
PROMPTS = [list(range(3, 19)), list(range(40, 108)), list(range(200, 210))]


def test_mixed_token_identity_every_depth_greedy(eng_mixed,
                                                 eng_mixed_pipe):
    """Pipeline x admission interaction: requests admitted mid-stream at
    depth 1 and depth 2 with mixed steps on produce streams token-equal
    to the alternating synchronous loop."""
    greedy = [
        SamplingParams(max_tokens=14, temperature=0.0, ignore_eos=True),
        SamplingParams(max_tokens=8, temperature=0.0, ignore_eos=True),
        SamplingParams(max_tokens=6, temperature=0.0, ignore_eos=True)]
    m0 = eng_mixed.mixed_steps
    ref = drive_alternating(eng_mixed, "idgr", greedy, PROMPTS)
    mix = drive_with_admissions(eng_mixed, "idgm", greedy, PROMPTS)
    pipe = drive_with_admissions(eng_mixed_pipe, "idgp", greedy, PROMPTS)
    assert mix == ref
    assert pipe == ref
    assert eng_mixed.mixed_steps > m0  # fused steps actually ran


def test_mixed_token_identity_seeded_sampled(eng_mixed_pipe):
    """Seeded-sampled streams (temperature/top-k/top-p) under mid-stream
    admissions: mixed + pipelined must equal the alternating reference
    token-for-token — same per-request (seed, counter) keys through the
    shared sample_logits tail. One engine carries both drives (the
    sampled program variants are the expensive compiles)."""
    sampled = [
        SamplingParams(max_tokens=10, temperature=0.9, top_k=12, seed=7,
                       ignore_eos=True),
        SamplingParams(max_tokens=8, temperature=0.7, top_p=0.8, seed=3,
                       ignore_eos=True),
        SamplingParams(max_tokens=6, temperature=0.8, seed=11,
                       ignore_eos=True)]
    ref = drive_alternating(eng_mixed_pipe, "idsr", sampled, PROMPTS)
    mix = drive_with_admissions(eng_mixed_pipe, "idsm", sampled, PROMPTS)
    assert mix == ref


def test_long_prompt_never_stalls_running_stream(eng_mixed):
    """Starvation bound: while a multi-chunk prompt prefills, the
    already-running stream emits a token on EVERY engine step — a long
    arrival delays a running stream's next token by at most one mixed
    step (the alternating scheduler stalled it for whole prefill
    steps)."""
    eng = eng_mixed
    p_run = SamplingParams(max_tokens=16, temperature=0.0, ignore_eos=True)
    p_new = SamplingParams(max_tokens=4, temperature=0.0, ignore_eos=True)
    eng.add_request(EngineRequest("starveA", list(range(5, 21)), p_run))
    tokens_a = 0
    while tokens_a < 2:  # A is decoding
        tokens_a += sum(1 for ev in eng.step()
                        if ev.token is not None
                        and ev.request_id == "starveA")
    stall0 = eng.decode_stall_steps
    eng.add_request(EngineRequest("starveB", list(range(50, 118)), p_new))
    # drive until B finishes; every step that did work must include an
    # "starveA" token while A is still live
    a_done = b_done = False
    while not (a_done and b_done):
        evs = eng.step()
        a_toks = sum(1 for ev in evs if ev.token is not None
                     and ev.request_id == "starveA")
        for ev in evs:
            if ev.finished and ev.request_id == "starveA":
                a_done = True
            if ev.finished and ev.request_id == "starveB":
                b_done = True
        if evs and not a_done:
            assert a_toks >= 1, "running stream skipped a step"
    assert eng.decode_stall_steps == stall0  # zero stall steps throughout


def test_alternating_baseline_counts_stall_steps(eng_mixed):
    """The stall counter attributes the interference the mixed scheduler
    removes: under the legacy policy (budget flipped to 0), prefill
    chunks that run while a decode is live each count one
    decode_stall_step."""
    eng = eng_mixed
    eng.scheduler.mixed_token_budget = 0
    try:
        p_run = SamplingParams(max_tokens=16, temperature=0.0,
                               ignore_eos=True)
        p_new = SamplingParams(max_tokens=4, temperature=0.0,
                               ignore_eos=True)
        eng.add_request(EngineRequest("stallA", list(range(5, 21)), p_run))
        got = 0
        while got < 2:
            got += sum(1 for ev in eng.step() if ev.token is not None)
        stall0 = eng.decode_stall_steps
        eng.add_request(EngineRequest("stallB", list(range(50, 118)),
                                      p_new))
        while eng.has_work():
            eng.step()
        assert eng.decode_stall_steps > stall0
    finally:
        eng.scheduler.mixed_token_budget = eng.cfg.mixed_token_budget


def test_metrics_carry_mixed_and_stall_counters(eng_mixed):
    m = eng_mixed.metrics()
    assert m.mixed_steps == eng_mixed.mixed_steps > 0
    assert m.decode_stall_steps == eng_mixed.decode_stall_steps
    # wire path keeps them (the /metrics exporter's source)
    import dataclasses

    from dynamo_tpu.kv_router.scoring import WorkerMetrics
    w = WorkerMetrics.from_dict(dataclasses.asdict(m))
    assert w.mixed_steps == m.mixed_steps
    assert w.decode_stall_steps == m.decode_stall_steps


# -- scheduler-level (no jit, no compiles) ------------------------------------


def sched(**kw):
    defaults = dict(page_size=8, num_pages=128, max_slots=2,
                    max_prefill_chunk=8, prefill_buckets=(8,),
                    max_model_len=512)
    defaults.update(kw)
    return Scheduler(EngineConfig(**defaults))


def commit_any(s, plan):
    """Drive a scheduler plan to completion host-side (no device)."""
    if isinstance(plan, MixedPlan):
        for i, seq in enumerate(plan.seqs):
            if seq is not None and plan.is_decode[i]:
                s.commit_decode_token(seq, 1)
        for i in reversed(range(len(plan.seqs))):
            seq = plan.seqs[i]
            if seq is None or plan.is_decode[i]:
                continue
            s.commit_prefill_row(plan, i,
                                 9 if plan.is_last_chunk[i] else None)
    elif isinstance(plan, PrefillPlan):
        for i in reversed(range(len(plan.seqs))):
            s.commit_prefill_row(plan, i,
                                 9 if plan.is_last_chunk[i] else None)
    else:
        s.commit_decode(plan, np.zeros(s.cfg.max_slots, np.int64))


def test_mixed_plan_layout_and_budget():
    """Decode rows lead the plan as one-token causal rows; every row is
    charged the full token bucket: Tb * (rows) <= mixed_token_budget,
    and all leading dims are bucketed."""
    s = sched(mixed_token_budget=32)
    s.add_request(EngineRequest("a", list(range(2, 10)),
                                SamplingParams(max_tokens=50,
                                               ignore_eos=True)))
    s.commit_prefill(s.schedule(), 7)  # a takes a decode slot
    s.add_request(EngineRequest("b", list(range(100, 180)),
                                SamplingParams(max_tokens=4,
                                               ignore_eos=True)))
    plan = s.schedule()
    assert isinstance(plan, MixedPlan)
    tb = plan.tokens.shape[1]
    assert tb in s.prefill_buckets
    n_rows = sum(1 for q in plan.seqs if q is not None)
    assert tb * n_rows <= 32
    # decode row: a's last token at column 0, kv_lens = position + 1
    i = plan.is_decode.index(True)
    a = plan.seqs[i]
    assert a.request_id == "a"
    assert plan.tokens[i, 0] == a.output[-1]
    assert plan.kv_lens[i] == a.total_len
    assert plan.last_idx[i] == 0
    assert plan.write_idx[i, 0] >= 0 and np.all(plan.write_idx[i, 1:] < 0)
    # prefill row rides the same step
    j = next(k for k, q in enumerate(plan.seqs)
             if q is not None and not plan.is_decode[k])
    assert plan.seqs[j].request_id == "b"
    # batch dim sits on the fixed pow2 ladder
    assert plan.tokens.shape[0] & (plan.tokens.shape[0] - 1) == 0


def test_streak_retired_decode_rides_every_step():
    """With mixed steps on, a multi-chunk prompt admitted against a
    running decode yields ONLY MixedPlans until its prefill completes —
    no pure-prefill stall steps, no streak bookkeeping."""
    s = sched(mixed_token_budget=32)
    s.add_request(EngineRequest("a", list(range(2, 10)),
                                SamplingParams(max_tokens=60,
                                               ignore_eos=True)))
    s.commit_prefill(s.schedule(), 7)
    s.add_request(EngineRequest("b", list(range(100, 180)),
                                SamplingParams(max_tokens=4,
                                               ignore_eos=True)))
    kinds = ""
    for _ in range(14):
        plan = s.schedule()
        if plan is None:
            break
        kinds += ("m" if isinstance(plan, MixedPlan) else
                  "p" if isinstance(plan, PrefillPlan) else "d")
        commit_any(s, plan)
    # b is 80 tokens -> 10 chunks of 8, every one fused with a's decode
    assert kinds.startswith("m" * 10), kinds
    assert "p" not in kinds, kinds


def test_prefill_skip_ahead_unblocks_later_request():
    """Head-of-line fix: a head whose FINAL chunk needs a decode slot
    (none free) no longer blocks a later multi-chunk request that could
    run now; with skip-ahead disabled the old blocking behavior is
    preserved."""
    def setup(skip):
        s = sched(max_slots=1, prefill_skip_ahead=skip,
                  mixed_token_budget=0)
        # fill the only slot
        s.add_request(EngineRequest("run", list(range(2, 10)),
                                    SamplingParams(max_tokens=60,
                                                   ignore_eos=True)))
        s.commit_prefill(s.schedule(), 7)
        # head: single-chunk prompt whose final chunk needs a slot -> blocked
        s.add_request(EngineRequest("head", list(range(20, 28)),
                                    SamplingParams(max_tokens=4)))
        # later: an 80-token prompt with chunks to burn before needing one
        s.add_request(EngineRequest("later", list(range(100, 180)),
                                    SamplingParams(max_tokens=4)))
        return s

    s = setup(skip=4)
    plan = s._schedule_prefill()
    assert plan is not None
    assert plan.seq.request_id == "later"
    # queue order preserved: head still first in line
    assert s.waiting[0].request_id == "head"

    s = setup(skip=0)
    assert s._schedule_prefill() is None  # old head-of-line behavior


def test_skip_ahead_memory_dead_end_still_raises():
    """Skip-ahead must not swallow the true dead end: a prompt that can
    never fit raises MemoryError when nothing can free pages."""
    s = sched(num_pages=4, max_prefill_chunk=8, prefill_skip_ahead=4)
    # 40-token prompt, 4 pages x 8 = 32 token slots: the 5th chunk can
    # never get a page
    s.add_request(EngineRequest("big", list(range(2, 42)),
                                SamplingParams(max_tokens=4)))
    with pytest.raises(MemoryError):
        for _ in range(8):
            plan = s.schedule()
            assert plan is not None
            commit_any(s, plan)


def test_mixed_page_width_uses_admission_bucket():
    """A mixed plan's page-table width covers each decode row's
    ADMISSION-TIME allocation (prompt + max_tokens), so the width never
    moves mid-request and mixed steps reuse compiled programs across a
    request's whole life (dynalint R10's invariant)."""
    s = sched(mixed_token_budget=32)
    s.add_request(EngineRequest("a", list(range(2, 10)),
                                SamplingParams(max_tokens=100,
                                               ignore_eos=True)))
    s.commit_prefill(s.schedule(), 7)
    s.add_request(EngineRequest("b", list(range(100, 140)),
                                SamplingParams(max_tokens=4,
                                               ignore_eos=True)))
    plan = s.schedule()
    assert isinstance(plan, MixedPlan)
    ps = s.cfg.page_size
    need = -(-(8 + 100) // ps)  # a's admission-time page need
    assert plan.page_table.shape[1] >= next_bucket(need, s.page_buckets)


# ---- the KV pool through the device programs (PERF.md section 6, PR 26) ----

_POOL_PAGES, _POOL_PS = 23, 8      # 23 pages: a size no other axis has


def _program_jaxprs(model_cfg):
    """make_jaxpr of `_engine_step` and `_engine_decode_window` at a tiny
    size, with the argument lists the engine dispatches them with."""
    import functools

    import jax
    import jax.numpy as jnp

    from dynamo_tpu.engine import engine as eng
    from dynamo_tpu.models import llama

    rows, chunk, pb, nw = 4, 16, 3, 4
    params = jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), model_cfg))
    cache = jax.eval_shape(
        lambda: llama.init_cache(model_cfg, _POOL_PAGES, _POOL_PS))

    def arr(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype)

    vec, fvec = arr((rows,)), arr((rows,), jnp.float32)
    step = functools.partial(eng._engine_step, model_cfg, (), None, None,
                             False, False, False, None)
    window = functools.partial(eng._engine_decode_window, model_cfg, (), None,
                               nw, _POOL_PS, False, False, False, False)
    return {
        "engine_step": jax.make_jaxpr(step)(
            params, cache, arr((rows, chunk)), arr((rows, chunk)),
            arr((rows, pb)), vec, arr((rows, chunk)), vec, fvec, vec, fvec,
            vec, vec, vec),
        "engine_decode_window": jax.make_jaxpr(window)(
            params, cache, vec, vec, arr((rows, pb)), arr((rows, 2)), vec,
            fvec, vec, fvec, vec, vec, vec, arr((rows,), jnp.bool_),
            arr((rows, 0))),
    }, {leaf.shape for leaf in jax.tree_util.tree_leaves(cache)}


def _walk(jaxpr, path=()):
    """(path of enclosing primitives, equation) of a jaxpr and of every
    jaxpr nested in its equations (scan / while / cond bodies, calls)."""
    import jax
    for eqn in jaxpr.eqns:
        yield path, eqn
        for val in eqn.params.values():
            for sub in (val if isinstance(val, (tuple, list)) else (val,)):
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, jax.extend.core.Jaxpr):
                    yield from _walk(sub, path + (eqn.primitive.name,))


@pytest.mark.parametrize("kv_quant", ["", "int8"])
def test_no_program_slices_or_copies_a_layers_pool(kv_quant):
    """Backend-independent shape of PR 26's mechanism: in `_engine_step`
    and `_engine_decode_window` the pool travels only as WHOLE stacked
    leaves. (1) no `scan` takes or gives a value with the page axis as
    xs / ys: per-layer slices of the pool through a scan are what made
    XLA copy 134 MB a layer six times over; the carry may hold the whole
    leaf. (2) wherever the page axis appears, in any equation of any
    nested body, the value has a leaf's full shape: no `[Hkv, P, ps, hd]`
    slice, no flattened `P*ps` view."""
    import dataclasses
    jaxprs, leaf_shapes = _program_jaxprs(
        dataclasses.replace(CFG, kv_quant=kv_quant))

    def has_pages(var):
        shape = tuple(getattr(var.aval, "shape", ()))
        return (_POOL_PAGES in shape
                or any(d % (_POOL_PAGES * _POOL_PS) == 0 and d
                       for d in shape))

    for name, closed in jaxprs.items():
        scans = 0
        for path, eqn in _walk(closed.jaxpr):
            for var in list(eqn.invars) + list(eqn.outvars):
                if has_pages(var):
                    assert tuple(var.aval.shape) in leaf_shapes, (
                        f"{name}: {'/'.join(path + (eqn.primitive.name,))} "
                        f"handles {var.aval.str_short()}, not a whole leaf "
                        f"of the pool {sorted(leaf_shapes)}")
            if eqn.primitive.name != "scan":
                continue
            scans += 1
            skip = eqn.params["num_consts"] + eqn.params["num_carry"]
            sliced = [v.aval.str_short() for v in
                      list(eqn.invars[skip:])
                      + list(eqn.outvars[eqn.params["num_carry"]:])
                      if has_pages(v)]
            assert not sliced, (
                f"{name}: a scan under {'/'.join(path) or 'the program'} "
                f"moves the pool as xs / ys: {sliced}")
        assert scans, f"{name}: no scan found; the walk is broken"
