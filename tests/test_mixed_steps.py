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

    # [16, 16]: a grid larger than its 128 flat rows, so the step holds
    # the compact branch and the grid branch of every layer half
    rows, chunk, pb, nw = 16, 16, 3, 4
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
                               nw, _POOL_PS, False, False, False)
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
    slice, no flattened `P*ps` view. (3) PR 32: the step's `cond`s (a
    layer's token-wise halves over the real tokens or over the grid) sit
    INSIDE the layer scan and neither takes nor gives a value with the
    page axis: the pool is written and read between them, outside both
    branches, so neither branch can copy, slice or re-lay it out."""
    import dataclasses
    jaxprs, leaf_shapes = _program_jaxprs(
        dataclasses.replace(CFG, kv_quant=kv_quant))

    def has_pages(var):
        shape = tuple(getattr(var.aval, "shape", ()))
        return (_POOL_PAGES in shape
                or any(d % (_POOL_PAGES * _POOL_PS) == 0 and d
                       for d in shape))

    for name, closed in jaxprs.items():
        scans = conds = 0
        for path, eqn in _walk(closed.jaxpr):
            if eqn.primitive.name == "cond" and "scan" in path:
                conds += 1
                assert len(eqn.params["branches"]) == 2
                moved = [v.aval.str_short() for v in
                         list(eqn.invars) + list(eqn.outvars)
                         if has_pages(v)]
                assert not moved, (
                    f"{name}: a cond under {'/'.join(path)} takes or gives "
                    f"the pool: {moved}")
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
        # front and back of the one layer body; the decode window has none
        assert conds == (2 if name == "engine_step" else 0), (name, conds)


# ---- the token-wise layers over a step's real tokens (PERF.md section 6, PR 32) ----

_ROWS, _CHUNK, _WIDTH = 16, 16, 128     # a [16, 16] grid; 128 flat rows
_TABLE, _PS = 4, 8

_COMPACT_MODELS = {
    "dense-gqa": CFG,
    "int8-kv-pool": ModelConfig(dtype="float32", max_model_len=512,
                                kv_quant="int8"),
    # 16 experts of 4 a token: the dropless sorted dispatch
    "dropless-experts": ModelConfig(
        dtype="float32", max_model_len=512, num_layers=2, num_experts=16,
        num_experts_per_tok=4, norm_topk_prob=False),
    # 8 experts of 2: the capacity form, 8 slots an expert and row here,
    # which a 16-token chunk overflows
    "capacity-experts": ModelConfig(
        dtype="float32", max_model_len=512, num_layers=2, num_experts=8,
        num_experts_per_tok=2),
    # latent attention, a sigmoid router with shared experts, a dense lead
    "latent-shared-dense-lead": ModelConfig(
        name="tiny-moonlight", vocab_size=128, hidden_size=64,
        intermediate_size=32, dense_intermediate_size=96,
        first_dense_layers=1, num_layers=3, num_heads=4, num_kv_heads=4,
        head_dim=16, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, query_scale=24 ** -0.5, rope_theta=50000.0,
        rms_norm_eps=1e-5, max_model_len=256, num_experts=16,
        num_experts_per_tok=4, norm_topk_prob=True, moe_scoring="sigmoid",
        moe_router_bias=True, moe_routed_scale=2.446,
        shared_expert_size=64, dtype="float32"),
}

# (decode rows, the prefill rows' chunk lengths) -> real tokens
_COMPACT_PLANS = {
    "mixed-23": (7, (16,)),                    # the compact branch
    "boundary-128": (7, (16,) * 7 + (9,)),     # == the width: still compact
    "chunks-over-132": (4, (16,) * 8),         # > the width: the grid
    "pure-chunk-256": (0, (16,) * 16),         # every cell real: the grid
}


def _mixed_plan_arrays(vocab, n_decode, chunks, rows=_ROWS, chunk=_CHUNK,
                       table=_TABLE):
    """A [rows, chunk] plan ([_ROWS, _CHUNK] unless said) laid out as
    Scheduler._build_prefill lays a MixedPlan out: decode rows first, one
    real token in column 0; then prefill rows; padding columns repeat the
    row's last position and write nothing; rows past the last are all
    padding."""
    rng = np.random.RandomState(n_decode * 31 + len(chunks))
    tokens = np.zeros((rows, chunk), np.int32)
    positions = np.zeros((rows, chunk), np.int32)
    write_idx = np.full((rows, chunk), -1, np.int32)
    page_table = np.zeros((rows, table), np.int32)
    kv_lens = np.zeros((rows,), np.int32)
    last = np.zeros((rows,), np.int32)
    for i in range(n_decode + len(chunks)):
        page_table[i] = np.arange(i * table, (i + 1) * table)
        start, n = (5 + i, 1) if i < n_decode else (3, chunks[i - n_decode])
        tokens[i, :n] = rng.randint(1, vocab, n)
        positions[i, :] = start + n - 1
        positions[i, :n] = np.arange(start, start + n)
        at = np.arange(start, start + n)
        write_idx[i, :n] = page_table[i, at // _PS] * _PS + at % _PS
        kv_lens[i] = start + n
        last[i] = n - 1
    return tokens, positions, page_table, kv_lens, write_idx, last


def _filled_pool(cfg, pages, seed):
    """A cache of `pages` pages in which every value is drawn: int8
    leaves over their whole range, the others in (0.01, 1)."""
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.models import llama
    key = jax.random.PRNGKey(seed)
    return {
        k: (jax.random.randint(key, v.shape, -127, 128, v.dtype)
            if jnp.issubdtype(v.dtype, jnp.integer)
            else jax.random.uniform(key, v.shape, v.dtype, 0.01, 1.0))
        for k, v in llama.init_cache(cfg, pages, _PS).items()}


@pytest.fixture(scope="module")
def compact_programs():
    """name -> (vocabulary, a filled pool, forward() jitted: with
    `last_idx` the step as the engine calls it, without it the same step
    over the grid with every position's logits): two compiles a model,
    every plan has the one shape."""
    import functools

    import jax

    from dynamo_tpu.models import llama

    @functools.lru_cache(maxsize=None)
    def build(name):
        cfg = _COMPACT_MODELS[name]
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        pool = _filled_pool(cfg, _ROWS * _TABLE, 7)

        def step(pool, tokens, positions, page_table, kv_lens, write_idx,
                 last_idx=None):
            meta = llama.AttnMetadata(positions, page_table, kv_lens,
                                      write_idx)
            return llama.forward(params, cfg, tokens, pool, meta,
                                 with_aux=True, last_idx=last_idx)
        return cfg.vocab_size, pool, jax.jit(step)
    return build


@pytest.mark.parametrize("plan", sorted(_COMPACT_PLANS))
@pytest.mark.parametrize("model", sorted(_COMPACT_MODELS))
def test_compact_step_is_the_grid_step_at_its_real_tokens(
        compact_programs, model, plan):
    """forward(last_idx=...), the engine's step, against forward() over
    the whole grid: the same [B, V] logits at every real row's last
    token, the same pool (so the same rows at the plan's `write_idx` and
    nothing else touched) and the same MoE counters, whichever branch
    the step takes: compact (23 real tokens), compact at the boundary
    (128 == the width), and the grid's full width for a step over it
    (132) and for a pure chunk. The host's predicate and the program's are one
    function of `write_idx` and agree; a row with no real token tells
    which branch ran, since it reads flat row 0 on the compact branch
    (here row 0's own logits) and the padding cell on the grid."""
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.models import llama
    from dynamo_tpu.ops.attention import compact_step

    vocab, pool, step = compact_programs(model)
    n_decode, chunks = _COMPACT_PLANS[plan]
    *arrays, last = _mixed_plan_arrays(vocab, n_decode, chunks)
    write_idx = arrays[-1]
    n_real = int((write_idx >= 0).sum())
    assert n_real == n_decode + sum(chunks)

    width, fits = llama.step_compaction(write_idx)
    assert width == _WIDTH and bool(fits) == (n_real <= _WIDTH)
    assert bool(jax.jit(lambda w: compact_step(w)[1])(
        jnp.asarray(write_idx))) == bool(fits)

    every, pool_grid, aux_grid = step(pool, *arrays)
    got, pool_flat, aux_flat = step(pool, *arrays, last)
    rows = n_decode + len(chunks)
    want = np.asarray(every)[np.arange(_ROWS), last]
    assert got.shape == (_ROWS, vocab)
    np.testing.assert_allclose(np.asarray(got)[:rows], want[:rows],
                               rtol=2e-5, atol=2e-5)
    assert set(pool_flat) == set(pool_grid)
    for leaf in pool_grid:
        np.testing.assert_allclose(
            np.asarray(pool_flat[leaf], np.float32),
            np.asarray(pool_grid[leaf], np.float32), rtol=2e-5, atol=2e-5,
            err_msg=leaf)
    cfg = _COMPACT_MODELS[model]
    assert set(aux_flat) == set(aux_grid)
    assert bool(aux_grid) == cfg.is_moe
    for name in ("moe_routed", "moe_dropped", "moe_experts_hit",
                 "moe_layer_calls") if cfg.is_moe else ():
        assert float(aux_flat[name]) == float(aux_grid[name]), name
    if cfg.is_moe:
        assert float(aux_grid["moe_routed"]) == (
            n_real * cfg.num_experts_per_tok
            * (cfg.num_layers - cfg.first_dense_layers))
    if rows < _ROWS and n_decode:
        took_compact = np.array_equal(np.asarray(got)[rows:],
                                      np.tile(np.asarray(got)[:1],
                                              (_ROWS - rows, 1)))
        assert took_compact == bool(fits)


# the benchmark's cells' own step shapes, whose attention takes the row
# form (ops/attention.attention_rows_pay): (rows, chunk, decode rows, the
# chunk rows' real tokens), inside the flat width of 256
_SERVED_SHAPES = {
    "8x64-one-chunk": (8, 64, 7, (64,)),
    "8x64-decode-only": (8, 64, 6, ()),
    "64x64-three-chunks": (64, 64, 59, (64, 64, 64)),
    "64x64-short-chunks": (64, 64, 40, (37, 1, 2)),
}


@pytest.mark.parametrize("shape", sorted(_SERVED_SHAPES))
@pytest.mark.parametrize("model", ["dense-gqa", "int8-kv-pool",
                                   "latent-shared-dense-lead"])
def test_a_served_shapes_step_reads_the_grid_forms_logits(model, shape):
    """An [8, 64] and a [64, 64] step as the engine calls them
    (`last_idx`), whose attention runs over the real queries inside the
    back half's `cond` (llama.step_attention_rows), against forward()
    over the whole grid: the same logits at every real row's last token
    and the same pool, to float32 rounding (a softmax over 67 keys sums
    in another order in each form)."""
    import jax

    from dynamo_tpu.models import llama

    rows, chunk, n_decode, chunks = _SERVED_SHAPES[shape]
    cfg = _COMPACT_MODELS[model]
    assert llama.step_attention_rows(cfg, chunk)
    table = -(-(3 + chunk) // _PS)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    pool = _filled_pool(cfg, rows * table, 11)
    *arrays, last = _mixed_plan_arrays(cfg.vocab_size, n_decode, chunks,
                                       rows, chunk, table)
    assert bool(llama.step_compaction(arrays[-1])[1])

    @jax.jit
    def step(pool, tokens, positions, page_table, kv_lens, write_idx,
             last_idx=None):
        return llama.forward(
            params, cfg, tokens, pool,
            llama.AttnMetadata(positions, page_table, kv_lens, write_idx),
            last_idx=last_idx)
    every, pool_grid = step(pool, *arrays)
    got, pool_flat = step(pool, *arrays, last)
    live = n_decode + len(chunks)
    np.testing.assert_allclose(
        np.asarray(got)[:live],
        np.asarray(every)[np.arange(rows), last][:live],
        rtol=1e-4, atol=1e-4)
    for leaf in pool_grid:
        np.testing.assert_allclose(
            np.asarray(pool_flat[leaf], np.float32),
            np.asarray(pool_grid[leaf], np.float32), rtol=1e-4, atol=1e-4,
            err_msg=leaf)


def test_capacity_form_drops_in_the_compact_tests():
    """The capacity-form case above compares drop counters that are not
    all zero: its 16-token chunks overflow an expert's 8 slots."""
    from dynamo_tpu.ops.moe import _capacity
    cfg = _COMPACT_MODELS["capacity-experts"]
    assert not cfg.moe_dropless and _COMPACT_MODELS[
        "dropless-experts"].moe_dropless
    assert _capacity(_CHUNK, cfg.num_experts_per_tok, cfg.num_experts,
                     2.0) < _CHUNK


@pytest.mark.parametrize("plan", sorted(_COMPACT_PLANS))
def test_host_counts_the_rows_the_program_runs_over(eng_mixed, plan):
    """`NativeEngine._dense_rows`, what `llm_engine_tokens_dense` adds a
    step: the flat width where the program's own predicate takes the
    compact branch, the grid where it does not; the ledger then counts
    a compact step exactly where the rows fell under the charge."""
    import types

    from dynamo_tpu.observability.ledger import StepLedger

    n_decode, chunks = _COMPACT_PLANS[plan]
    tokens, *_, write_idx, _ = _mixed_plan_arrays(100, n_decode, chunks)
    fits = n_decode + sum(chunks) <= _WIDTH
    dense = eng_mixed._dense_rows(
        types.SimpleNamespace(tokens=tokens, write_idx=write_idx))
    assert dense == (_WIDTH if fits else _ROWS * _CHUNK)
    ledger = StepLedger()
    ledger.stats = type(ledger.stats)()
    ledger.record_step("mixed", _ROWS, n_decode + len(chunks),
                       n_decode + sum(chunks), tokens.size, 0, 1, 0, 0, 0,
                       0, 0, 0, dense=dense)
    assert ledger.stats.tokens_dense == dense
    assert ledger.stats.compact_steps_total == int(fits)
    assert ledger.stats.tokens_padded == _ROWS * _CHUNK


def test_served_mixed_steps_take_the_compact_branch():
    """End to end through the engine: nine streams decode while a
    two-chunk prompt and then a short one are admitted, so the mixed
    steps are [16, 16] plans (a grid of 256 over 128 flat rows; the
    engines of the identity tests above stop at [2, 32], no larger than
    their flat width, and hold no `cond`). The ledger says every mixed
    step took the compact branch, and the streams are token-identical,
    greedy and seeded-sampled, to the alternating scheduler's, whose
    prefill steps are the only other `_engine_step`s."""
    first, n = 9, 11
    eng = make_engine(1, 512, max_slots=12, max_prefill_chunk=16,
                      prefill_buckets=(16,))
    prompts = [list(range(10 * i + 3, 10 * i + 9)) for i in range(first)] \
        + [list(range(100, 128)), list(range(200, 207))]

    def drive(tag, params):
        got, done, late = {}, set(), list(range(first, n))
        for i in range(first):
            got[f"{tag}{i}"] = []
            eng.add_request(EngineRequest(f"{tag}{i}", prompts[i], params[i]))
        for _ in range(400):
            for ev in eng.step():
                if ev.token is not None:
                    got[ev.request_id].append(ev.token)
                if ev.finished:
                    done.add(ev.request_id)
            if late and all(len(got[f"{tag}{i}"]) >= 2
                            for i in range(first)) \
                    and (late[0] == first or got[f"{tag}{first}"]):
                i = late.pop(0)
                got[f"{tag}{i}"] = []
                eng.add_request(
                    EngineRequest(f"{tag}{i}", prompts[i], params[i]))
            if len(done) == n:
                return [got[f"{tag}{i}"] for i in range(n)]
        raise AssertionError(sorted(done))

    stats = eng.ledger.stats
    mixed_rows, dense_rows = [], eng._dense_rows

    def spy(plan):
        if isinstance(plan, MixedPlan):
            mixed_rows.append((plan.tokens.shape, dense_rows(plan)))
        return dense_rows(plan)
    eng._dense_rows = spy
    for name, params in (
            ("greedy", [SamplingParams(max_tokens=20, temperature=0.0,
                                       ignore_eos=True)] * n),
            ("sampled", [SamplingParams(max_tokens=20, temperature=0.8,
                                        top_p=0.9, seed=5 + i,
                                        ignore_eos=True)
                         for i in range(n)])):
        eng.scheduler.mixed_token_budget = 0
        ref = drive(name + "r", params)
        eng.scheduler.mixed_token_budget = 512
        before = stats.snapshot()
        del mixed_rows[:]
        mix = drive(name + "m", params)
        delta = {k: v - before[k] for k, v in stats.snapshot().items()}
        assert mix == ref, name
        assert delta["steps_mixed"] >= 2, delta
        assert mixed_rows and all(
            shape == (16, 16) and dense == _WIDTH
            for shape, dense in mixed_rows), mixed_rows
        assert delta["compact_steps_total"] >= delta["steps_mixed"], delta
        assert delta["tokens_useful"] <= delta["tokens_dense"] \
            < delta["tokens_padded"]


@pytest.mark.parametrize("plan", ["mixed-23", "chunks-over-132"])
def test_compact_step_takes_image_embeds_at_their_cells(plan):
    """Multimodal prefill through both branches: the embed rows and
    their mask are gathered with the tokens, so an image span inside a
    chunk lands on the same tokens as over the grid."""
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.models import llama

    n_decode, chunks = _COMPACT_PLANS[plan]
    tokens, positions, page_table, kv_lens, write_idx, last = \
        _mixed_plan_arrays(CFG.vocab_size, n_decode, chunks)
    rng = np.random.RandomState(3)
    embeds = rng.randn(_ROWS, _CHUNK, CFG.hidden_size).astype(np.float32)
    mask = np.zeros((_ROWS, _CHUNK), bool)
    mask[n_decode, 2:9] = True          # a span inside the first chunk
    params = llama.init_params(jax.random.PRNGKey(0), CFG)
    pool = llama.init_cache(CFG, _ROWS * _TABLE, _PS)
    meta = llama.AttnMetadata(*(jnp.asarray(a) for a in (
        positions, page_table, kv_lens, write_idx)))

    def step(last_idx):
        return jax.jit(lambda pool: llama.forward(
            params, CFG, jnp.asarray(tokens), pool, meta,
            input_embeds=jnp.asarray(embeds), embeds_mask=jnp.asarray(mask),
            last_idx=last_idx))(pool)
    every, pool_grid = step(None)
    got, pool_flat = step(jnp.asarray(last))
    rows = n_decode + len(chunks)
    np.testing.assert_allclose(
        np.asarray(got)[:rows],
        np.asarray(every)[np.arange(_ROWS), last][:rows],
        rtol=2e-5, atol=2e-5)
    text_only = jax.jit(lambda pool: llama.forward(
        params, CFG, jnp.asarray(tokens), pool, meta,
        last_idx=jnp.asarray(last)))(pool)[0]
    assert np.abs(np.asarray(got)[n_decode]
                  - np.asarray(text_only)[n_decode]).max() > 1e-3
    for leaf in pool_grid:
        np.testing.assert_allclose(np.asarray(pool_flat[leaf]),
                                   np.asarray(pool_grid[leaf]),
                                   rtol=2e-5, atol=2e-5)
