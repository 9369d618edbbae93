"""PR 18: ONE ragged decode kernel; PR 44: ONE sampling tail.

Two gates in one file:

1. The parity matrix — the unified ragged kernel (ops/paged_attention.py)
   against the FROZEN pre-PR-18 kernels (ops/paged_attention_oracle.py),
   across the row vocabulary {plain direct, packed, prefix} x
   {single-device, tp=2 shard_map} x {f32, bf16, int8 scale-folding}.
   The oracle module is the pre-refactor code verbatim, so this matrix IS
   the "token-identical to HEAD" argument at the kernel layer; engine-level
   token identity (greedy + seeded-sampled) rides on top.

2. The sampling tail's contract — every sampled plan takes
   `sampler.sample`, whatever its rows' top_p: no static bit of the
   window key tells a top_p-free batch from one with a top_p row (one
   program, no recompile), a top_p-free batch draws what `sample` draws
   on the same logits, temperature-0 rows inside a sampled window get
   their argmax, and a fixed workload mints no program on its second
   pass.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.config import EngineConfig, ModelConfig
from dynamo_tpu.engine.engine import NativeEngine
from dynamo_tpu.engine.scheduler import SamplingParams
from dynamo_tpu.ops.paged_attention import (
    combine_self_attention, decode_paged_attention,
    decode_paged_attention_prefix, decode_paged_attention_sharded,
)
from dynamo_tpu.ops.paged_attention_oracle import decode_paged_attention_legacy

ECFG = EngineConfig(page_size=8, num_pages=32, max_slots=2,
                    max_prefill_chunk=32, prefill_buckets=(8, 16, 32),
                    max_model_len=256)


def _geometry(hd, dtype, quant, seed):
    """Random cache geometry exercising ragged lengths + page reuse."""
    rng = np.random.default_rng(seed)
    s, h, hkv, p, ps, pb = 3, 8, 4, 16, 8, 4
    if hd == 128:
        h, hkv = 4, 2  # keep interpret-mode runtime down at the wide head
    q = rng.standard_normal((s, h, hd)).astype(dtype)
    if quant:
        k = rng.integers(-127, 128, (hkv, p, ps, hd), dtype=np.int8)
        v = rng.integers(-127, 128, (hkv, p, ps, hd), dtype=np.int8)
        ks = rng.uniform(0.01, 0.05, (hkv, p, ps)).astype(np.float32)
        vs = rng.uniform(0.01, 0.05, (hkv, p, ps)).astype(np.float32)
    else:
        k = rng.standard_normal((hkv, p, ps, hd)).astype(dtype)
        v = rng.standard_normal((hkv, p, ps, hd)).astype(dtype)
        ks = vs = None
    pt = ((np.arange(s * pb).reshape(s, pb) * 7) % p).astype(np.int32)
    lens = np.array([5, 17, 32], np.int32)
    return q, k, v, ks, vs, pt, lens


@pytest.mark.parametrize("hd", [32, 64, 128])  # pack = 4 / 2 / 1 (direct)
@pytest.mark.parametrize("dtype,quant", [
    (np.float32, False), (jnp.bfloat16, False), (np.float32, True),
])
def test_unified_matches_legacy_plain(hd, dtype, quant):
    """Plain/packed rows: the unified wrapper == the frozen (s, hkv)-grid
    legacy kernel, bit-for-shape across pack factors, bf16 DMA, and the
    int8 scale fold."""
    q, k, v, ks, vs, pt, lens = _geometry(hd, dtype, quant, seed=hd)
    kw = dict(interpret=True)
    if quant:
        kw.update(k_scale=jnp.asarray(ks), v_scale=jnp.asarray(vs))
    out = decode_paged_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
        jnp.asarray(pt), jnp.asarray(lens), **kw)
    ref = decode_paged_attention_legacy(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
        jnp.asarray(pt), jnp.asarray(lens), **kw)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=tol, atol=tol)


@pytest.mark.parametrize("hd", [64, 128])
def test_unified_prefix_matches_legacy_inclusive(hd):
    """Prefix rows: prefix-mode kernel + combine_self_attention over a
    cache WITHOUT the current token == the legacy inclusive kernel over
    the cache WITH the token scattered in — the deferred-write decode hot
    path against the frozen pre-PR-18 implementation, including an empty
    prefix row."""
    rng = np.random.default_rng(hd)
    s, h, hkv, L, p, ps, pb = 3, 8, 2, 2, 16, 64, 3
    q = rng.standard_normal((s, h, hd)).astype(np.float32)
    kc = rng.standard_normal((L, hkv, p, ps, hd)).astype(np.float32)
    vc = rng.standard_normal((L, hkv, p, ps, hd)).astype(np.float32)
    k_new = rng.standard_normal((s, hkv, hd)).astype(np.float32)
    v_new = rng.standard_normal((s, hkv, hd)).astype(np.float32)
    # DISJOINT per-row pages: the inclusive reference scatters each row's
    # current token into its boundary page, so no page may be shared
    pt = np.arange(s * pb).reshape(s, pb).astype(np.int32)
    prefix = np.array([70, 0, 130], np.int32)
    layer = 1

    acc, m, l = decode_paged_attention_prefix(
        jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc),
        jnp.asarray([layer], jnp.int32), jnp.asarray(pt),
        jnp.asarray(prefix), interpret=True)
    out = combine_self_attention(
        jnp.asarray(q), jnp.asarray(k_new), jnp.asarray(v_new), acc, m, l)

    # scatter the current token into row prefix[i] of its boundary page
    # and ask the frozen inclusive kernel the same question
    k_inc, v_inc = kc[layer].copy(), vc[layer].copy()
    for i in range(s):
        pg, r = pt[i, prefix[i] // ps], prefix[i] % ps
        k_inc[:, pg, r] = k_new[i]
        v_inc[:, pg, r] = v_new[i]
    ref = decode_paged_attention_legacy(
        jnp.asarray(q), jnp.asarray(k_inc), jnp.asarray(v_inc),
        jnp.asarray(pt), jnp.asarray(prefix + 1), interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_unified_prefix_int8_scale_fold_matches_dequant():
    """Prefix rows x int8: in-kernel scale folding == running the same
    unified kernel on the explicitly dequantized f32 cache (the exactness
    argument: a row's scale is constant over the hd contraction, so it
    commutes with both kernel dots)."""
    rng = np.random.default_rng(9)
    s, h, hkv, L, p, ps, pb, hd = 3, 8, 2, 2, 8, 64, 3, 64
    q = rng.standard_normal((s, h, hd)).astype(np.float32)
    kc = rng.integers(-127, 128, (L, hkv, p, ps, hd), dtype=np.int8)
    vc = rng.integers(-127, 128, (L, hkv, p, ps, hd), dtype=np.int8)
    ks = rng.uniform(0.01, 0.05, (L, hkv, p, ps)).astype(np.float32)
    vs = rng.uniform(0.01, 0.05, (L, hkv, p, ps)).astype(np.float32)
    pt = ((np.arange(s * pb).reshape(s, pb) * 3) % p).astype(np.int32)
    prefix = np.array([70, 0, 130], np.int32)

    quant = decode_paged_attention_prefix(
        jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc),
        jnp.asarray([1], jnp.int32), jnp.asarray(pt), jnp.asarray(prefix),
        interpret=True, k_scale=jnp.asarray(ks), v_scale=jnp.asarray(vs))
    deq = decode_paged_attention_prefix(
        jnp.asarray(q),
        jnp.asarray(kc.astype(np.float32) * ks[..., None]),
        jnp.asarray(vc.astype(np.float32) * vs[..., None]),
        jnp.asarray([1], jnp.int32), jnp.asarray(pt), jnp.asarray(prefix),
        interpret=True)
    for a, b in zip(quant, deq):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("quant", [False, True])
def test_sharded_tp2_matches_legacy(quant):
    """tp=2 shard_map'd unified kernel == single-device legacy kernel
    (heads sharded; int8 shards the scale stacks the same way)."""
    from dynamo_tpu.parallel.mesh import make_mesh
    if len(jax.devices()) < 2:
        pytest.skip("needs 2 devices")
    q, k, v, ks, vs, pt, lens = _geometry(32, np.float32, quant, seed=5)
    kw = dict(interpret=True)
    if quant:
        kw.update(k_scale=jnp.asarray(ks), v_scale=jnp.asarray(vs))
    mesh = make_mesh(tp=2)
    out = decode_paged_attention_sharded(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
        jnp.asarray(pt), jnp.asarray(lens), mesh, **kw)
    ref = decode_paged_attention_legacy(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
        jnp.asarray(pt), jnp.asarray(lens), **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


# -- engine-level token identity ----------------------------------------------

SAMPLED = SamplingParams(max_tokens=6, temperature=0.8, top_k=40,
                         seed=1234, ignore_eos=True)
PROMPT = list(range(50, 70))


def _gen(mcfg, ecfg=ECFG, mesh=None, params=SAMPLED, rid="r"):
    eng = NativeEngine(mcfg, ecfg, mesh=mesh, seed=0)
    try:
        return eng.generate(PROMPT, params, rid), eng
    finally:
        eng.close()


@pytest.mark.parametrize("mesh_kw", [None, {"tp": 2}])
def test_engine_sampled_kernel_matches_gather(mesh_kw):
    """Seeded-sampled engine runs (the fused-tail path: top_p == 1) are
    token-identical between the unified ragged kernel and the XLA gather
    path, single-device and tp=2 shard_map."""
    from dynamo_tpu.parallel.mesh import make_mesh
    if mesh_kw and len(jax.devices()) < 2:
        pytest.skip("needs 2 devices")
    mesh = make_mesh(**mesh_kw) if mesh_kw else None
    base = ModelConfig(dtype="float32", max_model_len=256)
    off, _ = _gen(dataclasses.replace(base, decode_kernel="off"), mesh=mesh)
    kern, _ = _gen(dataclasses.replace(base, decode_kernel="interpret"),
                   mesh=mesh)
    assert off == kern


@pytest.mark.parametrize("params", [
    SamplingParams(max_tokens=5, temperature=0.0, ignore_eos=True),
    SAMPLED,
])
def test_engine_int8_kernel_matches_gather(params):
    """int8 kv_quant x {greedy, seeded-sampled}: the in-kernel scale fold
    decodes the same tokens as the gather path's row dequant."""
    base = ModelConfig(dtype="float32", max_model_len=256)
    ecfg = dataclasses.replace(ECFG, kv_quant="int8")
    off, _ = _gen(dataclasses.replace(base, decode_kernel="off"), ecfg,
                  params=params)
    kern, _ = _gen(dataclasses.replace(base, decode_kernel="interpret"),
                   ecfg, params=params)
    assert off == kern


# -- one sampling tail: one window program a sampled plan, whatever top_p ------


def _window_programs(eng):
    return {k for k in eng._seen_programs if k[0] == "window"}


def _run_batch(eng, reqs, prompt_shift=0):
    """Serve `reqs` ((request id, SamplingParams) pairs) as one batch;
    tokens by request id."""
    from dynamo_tpu.engine.scheduler import EngineRequest
    toks = {rid: [] for rid, _ in reqs}
    for i, (rid, p) in enumerate(reqs):
        eng.add_request(EngineRequest(
            rid, [t + prompt_shift + 7 * i for t in PROMPT], p))
    while eng.has_work():
        for ev in eng.step():
            if ev.token is not None:
                toks[ev.request_id].append(ev.token)
    return toks


def test_top_p_row_dispatches_the_same_window_program():
    """A top_p-free sampled batch (the API's default request) and the same
    batch with one row at top_p 0.95 run ONE window program: the second
    batch mints no window key. While a static `fused` bit rode the key the
    second batch compiled every sampled window again."""
    base = ModelConfig(dtype="float32", max_model_len=256)
    eng = NativeEngine(base, ECFG, seed=0)
    try:
        free = [("a0", SAMPLED), ("a1", dataclasses.replace(SAMPLED, seed=5))]
        _run_batch(eng, free)
        windows = _window_programs(eng)
        assert windows
        mixed = [("b0", SAMPLED),
                 ("b1", dataclasses.replace(SAMPLED, seed=5, top_p=0.95))]
        # other prompts of the same length: a prefix-cache hit would
        # shorten the prefill chunk, which is a step program's business
        _run_batch(eng, mixed, prompt_shift=100)
        assert _window_programs(eng) == windows
        assert eng.decode_kernel_tag == "gather"   # the attention path alone
    finally:
        eng.close()


def _top_k_only_tokens(logits, temperature, top_k, keys):
    """The tail a top_p-free row is owed, written the slow way: rank every
    token by one descending argsort, keep ranks < k, draw."""
    v = logits.shape[-1]
    scaled = logits / jnp.maximum(temperature, 1e-6)[:, None]
    ranks = jnp.argsort(jnp.argsort(scaled, axis=-1)[:, ::-1], axis=-1)
    k = jnp.where(top_k > 0, top_k, v)[:, None]
    masked = jnp.where(ranks < k, scaled, -1e30)
    drawn = jax.vmap(jax.random.categorical)(keys, masked).astype(jnp.int32)
    return jnp.where(temperature <= 0.0, jnp.argmax(logits, -1), drawn)


def test_top_p_free_tokens_equal_sample_on_the_same_logits(monkeypatch):
    """Seeded tokens of a top_p 1.0, top_k 12 batch through the engine
    equal `sampler.sample` called alone on the logits the programs handed
    their tail (top_p 1.0 switches keep_mask's mass condition off), and
    both equal the rank-by-one-argsort tail that such a batch used to
    take inside the window."""
    from dynamo_tpu.engine import sampler as sampler_mod
    real, calls = sampler_mod.sample, []

    def spied(logits, temperature, top_k, top_p, keys):
        toks = real(logits, temperature, top_k, top_p, keys)
        jax.debug.callback(lambda *xs: calls.append(xs), logits,
                           temperature, top_k, top_p, keys, toks)
        return toks

    monkeypatch.setattr(sampler_mod, "sample", spied)
    base = ModelConfig(dtype="float32", max_model_len=256)
    p = dataclasses.replace(SAMPLED, top_k=12, top_p=1.0)
    eng = NativeEngine(base, ECFG, seed=0)
    try:
        served = _run_batch(eng, [("r0", p),
                                  ("r1", dataclasses.replace(p, seed=9))])
    finally:
        jax.effects_barrier()
        eng.close()
    assert any(k[0] == "window" for k in eng._seen_programs)
    assert len(calls) >= SAMPLED.max_tokens
    emitted = set()
    for logits, temperature, top_k, top_p, keys, toks in calls:
        live = np.asarray(temperature) > 0        # padding rows: temp 0
        assert np.all(np.asarray(top_p)[live] == 1.0)
        assert np.all(np.asarray(top_k)[live] == 12)
        alone = real(logits, temperature, top_k, jnp.ones_like(top_p), keys)
        slow = _top_k_only_tokens(logits, temperature, top_k, keys)
        np.testing.assert_array_equal(np.asarray(alone), toks)
        np.testing.assert_array_equal(np.asarray(slow), toks)
        emitted.update(np.asarray(toks)[live].tolist())
    # what the tail drew is what the requests were sent
    assert all(len(t) == SAMPLED.max_tokens for t in served.values())
    assert set(served["r0"]) | set(served["r1"]) <= emitted


def test_greedy_rows_in_a_sampled_window_match_each_row_alone():
    """A batch mixing a temperature-0 row and a sampled row is ONE sampled
    window (`sample` resolves a temperature-0 row to the argmax
    in-program); each row gets the tokens it gets when served alone, the
    greedy one by the argmax-only program."""
    base = ModelConfig(dtype="float32", max_model_len=256)
    reqs = [
        ("greedy", SamplingParams(max_tokens=6, temperature=0.0,
                                  ignore_eos=True)),
        ("sampled", dataclasses.replace(SAMPLED, seed=77)),
    ]

    def run(batch):
        eng = NativeEngine(base, ECFG, seed=0)
        try:
            toks = _run_batch(eng, batch)
            return toks, {k[3] for k in _window_programs(eng)}
        finally:
            eng.close()

    together, greedy_bits = run(reqs)
    assert greedy_bits == {False}
    for i, (rid, p) in enumerate(reqs):
        # the batch's i-th prompt: _run_batch shifts a row's prompt by 7 * i
        eng = NativeEngine(base, ECFG, seed=0)
        try:
            alone = eng.generate([t + 7 * i for t in PROMPT], p, rid)
            assert {k[3] for k in _window_programs(eng)} == {p.temperature
                                                             <= 0.0}
        finally:
            eng.close()
        assert alone == together[rid], rid


def test_fixed_sampled_workload_mints_no_program_on_its_second_pass():
    """Recompile pin (_note_program): a second pass of a fixed sampled
    workload mints ZERO new programs, and the first minted exactly as
    many window programs as it has rungs to run."""
    base = ModelConfig(dtype="float32", max_model_len=256)
    eng = NativeEngine(base, ECFG, seed=0)
    try:
        eng.generate(PROMPT, SAMPLED, "a")
        programs = set(eng._seen_programs)
        assert len(_window_programs(eng)) == len(
            {k[4] for k in _window_programs(eng)})
        # distinct same-length prompt: prefix-cache reuse would otherwise
        # legitimately shrink request b's prefill chunk (a different
        # program, but not a sampling-tail recompile)
        eng.generate([t + 100 for t in PROMPT],
                     dataclasses.replace(SAMPLED, seed=99), "b")
        assert eng._seen_programs == programs
    finally:
        eng.close()
