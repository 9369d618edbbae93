"""Pipeline parallelism tests: pp_forward oracle parity on the CPU mesh.

VERDICT r2 next #8: a real microbatched pipeline over the "pp" mesh axis
(the reference delegates PP to vLLM, vllm_inc.py:38). The oracle is the
single-mesh models/llama.forward; pp must be bit-compatible in f32.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.config import ModelConfig
from dynamo_tpu.models import llama
from dynamo_tpu.models.llama import AttnMetadata
from dynamo_tpu.models.pp import pp_cache_sharding, pp_forward, pp_param_shardings
from dynamo_tpu.parallel.mesh import make_mesh

CFG = ModelConfig(dtype="float32", num_layers=4, max_model_len=128)
PAGE = 8
# enough pages that every test row gets a DISJOINT page range (aliased
# pages would make results order-dependent and the oracle meaningless)
NPAGES = 64


def make_inputs(b, tq, kv_len):
    """A prefill-shaped step: rows write positions [kv_len-tq, kv_len)."""
    rng = np.random.RandomState(0)
    tokens = rng.randint(1, CFG.vocab_size, (b, tq)).astype(np.int32)
    positions = np.tile(np.arange(kv_len - tq, kv_len, dtype=np.int32),
                        (b, 1))
    pages_per_seq = -(-CFG.max_model_len // PAGE)
    page_table = np.stack([
        np.arange(i * pages_per_seq, (i + 1) * pages_per_seq,
                  dtype=np.int32) % NPAGES
        for i in range(b)])
    kv_lens = np.full((b,), kv_len, np.int32)
    write_idx = np.stack([
        page_table[i, positions[i] // PAGE] * PAGE + positions[i] % PAGE
        for i in range(b)]).astype(np.int32)
    return (jnp.asarray(tokens),
            AttnMetadata(positions=jnp.asarray(positions),
                         page_table=jnp.asarray(page_table),
                         kv_lens=jnp.asarray(kv_lens),
                         write_idx=jnp.asarray(write_idx)))


@pytest.mark.parametrize("pp,tp,n_micro", [(2, 1, 2), (4, 1, 4), (2, 2, 2),
                                           (2, 1, 1)])
def test_pp_forward_matches_single_mesh(pp, tp, n_micro):
    params = llama.init_params(jax.random.PRNGKey(0), CFG)
    cache = llama.init_cache(CFG, num_pages=NPAGES, page_size=PAGE)
    b, tq, kv_len = 4, PAGE, PAGE
    tokens, meta = make_inputs(b, tq, kv_len)

    expect_logits, expect_cache = jax.jit(
        lambda p, c: llama.forward(p, CFG, tokens, c, meta))(params, cache)

    mesh = make_mesh(pp=pp, tp=tp, devices=jax.devices()[:pp * tp])
    from jax.sharding import NamedSharding
    shd = jax.tree.map(lambda s: NamedSharding(mesh, s),
                       pp_param_shardings(CFG),
                       is_leaf=lambda x: isinstance(
                           x, jax.sharding.PartitionSpec))
    params_pp = jax.device_put(params, shd)
    cache_shd = NamedSharding(mesh, pp_cache_sharding())
    cache_pp = jax.device_put(
        llama.init_cache(CFG, num_pages=NPAGES, page_size=PAGE),
        {"k": cache_shd, "v": cache_shd})

    got_logits, got_cache = jax.jit(
        lambda p, c: pp_forward(p, CFG, tokens, c, meta, mesh,
                                n_micro=n_micro))(params_pp, cache_pp)

    np.testing.assert_allclose(np.asarray(got_logits),
                               np.asarray(expect_logits),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(got_cache["k"]),
                               np.asarray(expect_cache["k"]),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got_cache["v"]),
                               np.asarray(expect_cache["v"]),
                               rtol=1e-5, atol=1e-5)


GEMMA2_CFG = ModelConfig(
    dtype="float32", num_layers=4, max_model_len=128, embed_scale=8.0,
    norm_plus_one=True, mlp_act="gelu_tanh", post_norms=True,
    attn_softcap=50.0, final_softcap=30.0, query_scale=32 ** -0.5,
    sliding_window=6, tie_word_embeddings=True)


@pytest.mark.parametrize("pp,tp", [(2, 1), (2, 2)])
def test_pp_forward_gemma2_matches_single_mesh(pp, tp):
    """Gemma-2-class configs (post-norms, soft-caps, query scaling, and
    ALTERNATING sliding windows threaded through the stage scan as a
    pp-sharded per-layer operand) stay oracle-exact on pp meshes."""
    cfg = GEMMA2_CFG
    params = llama.init_params(jax.random.PRNGKey(1), cfg)
    cache = llama.init_cache(cfg, num_pages=NPAGES, page_size=PAGE)
    b, tq, kv_len = 4, PAGE, PAGE
    tokens, meta = make_inputs(b, tq, kv_len)

    expect_logits, expect_cache = jax.jit(
        lambda p, c: llama.forward(p, cfg, tokens, c, meta))(params, cache)

    mesh = make_mesh(pp=pp, tp=tp, devices=jax.devices()[:pp * tp])
    from jax.sharding import NamedSharding
    shd = jax.tree.map(lambda s: NamedSharding(mesh, s),
                       pp_param_shardings(cfg),
                       is_leaf=lambda x: isinstance(
                           x, jax.sharding.PartitionSpec))
    params_pp = jax.device_put(params, shd)
    cache_shd = NamedSharding(mesh, pp_cache_sharding())
    cache_pp = jax.device_put(
        llama.init_cache(cfg, num_pages=NPAGES, page_size=PAGE),
        {"k": cache_shd, "v": cache_shd})
    got_logits, got_cache = jax.jit(
        lambda p, c: pp_forward(p, cfg, tokens, c, meta, mesh))(
            params_pp, cache_pp)
    np.testing.assert_allclose(np.asarray(got_logits),
                               np.asarray(expect_logits),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(got_cache["k"]),
                               np.asarray(expect_cache["k"]),
                               rtol=1e-5, atol=1e-5)


def test_pp_engine_gemma2_generates_identically():
    """Full engine on pp=2: Gemma-2-class greedy decode (multi-token pp
    windows incl. the sliding-window boundary) matches the single-device
    engine token-for-token."""
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import NativeEngine
    from dynamo_tpu.engine.scheduler import SamplingParams

    ecfg = EngineConfig(page_size=8, num_pages=64, max_slots=2,
                        max_prefill_chunk=16, prefill_buckets=(8, 16),
                        max_model_len=128)
    params = SamplingParams(max_tokens=10, temperature=0.0, ignore_eos=True)
    prompts = [list(range(3, 15)), list(range(40, 60))]

    oracle = NativeEngine(GEMMA2_CFG, ecfg, seed=0)
    expect = [oracle.generate(p, params, f"o{i}")
              for i, p in enumerate(prompts)]
    mesh = make_mesh(pp=2, tp=1, devices=jax.devices()[:2])
    eng = NativeEngine(GEMMA2_CFG, ecfg, mesh=mesh, seed=0)
    got, max_one = _drive_engine(eng, prompts, params)
    assert got == expect
    assert max_one > 1  # windowed pp decode, not per-token


def _drive_engine(eng, prompts, params):
    """Submit all prompts, run to completion; returns (tokens per request,
    max tokens any one request received from a single host dispatch)."""
    from dynamo_tpu.engine.scheduler import EngineRequest

    got = {}
    for i, p in enumerate(prompts):
        eng.add_request(EngineRequest(f"r{i}", p, params))
        got[f"r{i}"] = []
    max_tokens_one_dispatch = 0
    while eng.has_work():
        per_req = {}
        for ev in eng.step():
            if ev.token is not None:
                got[ev.request_id].append(ev.token)
                per_req[ev.request_id] = per_req.get(ev.request_id, 0) + 1
        if per_req:
            max_tokens_one_dispatch = max(max_tokens_one_dispatch,
                                          max(per_req.values()))
    return [got[f"r{i}"] for i in range(len(prompts))], \
        max_tokens_one_dispatch


@pytest.mark.parametrize("cfg,meshes", [
    pytest.param(CFG, ((2, 1), (2, 2)), id="llama"),
    # the stage's front half is models/llama.layer_front, QK-norm included;
    # pp x tp > 1 is refused by name (test_pp_refuses_by_name below)
    pytest.param(dataclasses.replace(CFG, qk_norm=True), ((2, 1),),
                 id="qk_norm"),
])
def test_pp_engine_generates_identically(cfg, meshes):
    """Full engine on a pp=2 mesh (pp=2 x tp=2 too): greedy tokens match the
    single-device engine exactly — the 'dryrun mesh pp=2 generating
    correctly' bar from VERDICT r2 next #8."""
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import NativeEngine
    from dynamo_tpu.engine.scheduler import SamplingParams

    ecfg = EngineConfig(page_size=8, num_pages=64, max_slots=2,
                        max_prefill_chunk=16, prefill_buckets=(8, 16),
                        max_model_len=128)
    params = SamplingParams(max_tokens=8, temperature=0.0, ignore_eos=True)
    prompts = [list(range(3, 15)), list(range(40, 60))]

    oracle = NativeEngine(cfg, ecfg, seed=0)
    expect = [oracle.generate(p, params, f"o{i}")
              for i, p in enumerate(prompts)]

    for pp, tp in meshes:
        mesh = make_mesh(pp=pp, tp=tp, devices=jax.devices()[:pp * tp])
        eng = NativeEngine(cfg, ecfg, mesh=mesh, seed=0)
        # multi-token pp decode (VERDICT r3 weak #7): the window survives
        # pp meshes instead of being forced to 1
        assert eng.pp == pp and eng.cfg.decode_steps == ecfg.decode_steps
        got, max_tokens_one_dispatch = _drive_engine(eng, prompts, params)
        assert got == expect, f"pp={pp} tp={tp} diverged"
        # the microbatch round-robin serves >1 token per host dispatch
        assert max_tokens_one_dispatch > 1, \
            f"pp={pp} tp={tp}: decode still per-token"


@pytest.mark.parametrize("change,tp,match", [
    (dict(num_experts=4, num_experts_per_tok=2), 1, "is_moe"),
    (dict(num_experts=4, num_experts_per_tok=2, norm_topk_prob=False), 1,
     "is_moe"),
    (dict(qk_norm=True), 2, "qk_norm with tp > 1"),
])
def test_pp_refuses_by_name_what_its_mesh_cannot_express(change, tp, match):
    """One place (models/pp.refuse_unserved, reached through
    pp_param_shardings) refuses what a pp mesh cannot serve, where the
    engine is built and at both entry points, naming the field."""
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import NativeEngine

    cfg = dataclasses.replace(CFG, **change)
    with pytest.raises(NotImplementedError, match=match):
        pp_param_shardings(cfg, tp)
    mesh = make_mesh(pp=2, tp=tp, devices=jax.devices()[:2 * tp])
    with pytest.raises(NotImplementedError, match=match):
        NativeEngine(cfg, EngineConfig(page_size=8, num_pages=64,
                                       max_slots=2, max_model_len=128),
                     mesh=mesh, seed=0)
    tokens, meta = make_inputs(2, 8, 8)
    with pytest.raises(NotImplementedError, match=match):
        pp_forward(llama.init_params(jax.random.PRNGKey(0), cfg), cfg,
                   tokens, llama.init_cache(cfg, NPAGES, PAGE), meta, mesh)


def test_pp_engine_sampled_window_matches_oracle():
    """VERDICT r4 #6: sampled plans (temperature / top-k / top-p) get
    windowed pp decode too — >1 token per host dispatch, token-exact vs
    the single-mesh engine at a fixed seed (the pp window samples through
    the same sample_logits tail with the same (seed, counter) keys).
    pp=2 x tp=2 covers sampling over the all_gathered vocab-sharded
    logits too."""
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import NativeEngine
    from dynamo_tpu.engine.scheduler import SamplingParams

    ecfg = EngineConfig(page_size=8, num_pages=64, max_slots=2,
                        max_prefill_chunk=16, prefill_buckets=(8, 16),
                        max_model_len=128)
    params = SamplingParams(max_tokens=8, temperature=0.8, top_k=40,
                            top_p=0.95, seed=1234, ignore_eos=True)
    prompts = [list(range(3, 15)), list(range(40, 60))]

    oracle = NativeEngine(CFG, ecfg, seed=0)
    expect = [oracle.generate(p, params, f"o{i}")
              for i, p in enumerate(prompts)]

    for pp, tp in ((2, 1), (2, 2)):
        mesh = make_mesh(pp=pp, tp=tp, devices=jax.devices()[:pp * tp])
        eng = NativeEngine(CFG, ecfg, mesh=mesh, seed=0)
        got, max_tokens_one_dispatch = _drive_engine(eng, prompts, params)
        assert got == expect, f"sampled pp={pp} tp={tp} diverged"
        # the sampled plan went through the window, not per-token dispatch
        assert max_tokens_one_dispatch > 1, \
            f"sampled pp={pp} tp={tp} decode still per-token"


def test_pp_tied_embeddings_engine_matches():
    """tie_word_embeddings + pp: the vocab-sharded embedding (P("tp",
    None) rows, _embed_lookup masked gather + psum) doubles as the
    vocab-sharded head; tokens match the single-device engine."""
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import NativeEngine
    from dynamo_tpu.engine.scheduler import SamplingParams

    cfg = ModelConfig(dtype="float32", max_model_len=128,
                      tie_word_embeddings=True)
    ecfg = EngineConfig(page_size=8, num_pages=64, max_slots=2,
                        max_prefill_chunk=16, prefill_buckets=(8, 16),
                        max_model_len=128)
    p = SamplingParams(max_tokens=6, temperature=0.0, ignore_eos=True)
    prompt = list(range(9, 25))
    oracle = NativeEngine(cfg, ecfg, seed=0).generate(prompt, p, "o")
    mesh = make_mesh(pp=2, tp=2, devices=jax.devices()[:4])
    got = NativeEngine(cfg, ecfg, mesh=mesh, seed=0).generate(
        prompt, p, "t")
    assert got == oracle


def test_pp_decode_step_matches():
    """tq=1 decode-shaped step through the pipeline (the engine's pp decode
    path) against the single-mesh oracle, including the KV row it writes."""
    params = llama.init_params(jax.random.PRNGKey(1), CFG)
    b, kv_len = 4, 24

    # build a warm cache by prefilling kv_len-1 tokens, then decode 1 token
    tokens_p, meta_p = make_inputs(b, PAGE, PAGE)
    cache = llama.init_cache(CFG, num_pages=NPAGES, page_size=PAGE)
    _, cache = jax.jit(
        lambda p, c: llama.forward(p, CFG, tokens_p, c, meta_p))(
            params, cache)

    tokens_d, meta_d = make_inputs(b, 1, PAGE + 1)
    expect_logits, expect_cache = jax.jit(
        lambda p, c: llama.forward(p, CFG, tokens_d, c, meta_d))(
            params, cache)

    mesh = make_mesh(pp=2, devices=jax.devices()[:2])
    from jax.sharding import NamedSharding
    shd = jax.tree.map(lambda s: NamedSharding(mesh, s),
                       pp_param_shardings(CFG),
                       is_leaf=lambda x: isinstance(
                           x, jax.sharding.PartitionSpec))
    params_pp = jax.device_put(params, shd)
    cache_shd = NamedSharding(mesh, pp_cache_sharding())
    cache_pp = jax.device_put(jax.device_get(cache),
                              {"k": cache_shd, "v": cache_shd})

    got_logits, got_cache = jax.jit(
        lambda p, c: pp_forward(p, CFG, tokens_d, c, meta_d, mesh))(
            params_pp, cache_pp)
    np.testing.assert_allclose(np.asarray(got_logits),
                               np.asarray(expect_logits),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(got_cache["k"]),
                               np.asarray(expect_cache["k"]),
                               rtol=1e-5, atol=1e-5)
