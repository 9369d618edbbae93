"""Moonlight-16B-A3B (`DeepseekV3ForCausalLM`) through the served path
against the plain reference (dynamo_tpu/models/reference.py), on LOGITS.

A tiny seeded model of the same kinds of layer (3 layers: one dense lead of
width 96, then two of 16 experts of 32, 4 a token, behind a sigmoid router
with a selection bias and a 2.446 scale, plus a shared expert of 64; latent
attention with 4 heads of 16 | 8, a latent of 32, value heads of 16) is
driven through the real NativeEngine exactly as tests/test_olmoe.py drives
OLMoE: prompts that cross page and chunk boundaries prefill in chunks, one
riding mixed steps beside a running decode, then everything decodes through
the ONE-leaf cache and the decode window. Every logits array the model
functions produce is compared with the reference's one full forward pass,
which runs attention in the EXPANDED form where the served path runs the
absorbed one.

The limit is shown to be tight: the same comparison fails by a wide margin
under each way of serving another model under this one's name. The loader
is held to transformers' own DeepseekV3 implementation on a tiny checkpoint.
"""
import dataclasses
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.config import (
    EngineConfig, ModelConfig, refuse_unserved, with_kv_rows,
)
from dynamo_tpu.engine.engine import NativeEngine
from dynamo_tpu.models import llama, reference
from dynamo_tpu.models.loader import (
    config_from_hf, deinterleave_rope, load_model_dir)
from dynamo_tpu.observability.ledger import LEDGER_STATS
from dynamo_tpu.ops import attention as attn_ops
from tests.test_olmoe import ENGINE_KW, Recorder, drive

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY = ModelConfig(
    name="tiny-moonlight", vocab_size=128, hidden_size=64,
    intermediate_size=32, dense_intermediate_size=96, first_dense_layers=1,
    num_layers=3, num_heads=4, num_kv_heads=4, head_dim=16,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
    query_scale=24 ** -0.5, rope_theta=50000.0, rms_norm_eps=1e-5,
    max_model_len=256, num_experts=16, num_experts_per_tok=4,
    norm_topk_prob=True, moe_scoring="sigmoid", moe_router_bias=True,
    moe_routed_scale=2.446, shared_expert_size=64, dtype="float32")

# One reading a comparison in float32: the largest, over positions, of
# max |logit difference| over the vocabulary (logits are O(1)). Both sides
# compute in float32 from the same weights; they differ in summation order
# (paged attention, the grouped matmul) and in the FORM of attention: the
# served path scores q_nope W_UK^T against the latent, the reference
# rebuilds per-head keys. The served path read a largest 7.2e-6 and a
# median 2.4e-6 on this CPU (seed 0), so 1e-4 is fourteen times the worst;
# the mildest mutation below (the bias also weighing) reads 1.05, ten
# thousand times the limit, the others 2.3 to 5.9. bfloat16 rounds every
# activation and the stored latent to 8 bits of mantissa and now and then
# flips a near-tie between the 4th and 5th expert, which here moves a
# position by a renormalised weight of about a quarter times 2.446 of one
# expert's output: 2 to 4 of 152 positions read 0.4 to 2.6 (seeds 0, 1)
# while the 90th percentile is 0.062 and the median 0.037 on both. So the
# bfloat16 readings are the 90th percentile and the median, at 0.15 and
# 0.08; a wrong router or norm reads a median over 0.5 (as OLMoE's did).
TOL = {"float32": (1e-4, 1e-4), "bfloat16": (0.15, 0.08)}
REQUESTS = ((70, 10), (37, 9), (21, 6))


def served_run(monkeypatch, cfg, seed=0):
    """(recorded (token, position, logits), sequences, the engine)."""
    rec = Recorder(monkeypatch)
    eng = NativeEngine(cfg, EngineConfig(**ENGINE_KW), seed=seed)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(2, cfg.vocab_size, n).tolist()
               for n, _ in REQUESTS]
    outs = drive(eng, prompts, [g for _, g in REQUESTS])
    assert [len(o) for o in outs] == [g for _, g in REQUESTS]
    return rec.entries, [p + o for p, o in zip(prompts, outs)], eng


def readings(entries, seqs, want):
    """(largest, median) over served positions of max |logit difference|
    from `want` (a [T, V] array a sequence); every fed position compared."""
    found, seen = [], [set() for _ in seqs]
    for token, pos, logits in entries:
        errs = [(float(np.max(np.abs(logits - want[i][pos]))), i)
                for i, s in enumerate(seqs)
                if pos < len(s) and s[pos] == token]
        assert errs, f"token {token} at {pos} belongs to no request"
        err, who = min(errs)
        seen[who].add(pos)
        found.append(err)
    for s, got in zip(seqs, seen):
        assert got >= set(range(len(s) - 1))
    return (max(found), float(np.median(found)),
            float(np.percentile(found, 90)))


def reference_logits(params, seqs, cfg=TINY, **arch_changes):
    arch = {**reference.arch_kwargs(cfg), **arch_changes}
    return [np.asarray(reference.forward(params, jnp.asarray(s), **arch))
            for s in seqs]


@pytest.fixture(scope="module")
def served_f32():
    """One float32 run of the served path, shared by the comparison and
    by every mutation of what it is compared with."""
    with pytest.MonkeyPatch.context() as mp:
        ledger0 = LEDGER_STATS.snapshot()
        entries, seqs, eng = served_run(mp, TINY)
        params = jax.device_get(eng.params)
        m = eng.metrics()
        moe = {k: v - ledger0[k] for k, v in LEDGER_STATS.snapshot().items()
               if k.startswith("moe_")}
        stats = dict(mixed=m.mixed_steps, windows=m.decode_windows,
                     routed=eng.moe_routed_tokens,
                     dropped=eng.moe_dropped_tokens,
                     cache={k: v.shape for k, v in eng.cache.items()},
                     page_bytes=m.kv_page_bytes,
                     kv_tokens=LEDGER_STATS.attn_kv_tokens_total,
                     kv_slots=LEDGER_STATS.attn_kv_slots_total,
                     kv_bytes=LEDGER_STATS.kv_bytes_per_token, moe=moe)
    return entries, seqs, params, stats


def test_served_logits_match_the_plain_reference(served_f32):
    """forward() in chunks and mixed steps, then decode through the
    one-leaf cache and the window, against the reference's full pass."""
    entries, seqs, params, stats = served_f32
    got = readings(entries, seqs, reference_logits(params, seqs))
    assert all(r < t for r, t in zip(got[:2], TOL["float32"])), got
    assert stats["mixed"] > 0 and stats["windows"] > 0, stats
    # the routed experts are counted as OLMoE's are, nothing dropped
    assert stats["routed"] > 0 and stats["dropped"] == 0


def test_served_logits_match_the_plain_reference_in_bfloat16(monkeypatch):
    cfg = dataclasses.replace(TINY, dtype="bfloat16")
    entries, seqs, eng = served_run(monkeypatch, cfg)
    _, median, p90 = readings(
        entries, seqs, reference_logits(jax.device_get(eng.params), seqs))
    assert p90 < TOL["bfloat16"][0] and median < TOL["bfloat16"][1], (
        p90, median)


# -- each way of serving another model fails, by a wide margin -----------------

def _mutant_attention(rope_all=False, skip_latent_norm=False, scale=None):
    """reference.attention_mla's lines with one thing changed (nothing
    changed: the same numbers, test_the_mutant_is_the_reference)."""
    def attention(x, lp, *, num_heads, head_dim, kv_lora_rank,
                  qk_nope_head_dim, qk_rope_head_dim, rope_theta,
                  rms_norm_eps, rope_interleaved=False):
        t, h, r = x.shape[0], num_heads, kv_lora_rank
        dn, dr = qk_nope_head_dim, qk_rope_head_dim
        q = (x @ lp["wq"]).reshape(t, h, dn + dr)
        ckv = x @ lp["wkv_a"]
        c = ckv[:, :r] if skip_latent_norm else reference.rms_norm(
            ckv[:, :r], lp["kv_a_norm"], rms_norm_eps)
        kv = (c @ lp["wkv_b"]).reshape(t, h, dn + head_dim)
        positions = jnp.arange(t)
        k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
            ckv[:, None, r:], (t, h, dr))], -1)
        if rope_all:        # the whole head rotated, as plain MHA does
            q = reference.rope(q, positions, rope_theta)
            k = reference.rope(k, positions, rope_theta)
        else:
            q = jnp.concatenate([q[..., :dn], reference.rope(
                q[..., dn:], positions, rope_theta)], -1)
            k = jnp.concatenate([k[..., :dn], reference.rope(
                k[..., dn:], positions, rope_theta)], -1)
        scores = jnp.einsum("qhd,khd->hqk", q, k) * (
            scale(dn, dr) if scale else (dn + dr) ** -0.5)
        causal = positions[None, :] <= positions[:, None]
        probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
        out = jnp.einsum("hqk,khd->qhd", probs, kv[..., dn:])
        return out.reshape(t, h * head_dim) @ lp["wo"]
    return attention


def _bias_also_weighs(x, lp, *, num_experts_per_tok, norm_topk_prob,
                      moe_scoring, moe_routed_scale):
    pick = jax.nn.sigmoid(x @ lp["router"]) + lp["router_bias"]
    _, chosen = jax.lax.top_k(pick, num_experts_per_tok)
    w = pick * jnp.sum(jax.nn.one_hot(chosen, pick.shape[-1]), 1)
    return w / (jnp.sum(w, -1, keepdims=True) + 1e-20) * moe_routed_scale


def _without_shared(params):
    return {**params, "layers": {k: v for k, v in params["layers"].items()
                                 if not k.startswith("ws_")}}


def _lead_as_expert_layer(params):
    """Layer 0 given an expert block (layer 1's) where its dense MLP is."""
    moe = {k: v[:1] for k, v in params["layers"].items()
           if k.startswith(("router", "w_", "ws_"))}
    lead = {k: v for k, v in params["dense_layers"].items()
            if not k.startswith("w_")}
    return {**params, "dense_layers": {**lead, **moe}}


MUTATIONS = {
    "softmax_for_sigmoid": dict(arch=dict(moe_scoring="softmax")),
    "bias_also_weighs": dict(
        patch=("router_weights", _bias_also_weighs)),
    "no_routed_scale": dict(arch=dict(moe_routed_scale=1.0)),
    "no_shared_expert": dict(params=_without_shared),
    "rope_over_the_whole_head": dict(
        patch=("attention_mla", _mutant_attention(rope_all=True))),
    "kv_a_layernorm_skipped": dict(
        patch=("attention_mla", _mutant_attention(skip_latent_norm=True))),
    "scale_from_the_nope_width": dict(
        patch=("attention_mla",
               _mutant_attention(scale=lambda dn, dr: dn ** -0.5))),
    "dense_lead_made_an_expert_layer": dict(params=_lead_as_expert_layer),
}


@pytest.mark.parametrize("name", sorted(MUTATIONS))
def test_the_tolerance_is_tight(monkeypatch, served_f32, name):
    """The served path against the reference WITH one thing changed (the
    distance is the same whichever side is changed, and this way the
    engine runs once): the largest difference is at least a hundred times
    the limit."""
    entries, seqs, params, _ = served_f32
    change = MUTATIONS[name]
    if "patch" in change:
        monkeypatch.setattr(reference, *change["patch"])
    params = change.get("params", lambda p: p)(params)
    want = reference_logits(params, seqs, **change.get("arch", {}))
    largest = readings(entries, seqs, want)[0]
    assert largest > 100 * TOL["float32"][0], (name, largest)


def test_the_mutant_is_the_reference(served_f32):
    _, seqs, params, _ = served_f32
    lp = {k: jnp.asarray(v[0]) for k, v in params["layers"].items()}
    x = jnp.asarray(np.random.default_rng(1).normal(size=(12, 64)),
                    jnp.float32)
    sizes = dict(num_heads=4, head_dim=16, kv_lora_rank=32,
                 qk_nope_head_dim=16, qk_rope_head_dim=8,
                 rope_theta=50000.0, rms_norm_eps=1e-5)
    np.testing.assert_allclose(
        _mutant_attention()(x, lp, **sizes),
        reference.attention_mla(x, lp, **sizes), rtol=1e-6, atol=1e-6)


def test_the_absorbed_form_is_the_expanded_one():
    """One layer's attention, no engine: models/llama's absorbed halves
    around the paged attention op, against reference.attention_mla."""
    params = llama.init_params(jax.random.PRNGKey(3), TINY)
    lp = {k: v[0] for k, v in params["layers"].items()}
    t = 21
    x = jnp.asarray(np.random.default_rng(2).normal(size=(1, t, 64)),
                    jnp.float32)
    positions = jnp.arange(t, dtype=jnp.int32)[None]
    q, row, v = llama.layer_front(x, lp, TINY, positions, (4, 4))
    assert v is None and q.shape == (1, t, 4, 40) and row.shape == (1, t, 1, 40)
    # the row is key and value at once; _mla_out keeps its first 32 columns
    rows = attn_ops.dense_causal_attention(
        q, row, row, positions, q_scale=TINY.query_scale)
    out = llama._mla_out(rows, lp, TINY).reshape(t, -1) @ lp["wo"]
    xn = reference.rms_norm(x[0], lp["attn_norm"], 1e-5)
    want = reference.attention_mla(
        xn, lp, num_heads=4, head_dim=16, rope_theta=50000.0,
        rms_norm_eps=1e-5, **reference.arch_kwargs(TINY)["mla"])
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)


# -- the cache ------------------------------------------------------------------

def bench_config(name="moonlight-16b-a3b"):
    with open(os.path.join(ROOT, "benchmark", "configs", name,
                           "config.json")) as f:
        return json.load(f)


def test_the_cache_is_one_leaf_of_576_at_the_published_widths():
    cfg = config_from_hf(bench_config(), name="moonlight")
    assert cfg.num_layers == 9
    # the MODEL's row: 512 latent + 64 rope values a token and layer, and
    # the bytes a step must read a token of context (the gauge
    # `llm_engine_kv_bytes_per_token`, `attn.kv_read_mb`, the window's
    # roofline). A raw configuration's pool is that wide
    shapes = jax.eval_shape(lambda: llama.init_cache(cfg, 8, 64))
    assert {k: v.shape for k, v in shapes.items()} == {
        "k": (9, 1, 8, 64, 576)}
    assert cfg.kv_bytes_per_token() == 9 * 576 * 2 == 10368
    # what an engine STORES (engine/config.kv_row_lanes, PR 53): the row
    # in five whole 128-lane tiles, 64 zero lanes, a ninth more pool
    # bytes (`llm_engine_kv_row_lanes`, `kv_page_bytes`); the model's
    # figure does not move with it
    served = with_kv_rows(cfg)
    assert (served.kv_row_lanes, served.kv_row_pad) == (640, 64)
    shapes = jax.eval_shape(lambda: llama.init_cache(served, 8, 64))
    assert {k: v.shape for k, v in shapes.items()} == {
        "k": (9, 1, 8, 64, 640)}
    assert shapes["k"].dtype == jnp.bfloat16
    assert served.kv_bytes_per_token() == 10368
    # what expanded keys and values would hold
    assert 9 * 16 * (192 + 128) * 2 == 92160
    assert set(llama.cache_shardings(cfg)) == {"k"}
    # the layer kinds, split once
    assert llama.layer_groups(cfg) == (("dense_layers", 0, 1, True),
                                       ("layers", 1, 8, False))
    tree = jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), cfg))
    assert tree["dense_layers"]["w_gate"].shape == (1, 2048, 11264)
    assert tree["layers"]["w_gate"].shape == (8, 64, 2048, 1408)
    assert tree["layers"]["ws_down"].shape == (8, 2816, 2048)
    assert tree["layers"]["router_bias"].shape == (8, 64)
    assert tree["layers"]["wkv_b"].shape == (8, 512, 16 * 256)
    assert "wk" not in tree["layers"] and "router" not in tree["dense_layers"]
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))
    assert n == 5_432_847_360
    specs = llama.param_shardings(cfg)
    assert {g: set(tree[g]) for g in ("dense_layers", "layers")} == {
        g: set(specs[g]) for g in ("dense_layers", "layers")}


def test_the_engine_stores_no_expanded_keys_or_values(served_f32):
    *_, stats = served_f32
    # [L, 1 head, pages, page size, latent 32 + rope 8 in one lane tile]:
    # a page's bytes are the pool's (the allocator's figure), a token's
    # the model's
    assert stats["cache"] == {"k": (3, 1, 64, 16, 128)}
    assert stats["page_bytes"] == 3 * 16 * 128 * 4
    assert stats["kv_bytes"] == 3 * 40 * 4


def test_the_attention_counters_follow_the_plans(served_f32):
    """`llm_engine_attn_kv_tokens_total` <= `_slots_total`: what the rows
    attend to against what the gather path reads for them."""
    *_, stats = served_f32
    assert 0 < stats["kv_tokens"] < stats["kv_slots"]


def test_a_windows_experts_are_counted_apart(served_f32):
    """`llm_engine_moe_window_*_total`: the experts hit and the layer
    calls of decode windows alone, which is what sets the expert bytes a
    window step reads (device.mla_window_roofline)."""
    *_, stats = served_f32
    moe = stats["moe"]
    calls, hit = (moe["moe_window_layer_calls_total"],
                  moe["moe_window_experts_hit_total"])
    assert 0 < calls < moe["moe_layer_calls_total"]
    assert hit < moe["moe_experts_hit_total"]
    # a whole number of (expert layer, window step) pairs; a step holds at
    # most one row a request, so it touches at most rows x k experts
    assert calls % (TINY.num_layers - TINY.first_dense_layers) == 0
    assert 1 <= hit / calls <= min(
        TINY.num_experts, len(REQUESTS) * TINY.num_experts_per_tok)


def test_seeded_init_would_show_a_skipped_leaf():
    params = llama.init_params(jax.random.PRNGKey(0), TINY)
    bias = np.asarray(params["layers"]["router_bias"])
    norm = np.asarray(params["layers"]["kv_a_norm"])
    assert bias.dtype == np.float32 and 0.05 < bias.std() < 0.2
    assert 0.05 < (norm - 1).std() < 0.2


@pytest.mark.parametrize("what,kwargs", [
    ("kv_quant", dict(engine=dict(kv_quant="int8"))),
    ("decode_kernel", dict(model=dict(decode_kernel="interpret"))),
    ("host-pages", dict(engine=dict(host_pages=8))),
    ("quant", dict(model=dict(quant="int8"))),
])
def test_what_a_one_leaf_cache_cannot_serve_is_refused(what, kwargs):
    cfg = dataclasses.replace(TINY, **kwargs.get("model", {}))
    ecfg = EngineConfig(**{**ENGINE_KW, **kwargs.get("engine", {})})
    with pytest.raises(ValueError, match="ONE cache leaf") as err:
        NativeEngine(cfg, ecfg)
    assert what in str(err.value)


def test_a_mesh_is_refused_in_the_same_place():
    from jax.sharding import Mesh
    mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(1, 2), ("dp", "tp"))
    with pytest.raises(ValueError, match="ONE cache leaf.*mesh"):
        refuse_unserved(TINY, EngineConfig(), mesh)
    # every other model passes
    refuse_unserved(
        ModelConfig(kv_quant="int8"), EngineConfig(host_pages=4), mesh)


def test_the_model_carries_its_named_scopes():
    cfg = TINY
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    cache = llama.init_cache(cfg, 4, 16)
    meta = llama.AttnMetadata(
        positions=jnp.arange(8, dtype=jnp.int32)[None],
        page_table=jnp.arange(4, dtype=jnp.int32)[None],
        kv_lens=jnp.asarray([8], jnp.int32),
        write_idx=jnp.arange(8, dtype=jnp.int32)[None])
    text = jax.jit(lambda p, c, t: llama.forward(p, cfg, t, c, meta)
                   ).lower(params, cache, jnp.zeros((1, 8), jnp.int32)
                           ).as_text(debug_info=True)
    for scope in ("attention.mla.q", "attention.mla.latent",
                  "attention.mla.absorb", "attention.mla.out", "moe.shared",
                  "mlp.dense_lead", "moe.route", "moe.experts"):
        assert scope in text, scope


# -- the benchmark's copy --------------------------------------------------------

def bench_reference():
    path = os.path.join(ROOT, "benchmark", "reference", "moonlight.py")
    spec = importlib.util.spec_from_file_location("bench_ref_moonlight", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


TINY_HF = {
    "architectures": ["DeepseekV3ForCausalLM"], "vocab_size": 128,
    "hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 32,
    "num_hidden_layers": 3, "num_attention_heads": 4,
    "num_key_value_heads": 4, "kv_lora_rank": 32, "q_lora_rank": None,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "n_routed_experts": 16, "n_shared_experts": 2, "num_experts_per_tok": 4,
    "first_k_dense_replace": 1, "moe_layer_freq": 1, "n_group": 1,
    "topk_group": 1, "norm_topk_prob": True, "scoring_func": "sigmoid",
    "topk_method": "noaux_tc", "routed_scaling_factor": 2.446,
    "rope_theta": 50000, "rms_norm_eps": 1e-5,
    "max_position_embeddings": 256, "tie_word_embeddings": False,
    "num_nextn_predict_layers": 0, "hidden_act": "silu",
    "attention_bias": False}


def test_the_published_keys_give_the_tiny_configuration():
    got = config_from_hf(TINY_HF, name="tiny-moonlight")
    assert got == dataclasses.replace(TINY, dtype="bfloat16")


def test_the_benchmarks_copy_of_the_reference_has_not_drifted():
    """benchmark/reference/moonlight.py imports nothing from dynamo_tpu, so
    it is a copy; the two give identical logits, and its blocked form the
    same log-probabilities at the rows asked for."""
    mod = bench_reference()
    params = llama.init_params(jax.random.PRNGKey(5), TINY)
    tokens = np.random.default_rng(5).integers(0, TINY.vocab_size, 40)
    ours = reference.forward(params, jnp.asarray(tokens),
                             **reference.arch_kwargs(TINY))
    theirs = mod.forward(params, tokens, TINY_HF)
    np.testing.assert_array_equal(np.asarray(ours), np.asarray(theirs))
    rows = [3, 17, 39]
    blocked = mod.forward_blocked(params, tokens, TINY_HF, positions=rows,
                                  expert_block=5, head_block=3,
                                  vocab_block=48)
    np.testing.assert_allclose(
        blocked, jax.nn.log_softmax(ours, -1)[jnp.asarray(rows)],
        rtol=1e-5, atol=1e-5)


# -- the loader -------------------------------------------------------------------

def test_rope_columns_are_deinterleaved_once_at_load():
    """(x W)[perm] == x W[:, perm], and rotating halves of the permuted
    vector is the published interleaved rotation."""
    perm = deinterleave_rope(8)
    assert perm.tolist() == [0, 2, 4, 6, 1, 3, 5, 7]
    x = jnp.asarray(np.random.default_rng(0).normal(size=(5, 2, 8)),
                    jnp.float32)
    np.testing.assert_array_equal(reference.deinterleave(x), x[..., perm])


def test_checkpoint_parity_with_transformers(tmp_path):
    """A tiny random DeepseekV3 checkpoint written by transformers, read
    by the loader (tensor names, transposes, the rope columns' order, the
    two layer groups), served by llama.forward: the logits of
    transformers' own forward pass; and the reference reads the
    checkpoint's own column order with `rope_interleaved`."""
    torch = pytest.importorskip("torch")
    from transformers import DeepseekV3Config, DeepseekV3ForCausalLM
    from tests.test_hf_loader import hf_logits, our_logits
    hf = DeepseekV3Config(**{k: v for k, v in TINY_HF.items()
                             if k != "architectures"})
    torch.manual_seed(0)
    model = DeepseekV3ForCausalLM(hf).eval()
    with torch.no_grad():   # a fresh model's selection bias is all zero
        for layer in model.model.layers[1:]:
            layer.mlp.gate.e_score_correction_bias.normal_(0.0, 0.1)
    path = tmp_path / "model"
    model.save_pretrained(path, safe_serialization=True)
    cfg, params = load_model_dir(str(path), dtype="float32")
    assert cfg == dataclasses.replace(TINY, name="model")
    tokens = np.random.default_rng(0).integers(1, 128, 24).astype(np.int32)
    theirs = hf_logits(model, tokens)
    np.testing.assert_allclose(our_logits(cfg, params, tokens), theirs,
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(
        reference.forward(params, tokens, **reference.arch_kwargs(cfg)),
        theirs, rtol=2e-4, atol=2e-4)
    # the checkpoint's own column order: undo the loader's permutation
    inv = np.argsort(deinterleave_rope(8))
    raw = jax.tree.map(np.asarray, params)
    for group in ("dense_layers", "layers"):
        wq = raw[group]["wq"].reshape(-1, 64, 4, 24).copy()
        wq[..., 16:] = wq[..., 16:][..., inv]
        raw[group]["wq"] = wq.reshape(-1, 64, 96)
        wkv = raw[group]["wkv_a"].copy()
        wkv[..., 32:] = wkv[..., 32:][..., inv]
        raw[group]["wkv_a"] = wkv
    arch = reference.arch_kwargs(cfg)
    arch["mla"] = dict(arch["mla"], rope_interleaved=True)
    np.testing.assert_allclose(reference.forward(raw, tokens, **arch),
                               theirs, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("key,value", [
    ("q_lora_rank", 1536), ("n_group", 8), ("topk_group", 4),
    ("rope_scaling", {"type": "yarn", "factor": 40}),
    ("num_nextn_predict_layers", 1), ("scoring_func", "tanh"),
    ("topk_method", "group_limited_greedy"), ("moe_layer_freq", 2)])
def test_the_loader_refuses_what_is_not_modelled(key, value):
    with pytest.raises(ValueError, match="not supported"):
        config_from_hf({**TINY_HF, key: value})
