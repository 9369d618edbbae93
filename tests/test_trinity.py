"""Trinity-Mini (`afmoe`): a dense lead IN FRONT of the window-pool period
loop, an RMSNorm over each head's q and k, RoPE on the sliding layers and
no positional embedding on the full ones, a sigmoid output gate before
`wo`, four norms a block, the embeddings times sqrt(hidden), 16 experts at
2 a token behind a sigmoid router with a selection bias, renormalised and
scaled, plus a shared expert; the served path against the plain reference
(dynamo_tpu/models/reference.py), on LOGITS.

Tiny widths with what the chip's cut cannot hold: TWO leads and TWO
periods, S S | S S S F | S S S F (the chip runs 1 + 1), a window of 16
tokens over pages of 4 (a 70-token prompt is four windows long and hands
back a page every fourth token). 16 experts and not 8: at 8 and fewer the
engine takes the capacity form (ModelConfig.moe_dropless), which drops,
and the published model's 128 take the dropless one.
"""
import dataclasses
import functools
import hashlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.config import (
    EngineConfig, RopeParams, refuse_unserved, with_kv_rows,
)
from dynamo_tpu.engine.engine import NativeEngine
from dynamo_tpu.engine.scheduler import Scheduler
from dynamo_tpu.models import llama, reference
from dynamo_tpu.models.loader import config_from_hf
from tests.test_ling import readings
from tests.test_olmoe import ENGINE_KW, Recorder, drive

PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
# the catalog row's `config` (Trinity-Mini), written out here: the catalog
# is not part of the repo and is not read
PUBLISHED = {
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 6144,
    "layer_types": PERIOD * 8, "load_balance_coeff": 0.001,
    "max_position_embeddings": 131072, "model_type": "afmoe",
    "moe_intermediate_size": 1024, "mup_enabled": True, "n_group": 1,
    "num_attention_heads": 32, "num_dense_layers": 2,
    "num_expert_groups": 1, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 32, "num_key_value_heads": 4,
    "num_limited_groups": 1, "num_shared_experts": 1, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 10000, "route_norm": True,
    "route_scale": 2.826, "score_func": "sigmoid", "sliding_window": 2048,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_mm": True,
    "vocab_size": 200192}
HF = dict(
    PUBLISHED, architectures=["AfmoeForCausalLM"],
    vocab_size=128, hidden_size=64, intermediate_size=128,
    num_hidden_layers=10, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, max_position_embeddings=512, sliding_window=16,
    layer_types=["sliding_attention"] * 2 + PERIOD * 2, num_dense_layers=2,
    num_experts=16, num_experts_per_tok=2, moe_intermediate_size=32)
TINY = dataclasses.replace(config_from_hf(HF, "tiny-trinity"),
                           dtype="float32")
KW = dict(ENGINE_KW, page_size=4, num_pages=128)
# (prompt, generated): as tests/test_mellum.py, whose window and pages
# these are: 70 is four windows and eighteen pages long and takes three
# 32-token chunks; the others arrive while it decodes, so their chunks
# ride mixed steps beside decode rows whose tables start deep in their
# context
REQUESTS = ((70, 24), (37, 9), (21, 6))

# Two readings a comparison in float32, over served positions, of max
# |logit difference| over the vocabulary: the largest, held to 1e-4, and
# the median, held to 3e-5 (Mellum's limits). Both sides compute in
# float32 from the same weights and differ in summation order (paged
# attention over a table that starts mid-context, the split base + window
# + self softmax, sorted dispatch against every expert masked). Read on
# this CPU (seed 0): largest 6.7e-6, median 2.6e-6, so the limits are
# fifteen and eleven times the readings. Each mutation is judged on the
# median, which nothing but a real change of the function moves, and must
# read 100 times its limit: the mildest reads 0.55 (the full layers
# rotated), eighteen thousand times the limit; the others 0.89 to 3.7.
TOL = (1e-4, 3e-5)


def served_run(monkeypatch, seed=0, **engine_kw):
    rec = Recorder(monkeypatch)
    eng = NativeEngine(TINY, EngineConfig(**dict(KW, **engine_kw)),
                       seed=seed)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(2, TINY.vocab_size, n).tolist()
               for n, _ in REQUESTS]
    outs = drive(eng, prompts, [g for _, g in REQUESTS])
    assert [len(o) for o in outs] == [g for _, g in REQUESTS]
    return rec.entries, [p + o for p, o in zip(prompts, outs)], eng


def reference_logits(params, seqs, **arch_changes):
    arch = {**reference.arch_kwargs(TINY), **arch_changes}
    return [np.asarray(reference.forward(params, jnp.asarray(s), **arch))
            for s in seqs]


@pytest.fixture(scope="module")
def served():
    """One float32 run of the served path (prefill chunks, mixed steps,
    decode windows, page releases), shared by the comparison and by every
    mutation of what it is compared with."""
    with pytest.MonkeyPatch.context() as mp:
        entries, seqs, eng = served_run(mp)
        params = jax.device_get(eng.params)
        m = eng.metrics()
        stats = dict(mixed=m.mixed_steps, windows=m.decode_windows,
                     cache={k: v.shape for k, v in eng.cache.items()},
                     released=eng.scheduler.window_released)
    return entries, seqs, params, stats


def test_served_logits_match_the_plain_reference(served):
    entries, seqs, params, stats = served
    largest, median, _ = readings(entries, seqs,
                                  reference_logits(params, seqs))
    assert largest < TOL[0] and median < TOL[1], (largest, median)
    # through every step kind, and past many releases
    assert stats["mixed"] > 0 and stats["windows"] > 0
    assert stats["released"] >= 20, stats["released"]
    # the window pool's layer axis: the two leads, then the six sliding
    # layers of the periods; the full pool's: the two full layers
    hkv, hd, ps = TINY.num_kv_heads, TINY.head_dim, KW["page_size"]
    wpages = (KW["max_slots"] + EngineConfig().max_prefill_batch) * 13
    assert stats["cache"] == {
        "k": (2, hkv, KW["num_pages"], ps, hd),
        "v": (2, hkv, KW["num_pages"], ps, hd),
        "wk": (8, hkv, wpages, ps, hd), "wv": (8, hkv, wpages, ps, hd)}


def test_served_logits_match_with_a_window_in_flight(monkeypatch):
    """The default pipeline: a window dispatched against the table of
    the plan before still gathers pages that the commit in between handed
    back. Every key in them is outside its masks."""
    entries, seqs, eng = served_run(monkeypatch, pipeline_depth=2)
    largest, median, _ = readings(
        entries, seqs, reference_logits(jax.device_get(eng.params), seqs),
        strays=True, every_position=False)
    assert largest < TOL[0] and median < TOL[1], (largest, median)
    assert eng.metrics().pipeline_overlapped > 0


def _without(params, drop):
    """The tree less the layer leaves that `drop` names."""
    return {k: ({n: a for n, a in v.items() if not drop(n)}
                if isinstance(v, dict) else v) for k, v in params.items()}


def _whole_projection_norm(params):
    """Each head norm's weights repeated over the heads: the leaves OLMoE's
    norm over the whole projection reads."""
    h, hkv = TINY.num_heads, TINY.num_kv_heads
    return {k: (dict(v, q_norm=np.tile(v["q_norm"], (1, h)),
                     k_norm=np.tile(v["k_norm"], (1, hkv)))
                if isinstance(v, dict) else v) for k, v in params.items()}


def _bias_weighs(x, lp, *, num_experts_per_tok, norm_topk_prob,
                 moe_scoring, moe_routed_scale, **_):
    """`reference.router_weights` with the selection bias left in the
    weights: the router served wrong."""
    scores = jax.nn.sigmoid(x @ lp["router"]) + lp["router_bias"]
    _, chosen = jax.lax.top_k(scores, num_experts_per_tok)
    weights = scores * jnp.sum(jax.nn.one_hot(
        chosen, scores.shape[-1], dtype=jnp.float32), 1)
    return weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20) \
        * moe_routed_scale


# name -> (what the reference is given instead of the params, changes of
# its arguments, a function of `reference` to replace)
MUTATIONS = {
    "the full layers are rotated": (None, dict(
        rope_full=dataclasses.asdict(TINY.rope_sliding)), None),
    "the sliding layers are not rotated": (None, dict(
        rope_sliding=dataclasses.asdict(TINY.rope_full)), None),
    "the window is dropped": (None, dict(sliding_window=0), None),
    "the gate is skipped": (
        lambda p: _without(p, lambda n: n == "w_out_gate"), {}, None),
    "the head norm is the whole-projection norm": (
        _whole_projection_norm, dict(qk_norm=True), None),
    "the bias is used as a weight": (None, {}, _bias_weighs),
    "the embeddings are not scaled": (None, dict(embed_scale=0.0), None),
    "the post norms are skipped": (
        lambda p: _without(p, lambda n: n.startswith("post_")), {}, None),
    "route_scale is left out": (None, dict(moe_routed_scale=1.0), None),
}


@pytest.mark.parametrize("name", sorted(MUTATIONS))
def test_the_tolerance_is_tight(served, monkeypatch, name):
    """The same comparison FAILS against a reference that is wrong in one
    of the ways this model can be served wrong."""
    entries, seqs, params, _ = served
    change, arch, router = MUTATIONS[name]
    if router is not None:
        monkeypatch.setattr(reference, "router_weights", router)
    _, median, _ = readings(
        entries, seqs, reference_logits(
            change(params) if change else params, seqs, **arch),
        every_position=False)
    assert median > 100 * TOL[1], (name, median)


# -- a lead in front of the loop ------------------------------------------------

def _types(pattern):
    return tuple("sliding_attention" if c == "S" else "full_attention"
                 for c in pattern)


def test_a_lead_has_stacks_of_its_own_before_the_period_loop():
    """S S | S S S F x 2: the lead is no part of the kind stacks, the
    period is found behind it, and the lead's sliding layers lie first in
    the window pool's layer axis."""
    runs = llama.layer_runs(TINY)
    assert [(r.key, r.first, r.count, r.dense, r.kind, r.store_first)
            for r in runs] == [("lead0", 0, 2, True, "swa", 0),
                               ("run0", 2, 6, False, "swa", 2),
                               ("run1", 5, 2, False, "mha", 0)]
    assert llama.layer_period(TINY) == llama.LayerPeriod(
        2, ((1, 3, 0, 3), (2, 1, 0, 1)), 1)
    shapes = jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), TINY))
    # a dense MLP of its own width in the lead, experts behind it
    assert shapes["lead0"]["w_gate"].shape == (2, 64, 128)
    assert shapes["run0"]["w_gate"].shape == (6, 16, 64, 32)
    assert shapes["run1"]["w_gate"].shape == (2, 16, 64, 32)
    assert "router" not in shapes["lead0"] and "ws_gate" in shapes["run0"]
    # a head's norm has one weight vector of head_dim; the gate is D x H hd
    assert shapes["run1"]["q_norm"].shape == (2, 16)
    assert shapes["run1"]["w_out_gate"].shape == (2, 64, 64)
    assert set(llama.param_shardings(TINY)["run0"]) == set(shapes["run0"])
    assert set(llama.param_shardings(TINY)["lead0"]) == set(shapes["lead0"])
    assert (TINY.num_window_layers, TINY.num_cache_layers) == (8, 2)
    # the chip's cut: one lead, one period
    chip = dataclasses.replace(TINY, num_layers=5, first_dense_layers=1,
                               layer_types=_types("SSSSF"))
    assert [(r.key, r.first, r.count, r.store_first)
            for r in llama.layer_runs(chip)] == [
        ("lead0", 0, 1, 0), ("run0", 1, 3, 1), ("run1", 4, 1, 0)]
    assert llama.layer_period(chip) == llama.LayerPeriod(
        1, ((1, 3, 0, 3), (2, 1, 0, 1)), 1)
    # a lead of both kinds is a stack a run of like kinds, each first in
    # its own store
    both = dataclasses.replace(TINY, num_layers=7, first_dense_layers=3,
                               layer_types=_types("SFSSSSF"))
    assert [(r.key, r.first, r.count, r.kind, r.store_first)
            for r in llama.layer_runs(both)] == [
        ("lead0", 0, 1, "swa", 0), ("lead1", 1, 1, "mha", 0),
        ("lead2", 2, 1, "swa", 1), ("run0", 3, 3, "swa", 2),
        ("run1", 6, 1, "mha", 1)]
    assert llama.layer_period(both).lead == 3
    # the published 32 layers: the 30 behind the lead of two are S F and
    # then S S S F x 7, which repeats nowhere: one period of sixteen parts
    # (served, but sixteen layer bodies a program: PERF.md section 7)
    full = dataclasses.replace(TINY, num_layers=32,
                               layer_types=tuple(PUBLISHED["layer_types"]))
    period = llama.layer_period(full)
    assert (period.count, len(period.parts), period.lead) == (1, 16, 1)
    # a model without a lead is what it was
    no_lead = dataclasses.replace(TINY, num_layers=8, first_dense_layers=0,
                                  layer_types=_types("SSSF" * 2))
    assert [(r.key, r.first, r.count, r.store_first)
            for r in llama.layer_runs(no_lead)] == [
        ("run0", 0, 6, 0), ("run1", 3, 2, 0)]
    assert llama.layer_period(no_lead) == llama.LayerPeriod(
        2, ((0, 3, 0, 3), (1, 1, 0, 1)))
    with pytest.raises(ValueError, match="first_dense_layers"):
        llama.layer_period(dataclasses.replace(TINY, first_dense_layers=10))


def test_a_lead_of_both_kinds_is_served_too(monkeypatch):
    """S F S | S S S F: three lead stacks, each layer's cache row at its
    own index of its kind's store, against the reference in the model's
    order."""
    both = dataclasses.replace(TINY, num_layers=7, first_dense_layers=3,
                               layer_types=_types("SFSSSSF"))
    monkeypatch.setattr(sys.modules[__name__], "TINY", both)
    entries, seqs, eng = served_run(monkeypatch)
    assert eng.cache["wk"].shape[0] == 5 and eng.cache["k"].shape[0] == 2
    largest, median, _ = readings(
        entries, seqs, reference_logits(jax.device_get(eng.params), seqs))
    assert largest < TOL[0] and median < TOL[1], (largest, median)


def test_a_window_pool_model_with_a_lead_and_nothing_else_new(monkeypatch):
    """What `layer_runs` mis-served before: a lead in front of a Mellum
    (softmax router, no gate, no head norm, YaRN on the full layers) was
    given experts. It is a dense MLP now, and the logits say so."""
    from tests.test_mellum import TINY as MELLUM
    led = dataclasses.replace(
        MELLUM, name="tiny-mellum-led", num_layers=9, first_dense_layers=1,
        dense_intermediate_size=96,
        layer_types=_types("S") + MELLUM.layer_types)
    assert [r.dense for r in llama.layer_runs(led)] == [True, False, False]
    monkeypatch.setattr(sys.modules[__name__], "TINY", led)
    entries, seqs, eng = served_run(monkeypatch)
    assert eng.params["lead0"]["w_gate"].shape == (1, 64, 96)
    largest, median, _ = readings(
        entries, seqs, reference_logits(jax.device_get(eng.params), seqs))
    assert largest < TOL[0] and median < TOL[1], (largest, median)


def test_no_rope_traces_no_op():
    """A kind whose table says "none" is handed to attention as the
    projection left it: no cos, no sin, no multiply by one."""
    assert llama.rope_table(TINY, "mha") is None
    assert llama.rope_table(TINY, "swa") == (10000.0, None, 1.0)
    one = jax.eval_shape(lambda: llama._init_layer_stack(
        jax.random.split(jax.random.PRNGKey(0), 12), TINY, 1, False, "mha"))
    lp = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype),
                      one)
    x = jax.ShapeDtypeStruct((2, 3, 64), jnp.float32)
    pos = jax.ShapeDtypeStruct((2, 3), jnp.int32)
    text = {kind: str(jax.make_jaxpr(lambda x, lp, pos: llama.layer_front(
        x, lp, TINY, pos, (4, 2), kind))(x, lp, pos))
        for kind in ("mha", "swa")}
    assert " cos " in text["swa"] and " sin " in text["swa"]
    assert " cos " not in text["mha"] and " sin " not in text["mha"]
    with pytest.raises(ValueError, match="longrope"):
        llama.rope_table(dataclasses.replace(TINY, rope_full=RopeParams(
            rope_type="longrope")), "mha")


# -- the loader -------------------------------------------------------------------

def test_the_loader_maps_every_key_of_the_published_config():
    cfg = config_from_hf(dict(PUBLISHED, architectures=["AfmoeForCausalLM"]),
                         "trinity-mini")
    want = dict(
        vocab_size=200192, hidden_size=2048, num_layers=32, num_heads=32,
        num_kv_heads=4, head_dim=128, rms_norm_eps=1e-5,
        max_model_len=131072, tie_word_embeddings=False, mlp_act="silu",
        sliding_window=2048, layer_types=tuple(PERIOD * 8),
        window_pool=True, rope_theta=10000.0,
        rope_sliding=RopeParams(theta=10000.0),
        rope_full=RopeParams(theta=10000.0, rope_type="none"),
        qk_norm="head", attn_out_gate=True, post_norms=True,
        embed_scale=2048 ** 0.5, norm_plus_one=False,
        num_experts=128, num_experts_per_tok=8, intermediate_size=1024,
        dense_intermediate_size=6144, first_dense_layers=2,
        shared_expert_size=1024, norm_topk_prob=True,
        moe_scoring="sigmoid", moe_router_bias=True,
        moe_routed_scale=2.826, moe_n_group=1, moe_topk_group=1,
        experts_held=0, attn_bias=False, attn_softcap=0.0, kv_lora_rank=0)
    assert {k: getattr(cfg, k) for k in want} == want
    assert cfg.moe_dropless and cfg.layer_windows() is None
    assert cfg.layer_kinds() == ("swa", "swa", "swa", "mha") * 8
    # every key of the row's config is mapped above, refused when it says
    # something unmodelled (below), or named by the loader as not read
    import inspect
    from dynamo_tpu.models import loader
    text = inspect.getsource(loader.afmoe_fields) \
        + inspect.getsource(loader.config_from_hf)
    unnamed = [k for k in PUBLISHED if f'"{k}"' not in text
               and f"`{k}`" not in text]
    assert unnamed == [], unnamed
    # TINY is the same mapping at tiny widths
    assert TINY.layer_kinds() == ("swa",) * 2 + ("swa", "swa", "swa",
                                                 "mha") * 2
    assert TINY.max_model_len == 512 and TINY.embed_scale == 8.0


@pytest.mark.parametrize("change,word", [
    (dict(n_group=2), "n_group"),
    (dict(num_expert_groups=4), "num_expert_groups"),
    (dict(topk_group=2), "topk_group"),
    (dict(num_limited_groups=2), "num_limited_groups"),
    (dict(hidden_act="gelu"), "hidden_act"),
    (dict(tie_word_embeddings=True), "tie_word_embeddings"),
    (dict(score_func="tanh"), "score_func"),
    (dict(num_nextn_predict_layers=1), "num_nextn_predict_layers"),
    (dict(mtp_num_layers=1), "mtp_num_layers"),
    (dict(attention_bias=True), "attention_bias"),
    (dict(rope_scaling={"rope_type": "yarn", "factor": 4}), "yarn"),
    (dict(layer_types=["chunked_attention"] * 10), "layer_types"),
    (dict(layer_types=None), "layer_types"),
    (dict(num_dense_layers=10), "num_dense_layers"),
    (dict(sliding_window=None), "sliding_window"),
])
def test_the_loader_refuses_by_key(change, word):
    with pytest.raises(ValueError, match=word):
        config_from_hf(dict(HF, **change))


def test_what_a_window_pool_is_not_served_with_is_still_refused():
    for kw, word in ((dict(spec_decode="ngram"), "spec_decode"),
                     (dict(host_pages=8), "tiers"),
                     (dict(kv_quant="int8"), "kv_quant")):
        with pytest.raises(ValueError, match=word):
            NativeEngine(TINY, EngineConfig(**dict(KW, **kw)))
    with pytest.raises(ValueError, match="decode_kernel"):
        refuse_unserved(dataclasses.replace(TINY, decode_kernel="on"))
    with pytest.raises(ValueError, match="quant"):
        refuse_unserved(dataclasses.replace(TINY, quant="int8"))
    # a checkpoint is refused: the catalog gives no tensor names
    from dynamo_tpu.models.loader import load_params_from_hf
    with pytest.raises(ValueError, match="no checkpoint mapping"):
        load_params_from_hf("/nonexistent", TINY)


def test_the_window_tables_width_follows_the_window():
    """2048 tokens: 34 pages beside a 64-token chunk or a decode window,
    41 at the largest chunk (Mellum's 1024: 18 and 25): a function of the
    config, no constant."""
    ecfg = EngineConfig(max_slots=8, num_pages=1024)
    sch = Scheduler(ecfg, window=(2048, 1))
    assert {sch.window_table_pages(c) for c in (1, 16, 32, 64)} == {34}
    assert sch.window_table_pages(ecfg.max_prefill_chunk) == 41
    assert Scheduler(ecfg, window=(1024, 1)).window_table_pages(64) == 18


# -- the older programs are the parent's --------------------------------------------

# sha256 (first 16 hex digits) of the jaxpr text of the engine's step
# program [8,16] and full decode window [8 rows] for four of the
# benchmark's rehearsal configurations, traced from the PARENT commit of
# PR 40 (2e87223) by the function below and equal on this tree: a dense
# GQA model, OLMoE (whole-projection QK-norm, dropless experts), Moonlight
# (latent attention, a dense lead, sigmoid router, shared experts) and
# Mellum (window pool, period loop, YaRN by kind). A PR that MEANS to
# change one of these programs replaces its digest (and says so); one
# that does not has changed a program it did not mean to. PR 41 replaced
# all eight: every program ends in the sampler tail, whose `keep_mask`
# lost its sort (with tests/test_sampler_tail.one_sort_keep_mask patched
# over it, all eight read PR 40's digests again: nothing else moved).
PARENT_PROGRAMS = {
    # PR 51 replaced these four, and MEANT to: 4 KV heads of 32 share one
    # 128-lane pool row (engine/config.kv_heads_per_row gives f = 4), so
    # `layer_front` forms rows, the queries are zero-extended and the
    # scale is named; with the rule patched to 1 they read PR 50's
    # 8b3a1bcc2cd60304 / f9966fa4a465991d / fc8c262d1fc40d22 /
    # c484f91bd464d383 again. The four of Moonlight and Mellum below have
    # 2 KV heads, keep a head a row and stand
    ("rehearsal-tiny", "step"): "d6d4e28b3a6aa3ba",
    ("rehearsal-tiny", "window"): "e8e6031c9fbd2901",
    ("rehearsal-tiny-olmoe", "step"): "fc391189438bccfd",
    ("rehearsal-tiny-olmoe", "window"): "214e56cb1f87ea0a",
    # PR 53 replaced Moonlight's two and Ling's two, and MEANT to: a
    # latent row's 32 + 8 values are stored in one whole 128-lane tile
    # (engine/config.kv_row_lanes), so `_mla_front` zero-extends q and
    # the row and the pool is [L, 1, P, ps, 128], and a latent window
    # gathers its base a (layer, page) an index (engine.gather_base);
    # with the rule patched to 0, and for the windows the base's `take`
    # along the page axis put back, they read the parent's
    # 285056b88b57c562 / 1f212b404bab2dbb / 6a79565f07f66d72 /
    # d8709fd5313f3673 again. No other moved
    ("rehearsal-tiny-moonlight", "step"): "c173084287903862",
    ("rehearsal-tiny-moonlight", "window"): "7718b8012f9b8fa7",
    ("rehearsal-tiny-mellum", "step"): "6921cab248da7e90",
    ("rehearsal-tiny-mellum", "window"): "826993a03ea7d66a",
    # PR 45: Ling (Kimi-Delta layers over state slots, one latent-attention
    # layer, experts), whose chunk rows' bookkeeping and convolution the
    # state-space mixer now shares (llama._chunk_group, conv_with_tail,
    # conv_one_token): traced from PR 45's parent (3e6f8f0) at 16 rows,
    # where the linear layers split a step's rows (at 8 they do not)
    ("rehearsal-tiny-ling", "step"): "c576e97dc97f4766",
    ("rehearsal-tiny-ling", "window"): "dc3f1b566de8abdf",
}
PROGRAM_ROWS = {"rehearsal-tiny-ling": 16}


def program_texts(name, rows=8, chunk=16, pages=8, base_pages=8):
    """{"step", "window"}: the jaxpr text of the engine's two programs for
    `benchmark/configs/<name>`, on abstract arguments (tools/pool_ops.
    build_programs' lists, without a mesh), object addresses masked."""
    import functools
    import json
    import os
    import re
    from dynamo_tpu.engine import engine as eng
    from dynamo_tpu.engine.scheduler import (
        window_ladder, window_table_pages,
    )
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", name,
                           "config.json")) as f:
        # the pool's rows as NativeEngine resolves them on one device
        cfg = with_kv_rows(config_from_hf(json.load(f), name=name))
    ecfg = EngineConfig()

    def arr(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype)
    params = jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), cfg))
    wtable = functools.partial(window_table_pages, ecfg, cfg.sliding_window)
    window_pages = (rows + ecfg.max_prefill_batch) \
        * wtable(ecfg.max_prefill_chunk) if cfg.window_pool else 0
    cache = jax.eval_shape(lambda: llama.init_cache(
        cfg, 64, ecfg.page_size, window_pages))
    names = ("wtable", "woff", "wwrite_idx") * bool(cfg.window_pool)
    state = ("state_slots",) * bool(cfg.state_leaves())
    if state:
        # a recurrent state rides the cache dict: a slot a row and one a
        # row of a prefill batch
        cache = {**cache, **jax.eval_shape(lambda: llama.init_state(
            cfg, rows + ecfg.max_prefill_batch))}

    def named(fn, names):
        if not names:
            return fn
        return lambda params, cache, *args: fn(
            params, cache, *args[:-len(names)],
            **dict(zip(names, args[-len(names):])))
    f32, vec = jnp.float32, arr((rows,))
    step = named(functools.partial(
        eng._engine_step, cfg, (), None, None, False, False, False, None),
        names + state)
    step_args = (params, cache, arr((rows, chunk)), arr((rows, chunk)),
                 arr((rows, pages)), vec, arr((rows, chunk)), vec,
                 arr((rows,), f32), vec, arr((rows,), f32), vec, vec, vec)
    nw = window_ladder(ecfg.decode_steps)[0]
    window = named(functools.partial(
        eng._engine_decode_window, cfg, (), None, nw, ecfg.page_size,
        False, False, False), names[:2] + state)
    window_args = (params, cache, vec, vec, arr((rows, pages)),
                   arr((rows, base_pages)), vec, arr((rows,), f32), vec,
                   arr((rows,), f32), vec, vec, vec, arr((rows,), jnp.bool_),
                   arr((rows, 0)))
    if cfg.window_pool:
        step_args += (arr((rows, wtable(chunk))), vec, arr((rows, chunk)))
        window_args += (arr((rows, wtable(1))), vec)
    if state:
        step_args += (vec,)
        window_args += (vec,)
    return {key: re.sub(r"0x[0-9a-f]+", "0x",
                        str(jax.make_jaxpr(fn)(*args)))
            for key, fn, args in (("step", step, step_args),
                                  ("window", window, window_args))}


@pytest.mark.parametrize("name", sorted({n for n, _ in PARENT_PROGRAMS}))
@pytest.mark.parametrize("program", ["step", "window"])
def test_an_older_models_program_is_the_parents(name, program):
    text = program_texts(name, rows=PROGRAM_ROWS.get(name, 8))[program]
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == PARENT_PROGRAMS[name, program], (name, program)


# PR 48: the same at the SERVED widths and step shapes
# (`benchmark/configs/<name>`, the cells' own [rows, chunk]), traced from
# PR 48's parent (b77b8fd). A [32, 16] step keeps its attention on the
# grid outside the `cond`s (ops/attention.attention_rows_pay says the row
# form does not pay there) and no decode window holds the changed code:
# those are the parent's to the character. An [8, 64] or [64, 64] step
# takes the row form inside the back half's `cond`, MEANT to change:
# Trinity's digest is this tree's (the parent's read 332fb541881f7a7b).
SERVED_PROGRAMS = {
    ("mistral-7b", 32, 16, "step"): "3048639dbbb4697c",
    ("mistral-7b", 32, 16, "window"): "af20e6991fff77ee",
    ("mixtral-8x7b", 32, 16, "step"): "657974782ae31be3",
    ("mixtral-8x7b", 32, 16, "window"): "4ec4c91e571b5d13",
    ("olmoe-1b-7b", 32, 16, "step"): "512b02ddc6af76a2",
    ("olmoe-1b-7b", 32, 16, "window"): "3e4a829462e81321",
    # PR 53: the two latent windows below are this tree's, MEANT to
    # change (576 -> 640 lanes a stored row, the base gathered a (layer,
    # page) an index; with both undone they read 659294f9b1fcc10f /
    # 4499a3a1552d90c3, PR 48's parent's)
    ("moonlight-16b-a3b", 8, 64, "window"): "d52ec8825dce4249",
    ("mellum2-12b-a2.5b", 8, 64, "window"): "a35fc1fb307f3aa2",
    ("trinity-mini", 8, 64, "step"): "5c5360c8188fecef",
    ("trinity-mini", 8, 64, "window"): "88a92f6215229ff7",
    ("ling-3.0-flash-vl", 64, 64, "window"): "b5cf7118a170b93f",
    ("falcon-h1-34b", 64, 64, "window"): "279a188e98469eba",
    # PR 50: LFM2 at its cell's shape, this tree's own (the parent cannot
    # trace it): a lead's conv body before the period loop's two, the
    # tails in the window's carry beside the attention layers' new rows.
    # PR 51 replaced both, and MEANT to (they read ef012449ae3183b4 /
    # 30ba5ac52d223f9b): two 64-wide KV heads share a pool row, the pool
    # is [3, 4, P, 64, 128]; the 12 above (128-wide heads, or a latent
    # cache, which PR 53 took up) are untouched
    ("lfm2-8b-a1b", 8, 64, "step"): "58702a7655d63973",
    ("lfm2-8b-a1b", 8, 64, "window"): "f697e06a037af8e9",
    # PR 55: Brumby at its cell's shape (16 rows beside a 256-token
    # chunk), this tree's own (the parent cannot trace it): one layer
    # body of kind "ret", no pool leaf among the operands, a window
    # without base, buffer or writeback. Every digest above stands: no
    # older program holds a line of the new kind
    ("brumby-14b", 16, 256, "step"): "defdcacca2ddb221",
    ("brumby-14b", 16, 256, "window"): "acca635cef6b4aa6",
}


@functools.lru_cache(maxsize=None)
def _served_digests(name, rows, chunk):
    return {key: hashlib.sha256(text.encode()).hexdigest()[:16]
            for key, text in program_texts(name, rows=rows,
                                           chunk=chunk).items()}


@pytest.mark.parametrize("name,rows,chunk,program", sorted(SERVED_PROGRAMS))
def test_a_served_shapes_program_is_the_one_recorded(name, rows, chunk,
                                                     program):
    assert _served_digests(name, rows, chunk)[program] \
        == SERVED_PROGRAMS[name, rows, chunk, program]


def test_the_new_models_programs_hold_what_the_old_ones_lack():
    """The same two programs for the rehearsal Trinity: three layer
    bodies (the lead's scan before the scan over periods), and the gate,
    the head norm and the absent RoPE leave their marks."""
    text = program_texts("rehearsal-tiny-trinity")
    for key in ("step", "window"):
        assert text[key].count("logistic") > \
            program_texts("rehearsal-tiny-mellum")[key].count("logistic")


def test_the_programs_trace_three_layer_bodies():
    """The rehearsal configuration's step and window programs: each conv
    body multiplies by `conv_in` (twice in a step: the one-token rows and
    a group of chunk rows), each expert body routes once; two conv bodies
    (the lead's, the loop's) and two expert bodies (F, C), whatever the
    depth."""
    from tests.test_trinity import program_texts
    text = program_texts("rehearsal-tiny-lfm2")
    for key, per_body in (("step", 2), ("window", 1)):
        # a product by `conv_in` [128, 384] leaves 384 columns
        uses = [line for line in text[key].splitlines()
                if ",384] = dot_general" in line]
        assert len(uses) == 2 * per_body, (key, len(uses))
        # the sigmoids (routers, SiLUs) of three bodies, read off this
        # tree: a fourth body would add to them
        assert text[key].count("logistic") == 4, key


def test_the_benchmarks_copy_of_the_reference_is_this_one():
    """benchmark/reference/trinity.py imports nothing from dynamo_tpu and
    must not drift from models/reference.py
    (benchmark/tests/test_trinity_cell.py holds the same line from its
    side, and the blocked form the chip runs to it)."""
    import importlib.util
    import os
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "reference", "trinity.py")
    spec = importlib.util.spec_from_file_location("bench_ref_trinity", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    params = llama.init_params(jax.random.PRNGKey(5), TINY)
    tokens = np.random.default_rng(5).integers(0, TINY.vocab_size, 60)
    ours = np.asarray(reference.forward(params, tokens,
                                        **reference.arch_kwargs(TINY)))
    np.testing.assert_array_equal(
        ours, np.asarray(mod.forward(params, tokens, HF)))
    rows = [0, 17, 59]
    blocked = np.asarray(mod.forward_blocked(
        params, tokens, HF, positions=rows, expert_block=5, vocab_block=50))
    np.testing.assert_allclose(
        blocked, np.asarray(jax.nn.log_softmax(ours, axis=-1))[rows],
        atol=2e-5)
