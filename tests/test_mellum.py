"""Mellum2-12B-A2.5B (`mellum`): sliding-window and full-attention layers
in one model, the sliding layers' cache in a page pool of their own in
which a sequence holds only what its next step can see, a RoPE a layer
kind (YaRN on the full layers), 16 experts at 2 a token behind a
renormalised softmax router; the served path against the plain reference
(dynamo_tpu/models/reference.py), on LOGITS.

Tiny widths with both kinds present: two periods S S S F, a window of 16
tokens over pages of 4 (a 70-token prompt is four windows long and hands
back a page every fourth token), YaRN with an original context of 32 so
that the ramp lies inside the 8 frequencies of a 16-wide head. 16 experts
and not 8: at 8 and fewer the engine takes the capacity form
(ModelConfig.moe_dropless), which drops, and the published model's 64
take the dropless one.
"""
import dataclasses
import math
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.config import (
    EngineConfig, RopeParams, refuse_unserved,
)
from dynamo_tpu.engine.engine import NativeEngine
from dynamo_tpu.engine.scheduler import (
    EngineRequest, SamplingParams, Scheduler,
)
from dynamo_tpu.models import llama, reference
from dynamo_tpu.models.loader import config_from_hf
from dynamo_tpu.observability.ledger import LEDGER_STATS
from tests.test_ling import readings
from tests.test_olmoe import ENGINE_KW, Recorder, drive

HF = dict(
    architectures=["MellumForCausalLM"], model_type="mellum",
    vocab_size=128, hidden_size=64, intermediate_size=128,
    num_hidden_layers=8, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, rms_norm_eps=1e-6, max_position_embeddings=512,
    sliding_window=16, use_sliding_window=True, max_window_layers=0,
    layer_types=(["sliding_attention"] * 3 + ["full_attention"]) * 2,
    mlp_layer_types=["sparse"] * 8, num_experts=16, num_experts_per_tok=2,
    moe_intermediate_size=32, norm_topk_prob=True, hidden_act="silu",
    attention_bias=False, tie_word_embeddings=False,
    rope_parameters={
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 10000.0, "factor": 4,
            "original_max_position_embeddings": 32, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2},
        "sliding_attention": {"rope_type": "default",
                              "rope_theta": 10000.0}})
TINY = dataclasses.replace(config_from_hf(HF, "tiny-mellum"),
                           dtype="float32")
KW = dict(ENGINE_KW, page_size=4, num_pages=128)
# (prompt, generated): 70 is four windows and eighteen pages long and
# takes three 32-token chunks (a chunk is two windows: pages that its
# first token sees outlive it, pages behind its last go at its commit);
# the others arrive while it decodes, so their chunks ride mixed steps
# beside decode rows whose tables start deep in their context
REQUESTS = ((70, 24), (37, 9), (21, 6))

# Two readings a comparison in float32, over served positions, of max
# |logit difference| over the vocabulary (logits are O(1)): the largest,
# held to 1e-4, and the median, held to 3e-5. Both sides compute in
# float32 from the same weights and differ in summation order (paged
# attention over a table that starts mid-context, the split base + window
# + self softmax, sorted dispatch against every expert masked) and in
# where cos and sin are scaled. Read on this CPU: largest 9.3e-6, median
# 2.1e-6 (seed 0), so the limits are eleven and fourteen times the
# readings. Each mutation is judged on the median, which nothing but a
# real change of the function moves, and must read 100 times its limit:
# they read 0.28 (attention_factor left out), 0.63 and 2.2 (the tables
# swapped), 1.7 (no renormalisation) and 2.4 (the window dropped), nine
# thousand times the limit and more.
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
        before = LEDGER_STATS.snapshot()
        entries, seqs, eng = served_run(mp)
        params = jax.device_get(eng.params)
        m = eng.metrics()
        sch = eng.scheduler
        stats = dict(
            mixed=m.mixed_steps, windows=m.decode_windows,
            cache={k: v.shape for k, v in eng.cache.items()},
            released=sch.window_released,
            window_free=sch.window_alloc.num_free,
            window_pages=sch.window_alloc.num_pages,
            full_free=sch.allocator.num_free,
            delta={k: v - before[k]
                   for k, v in LEDGER_STATS.snapshot().items()},
            now=LEDGER_STATS.snapshot())
    return entries, seqs, params, stats


def test_served_logits_match_the_plain_reference(served):
    entries, seqs, params, stats = served
    largest, median, _ = readings(entries, seqs,
                                  reference_logits(params, seqs))
    assert largest < TOL[0] and median < TOL[1], (largest, median)
    # through every step kind, and past many releases
    assert stats["mixed"] > 0 and stats["windows"] > 0
    assert stats["released"] >= 20, stats["released"]


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


MUTATIONS = {
    # full attention in the S layers
    "the window is dropped": dict(sliding_window=0),
    "the S layers get the F layers' table": dict(
        rope_sliding=dataclasses.asdict(TINY.rope_full)),
    "the F layers get the S layers' table": dict(
        rope_full=dataclasses.asdict(TINY.rope_sliding)),
    "attention_factor is left out": dict(rope_full=dict(
        dataclasses.asdict(TINY.rope_full), attention_factor=1.0)),
    "router weights are not renormalised": dict(norm_topk_prob=False),
}


@pytest.mark.parametrize("name", sorted(MUTATIONS))
def test_the_tolerance_is_tight(served, name):
    """The same comparison FAILS against a reference that is wrong in one
    of the ways this model can be served wrong."""
    entries, seqs, params, _ = served
    _, median, _ = readings(
        entries, seqs, reference_logits(params, seqs, **MUTATIONS[name]),
        every_position=False)
    assert median > 100 * TOL[1], (name, median)


def test_the_cache_is_held_by_layer_kind(served):
    _, _, _, stats = served
    hkv, hd, ps = TINY.num_kv_heads, TINY.head_dim, KW["page_size"]
    rows = KW["max_slots"] + EngineConfig().max_prefill_batch
    # a 32-token chunk over a 16-token window: ceil(48 / 4) + 1 pages
    wpages = rows * 13
    assert stats["cache"] == {
        "k": (2, hkv, KW["num_pages"], ps, hd),
        "v": (2, hkv, KW["num_pages"], ps, hd),
        "wk": (6, hkv, wpages, ps, hd), "wv": (6, hkv, wpages, ps, hd)}
    # both lists went back whole
    assert stats["window_free"] == stats["window_pages"] == wpages
    assert stats["full_free"] == KW["num_pages"]
    now, delta = stats["now"], stats["delta"]
    assert now["kv_bytes_per_token_full"] == TINY.kv_bytes_per_token() \
        == 2 * 2 * hkv * hd * 4
    assert now["kv_bytes_per_token_window"] \
        == TINY.window_kv_bytes_per_token() == 6 * 2 * hkv * hd * 4
    # (a gauge of the scheduler's count, read when a step is planned: the
    # last engine's that planned one in this process, so no delta)
    assert stats["released"] - 3 <= now["kv_window_pages_released_total"] \
        <= stats["released"]
    # a sliding layer's gather is a fraction of a full-length one, and
    # what it reads is at most the window a row
    assert 0 < delta["attn_kv_window_slots_total"] \
        < delta["attn_kv_slots_total"]
    assert delta["attn_kv_window_tokens_total"] \
        < delta["attn_kv_tokens_total"]
    held = delta["kv_window_pages_held_sum_total"] \
        / delta["kv_window_rows_total"]
    assert 1 <= held <= 13, held


def test_yarn_table_against_an_independent_writing():
    """The served table (models/llama.yarn_inv_freq, float32 NumPy) and
    the reference's (float32 jax.numpy) against a third writing, in
    Python floats, one dimension at a time, at the published numbers."""
    d, base, factor, l0, fast, slow = 128, 5e5, 16.0, 8192, 32.0, 1.0

    def turns_dim(r):
        return d * math.log(l0 / (2 * math.pi * r)) / (2 * math.log(base))
    low, high = math.floor(turns_dim(fast)), math.ceil(turns_dim(slow))
    assert (low, high) == (18, 35)
    want = []
    for i in range(d // 2):
        f = base ** (2 * i / d)
        ramp = min(1.0, max(0.0, (i - low) / (high - low)))
        want.append(ramp / (factor * f) + (1 - ramp) / f)
    p = RopeParams(theta=base, rope_type="yarn", factor=factor,
                   original_max_position=l0, beta_fast=fast, beta_slow=slow)
    got = llama.yarn_inv_freq(p, d)
    assert got.dtype == np.float32
    np.testing.assert_allclose(got, want, rtol=3e-6)
    np.testing.assert_allclose(
        np.asarray(reference.yarn_inv_freq(d, base, factor, l0, fast, slow)),
        want, rtol=3e-6)
    # the ends: untouched above the fast dimensions, divided by the
    # factor below the slow ones
    assert got[0] == 1.0 and got[17] == np.float32(base ** (-34 / d))
    np.testing.assert_allclose(got[36:] * factor,
                               [base ** (-2 * i / d) for i in range(36, 64)],
                               rtol=3e-6)
    # the attention factor the file gives is the default one
    cfg = dataclasses.replace(TINY, head_dim=d, rope_full=p)
    assert llama.rope_table(cfg, "mha")[2] == pytest.approx(
        1.2772588722239782) == 0.1 * math.log(16) + 1


def test_plain_rope_is_the_program_it_was():
    """A model without RoPE parameters by kind traces the expression
    every program had before: same jaxpr, from the same theta."""
    def before(x, positions, theta):
        hd = x.shape[-1]
        freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, jnp.float32) / hd))
        angles = positions[..., None].astype(jnp.float32) * freqs
        cos = jnp.cos(angles)[:, :, None, :]
        sin = jnp.sin(angles)[:, :, None, :]
        x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
        out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                              axis=-1)
        return out.astype(x.dtype)
    from dynamo_tpu.engine.config import ModelConfig
    cfg = ModelConfig(rope_theta=1e6)
    assert llama.rope_table(cfg, "mha") == (1e6, None, 1.0)
    assert llama.rope_table(TINY, "swa") == (10000.0, None, 1.0)
    x = jnp.ones((2, 3, 4, 32), jnp.bfloat16)
    pos = jnp.arange(6, dtype=jnp.int32).reshape(2, 3)
    new = jax.make_jaxpr(lambda x, p: llama.apply_rope(
        x, p, *llama.rope_table(cfg, "mha")))(x, pos)
    old = jax.make_jaxpr(lambda x, p: before(x, p, 1e6))(x, pos)
    assert str(new) == str(old)


def test_what_a_window_pool_is_not_served_with_is_refused_by_name():
    for kw, word in ((dict(spec_decode="ngram"), "spec_decode"),
                     (dict(host_pages=8), "tiers"),
                     (dict(kv_quant="int8"), "kv_quant")):
        with pytest.raises(ValueError, match=word):
            NativeEngine(TINY, EngineConfig(**dict(KW, **kw)))
    with pytest.raises(ValueError, match="decode_kernel"):
        refuse_unserved(dataclasses.replace(TINY, decode_kernel="on"))
    refuse_unserved(
        dataclasses.replace(TINY, window_pool=False), EngineConfig(
            spec_decode="ngram"))


def test_the_loader_refuses_by_key():
    for change, word in (
            (dict(use_qk_norm=True), "use_qk_norm"),
            (dict(n_shared_experts=1), "n_shared_experts"),
            (dict(num_nextn_predict_layers=1), "num_nextn_predict_layers"),
            (dict(norm_topk_prob=False), "norm_topk_prob"),
            (dict(mlp_layer_types=["dense"] + ["sparse"] * 7),
             "mlp_layer_types"),
            (dict(layer_types=["chunked_attention"] * 8), "layer_types"),
            (dict(rope_parameters={"full_attention": {
                "rope_type": "longrope", "rope_theta": 1e4},
                "sliding_attention": {"rope_type": "default"}}),
             "longrope"),
            (dict(rope_scaling={"rope_type": "llama3"}), "llama3")):
        with pytest.raises(ValueError, match=word):
            config_from_hf(dict(HF, **change))
    # the serving length is not capped at the window
    assert TINY.max_model_len == 512 and TINY.sliding_window == 16
    assert TINY.layer_kinds() == ("swa", "swa", "swa", "mha") * 2
    assert TINY.layer_windows() is None and TINY.moe_dropless


# -- the scheduler's second page list -----------------------------------------

def _sched(window_pages=64, **kw):
    cfg = EngineConfig(**dict(
        page_size=4, num_pages=256, max_slots=4, max_prefill_chunk=32,
        prefill_buckets=(8, 16, 32), max_model_len=512, decode_steps=4,
        **kw))
    return Scheduler(cfg, window=(16, window_pages)), cfg


def _run(sch, steps=400, on_plan=None):
    """Drive the scheduler alone: every plan commits with token 1."""
    for _ in range(steps):
        plan = sch.schedule()
        if plan is None:
            return
        if on_plan:
            on_plan(plan)
        if hasattr(plan, "n_valid"):
            decode = getattr(plan, "is_decode", [False] * len(plan.seqs))
            for i, seq in enumerate(plan.seqs):
                if seq is not None and decode[i]:
                    sch.commit_decode_token(seq, 1)
                else:
                    sch.commit_prefill_row(plan, i, 1)
        else:
            for _ in range(plan.n_window):
                for seq in plan.seqs:
                    if seq is not None and seq.slot >= 0:
                        sch.commit_decode_token(seq, 1)
        for seq in list(sch.running):
            if seq is not None and len(seq.output) >= \
                    sch.params[seq.request_id].max_tokens:
                sch.finish(seq)


def test_a_sequences_window_pages_never_exceed_the_bound():
    """Before a step of chunk c a row holds at most ceil((window + c) /
    page) + 1 pages of the window pool (c the chunk or a decode row's
    lookahead, whichever is larger): the width of that step's table. The
    table starts at the row's first held page, every cell a step writes
    lies in a held page, and what a commit hands back is free again."""
    sch, cfg = _sched()
    seen = {"plans": 0, "widest": 0}

    def check(plan):
        ps, wb = cfg.page_size, plan.wtable.shape[1]
        chunk = plan.tokens.shape[1] if hasattr(plan, "n_valid") else 1
        assert wb == sch.window_table_pages(chunk) \
            == -(-(16 + max(chunk, 8)) // ps) + 1
        for i, seq in enumerate(plan.seqs):
            if seq is None:
                continue
            assert len(seq.wpages) <= wb
            seen["widest"] = max(seen["widest"], len(seq.wpages))
            assert plan.woff[i] == seq.wfirst * ps
            assert list(plan.wtable[i, :len(seq.wpages)]) == seq.wpages
            # the first key the row's first query sees is held
            first_q = int(plan.positions[i].min())
            assert seq.wfirst * ps <= max(0, first_q - 16 + 1)
            if hasattr(plan, "wwrite_idx"):
                real = plan.write_idx[i] >= 0
                assert ((plan.wwrite_idx[i] >= 0) == real).all()
                for t in np.nonzero(real)[0]:
                    pos = int(plan.positions[i, t])
                    page = seq.wpages[pos // ps - seq.wfirst]
                    assert plan.wwrite_idx[i, t] == page * ps + pos % ps
        live = {id(s): s for s in [*sch.running, *sch.waiting, *plan.seqs]
                if s is not None}
        assert sum(len(s.wpages) for s in live.values()) \
            == sch.window_alloc.num_pages - sch.window_alloc.num_free
        seen["plans"] += 1

    rng = np.random.default_rng(0)
    for i, (n, g) in enumerate(((150, 40), (70, 30), (33, 20), (90, 25))):
        sch.add_request(EngineRequest(
            f"r{i}", rng.integers(2, 100, n).tolist(),
            SamplingParams(max_tokens=g, temperature=0.0)))
    _run(sch, on_plan=check)
    assert seen["plans"] > 12 and seen["widest"] in (12, 13), seen
    assert sch.window_released > 60
    # everything went back, to both free lists
    assert sch.window_alloc.num_free == sch.window_alloc.num_pages
    assert sch.allocator.num_free == sch.allocator.num_pages


def test_preemption_and_abort_return_both_lists():
    sch, cfg = _sched()
    rng = np.random.default_rng(1)
    for i in range(3):
        sch.add_request(EngineRequest(
            f"r{i}", rng.integers(2, 100, 60).tolist(),
            SamplingParams(max_tokens=40, temperature=0.0)))
    _run(sch, steps=6)
    running = [s for s in sch.running if s is not None]
    assert len(running) >= 2 and all(s.wpages for s in running)
    victim = running[0]
    sch._preempt_one()
    victims = [s for s in sch.waiting if not s.wpages and not s.pages]
    assert victims and victims[0].wfirst == 0
    assert victims[0].num_cached == 0   # no prefix to reclaim: reuse is off
    assert sch.abort(running[-1].request_id) or sch.abort(victim.request_id)
    held = lambda attr: sum(len(getattr(s, attr)) for s in
                            [*sch.running, *sch.waiting] if s is not None)
    assert held("wpages") == sch.window_alloc.num_pages \
        - sch.window_alloc.num_free
    assert held("pages") == sch.allocator.num_pages \
        - sch.allocator.num_free
    _run(sch)
    assert sch.window_alloc.num_free == sch.window_alloc.num_pages
    assert sch.allocator.num_free == sch.allocator.num_pages


def test_admission_reads_both_pools():
    """A window pool too small for a second sequence blocks it though the
    full pool has room, and takes nothing from either; it is admitted once
    the first hands its pages back."""
    sch, cfg = _sched(window_pages=14)
    rng = np.random.default_rng(2)
    for i in range(2):
        sch.add_request(EngineRequest(
            f"r{i}", rng.integers(2, 100, 40).tolist(),
            SamplingParams(max_tokens=4, temperature=0.0)))
    plan = sch.schedule()
    assert [s.request_id for s in plan.seqs if s is not None] == ["r0"]
    blocked = sch.waiting[0]
    assert blocked.request_id == "r1" and not blocked.pages \
        and not blocked.wpages
    for i, seq in enumerate(plan.seqs):
        sch.commit_prefill_row(plan, i, 1)
    _run(sch)
    assert not sch.waiting and sch.window_alloc.num_free == 14
    assert sch.peek_prefix(list(range(2, 40))) == 0   # prefix reuse is off


# -- the benchmark's files, from the program's side -----------------------------

def _bench(*parts):
    import os
    return os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", *parts)


def test_the_benchmarks_copy_of_the_reference_is_this_one():
    """benchmark/reference/mellum.py imports nothing from dynamo_tpu and
    must not drift from models/reference.py
    (benchmark/tests/test_mellum_cell.py holds the same line from its
    side, and the blocked form the chip runs to it)."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "bench_ref_mellum", _bench("reference", "mellum.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    params = llama.init_params(jax.random.PRNGKey(5), TINY)
    tokens = np.random.default_rng(5).integers(0, TINY.vocab_size, 60)
    ours = np.asarray(reference.forward(params, tokens,
                                        **reference.arch_kwargs(TINY)))
    hf = dict(HF, layer_types=list(HF["layer_types"]))
    np.testing.assert_array_equal(
        ours, np.asarray(mod.forward(params, tokens, hf)))
    rows = [0, 17, 59]
    blocked = np.asarray(mod.forward_blocked(
        params, tokens, hf, positions=rows, expert_block=5, vocab_block=50))
    np.testing.assert_allclose(
        blocked, np.asarray(jax.nn.log_softmax(ours, axis=-1))[rows],
        atol=2e-5)


def test_the_configurations_constants_are_the_programs():
    """meta.json's sizes, which the roofline's metric file carries as
    constants, against ModelConfig and the engine's own sizing."""
    import json
    with open(_bench("configs", "mellum2-12b-a2.5b", "config.json")) as f:
        hf = json.load(f)
    with open(_bench("configs", "mellum2-12b-a2.5b", "meta.json")) as f:
        meta = json.load(f)
    sizes = meta["sizes"]
    cfg = config_from_hf(hf, "mellum2-12b-a2.5b")
    shapes = jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), cfg))
    leaves = jax.tree.leaves(shapes)
    assert all(a.dtype == jnp.bfloat16 for a in leaves)
    assert sum(a.size for a in leaves) == sizes["params"]
    assert sizes["weights_bytes"] == 2 * sizes["params"]
    routed = sum(shapes[r.key][name].size for r in llama.layer_runs(cfg)
                 for name in llama.EXPERT_LEAVES)
    assert 2 * routed == sizes["routed_expert_bytes"]
    assert sizes["decode_step_fixed_bytes"] == sizes["weights_bytes"] \
        - 2 * shapes["embed"].size - 2 * routed
    assert sizes["decode_step_bytes_per_expert_hit"] \
        == 2 * routed // cfg.num_experts
    assert cfg.kv_bytes_per_token() == sizes["kv_bytes_per_token_full"]
    assert cfg.window_kv_bytes_per_token() \
        == sizes["kv_bytes_per_token_window"]
    # the window pool as the engine sizes it from the serve flags
    flags = dict(zip(meta["serve"][::2], meta["serve"][1::2]))
    ecfg = EngineConfig(max_slots=int(flags["--max-slots"]),
                        num_pages=int(flags["--num-pages"]))
    per_seq = Scheduler(ecfg, window=(cfg.sliding_window, 1)) \
        .window_table_pages(ecfg.max_prefill_chunk)
    assert per_seq == sizes["window_pages_per_sequence_max"] == 25
    assert (ecfg.max_slots + ecfg.max_prefill_batch) * per_seq \
        == sizes["kv_pages_window"]
    # one table of 18 pages serves a decode window and a 64-token chunk
    sch = Scheduler(ecfg, window=(cfg.sliding_window, 1))
    assert {sch.window_table_pages(c) for c in (1, 16, 32, 64)} == {18}
    with open(_bench("layer_metrics", "device.swa_window_roofline.json")) \
            as f:
        text = f.read()
    assert str(sizes["decode_step_fixed_bytes"]) in text
    assert str(sizes["decode_step_bytes_per_expert_hit"]) in text


# -- one compiled loop ------------------------------------------------------------

def test_one_loop_over_periods_serves_the_model():
    """A stack a kind, and a scan over periods with a scan a part in its
    body: S S S F x 2 compiles two layer bodies, not four."""
    runs = llama.layer_runs(TINY)
    assert [(r.key, r.first, r.count, r.kind, r.store_first)
            for r in runs] == [("run0", 0, 6, "swa", 0),
                               ("run1", 3, 2, "mha", 0)]
    assert llama.layer_period(TINY) == llama.LayerPeriod(
        2, ((0, 3, 0, 3), (1, 1, 0, 1)))
    shapes = jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), TINY))
    assert shapes["run0"]["wq"].shape[0] == 6
    assert shapes["run1"]["wq"].shape[0] == 2
    from dynamo_tpu.engine.config import ModelConfig
    assert llama.layer_period(ModelConfig()) is None
    # a pattern that repeats nowhere is one period of many parts
    odd = dataclasses.replace(TINY, layer_types=tuple(
        "sliding_attention" if c == "S" else "full_attention"
        for c in "SFSSFSSF"))
    assert llama.layer_period(odd) == llama.LayerPeriod(1, (
        (0, 5, 0, 1), (1, 3, 0, 1), (0, 5, 1, 2), (1, 3, 1, 1),
        (0, 5, 3, 2), (1, 3, 2, 1)))


def test_a_pattern_without_a_period_is_served_too(monkeypatch):
    """S F S S F S S F: every part's offset into its kind's stack, its
    cache leaves and its RoPE table, against the reference in the model's
    order."""
    global TINY
    odd = dataclasses.replace(TINY, layer_types=tuple(
        "sliding_attention" if c == "S" else "full_attention"
        for c in "SFSSFSSF"))
    monkeypatch.setattr(sys.modules[__name__], "TINY", odd)
    entries, seqs, eng = served_run(monkeypatch)
    assert eng.cache["wk"].shape[0] == 5 and eng.cache["k"].shape[0] == 3
    largest, median, _ = readings(
        entries, seqs, reference_logits(jax.device_get(eng.params), seqs))
    assert largest < TOL[0] and median < TOL[1], (largest, median)
