"""LFM2 (`lfm2` / `lfm2_moe`): gated short-convolution layers that hold a
two-row tail and no pages, three to every attention layer of head-normed
GQA, kinds given by a LIST, a dense lead of conv layers, experts behind a
sigmoid router whose bias picks and does not weigh. The plain reference
(dynamo_tpu/models/reference.py) against `transformers`' own dense class,
the served path against the reference on LOGITS and on the slots' TAILS,
the period loop that serves a state hybrid, the router, the loader and what
it refuses, the host's accounting of a tail-only state, and the benchmark's
configuration.

Tiny widths with everything present: a lead of 2 dense conv layers, then
two periods F C C; GQA 4 / 2 of 16-wide heads; 3 taps; 16 experts at 2 (the
dropless dispatch needs more than 8); a tied head.
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
    EngineConfig, ModelConfig, refuse_unserved,
)
from dynamo_tpu.engine.engine import NativeEngine
from dynamo_tpu.engine.scheduler import EngineRequest, SamplingParams
from dynamo_tpu.models import llama, loader, reference
from dynamo_tpu.observability.ledger import LEDGER_STATS
from dynamo_tpu.ops import moe
from tests.test_ling import readings
from tests.test_olmoe import ENGINE_KW, Recorder, drive

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

C, F = "conv", "full_attention"
TINY = ModelConfig(
    name="tiny-lfm2", vocab_size=128, hidden_size=64, num_layers=8,
    num_heads=4, num_kv_heads=2, head_dim=16, qk_norm="head",
    layer_types=(C, C, F, C, C, F, C, C), conv_l_cache=3,
    intermediate_size=32, dense_intermediate_size=96, first_dense_layers=2,
    num_experts=16, num_experts_per_tok=2, moe_scoring="sigmoid",
    moe_router_bias=True, moe_renorm_eps=1e-6, tie_word_embeddings=True,
    rope_theta=1e6, rms_norm_eps=1e-5, dtype="float32", max_model_len=256)
# the published 24 layers' SHAPE at toy depth: behind the lead no prefix of
# the kinds repeats (F C C C F C C F C C), so the loop is one period
IRREGULAR = dataclasses.replace(
    TINY, name="tiny-lfm2-tail", num_layers=12,
    layer_types=(C, C, F, C, C, C, F, C, C, F, C, C))

# Two readings a comparison in float32, over served positions, of max
# |logit difference| over the vocabulary (logits have a standard deviation
# of ~1): the largest, held to 1e-4, and the median, held to 3e-5. Both
# sides compute in float32 from the same weights; they differ in summation
# order and in the FORM of the mixer (chunks continued from a slot's tail
# and one-token rows against the three-tap sum over the whole sequence), of
# attention (pages against the whole sequence) and of the experts (the
# sorted grouped matmul against every expert on every token). Read on this
# CPU: largest 7.3e-6, median 2.7e-6 (seed 0), so the limits are fourteen
# and eleven times the readings. The router's renormalisation adds 1e-6 on
# both sides (`moe_renorm_eps`); at 1e-20 on the served side alone every
# expert weight would move by a relative 5e-7, under these limits: that
# constant is held by `test_the_renormalisation_adds_the_published_term`,
# not by a logit. The mutations are judged on the median: each must read
# 300 times its limit (a tail lost at edges that most positions lie before,
# on the 90th percentile).
TOL = (1e-4, 3e-5)
REQUESTS = ((70, 10), (37, 9), (21, 6))


def reference_logits(params, seqs, cfg=TINY, **changes):
    arch = {**reference.arch_kwargs(cfg), **changes}
    return [np.asarray(reference.forward(params, jnp.asarray(s), **arch))
            for s in seqs]


def reference_tails(params, seq, cfg=TINY):
    """[conv layers, K - 1, D]: every conv layer's state after `seq`."""
    tails = []
    reference.forward(params, jnp.asarray(seq), tails=tails,
                      **reference.arch_kwargs(cfg))
    return np.stack([np.asarray(t) for t in tails])


def served_run(monkeypatch, cfg=TINY, seed=0, **engine_kw):
    rec = Recorder(monkeypatch)
    eng = NativeEngine(cfg, EngineConfig(**dict(ENGINE_KW, **engine_kw)),
                       seed=seed)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(2, cfg.vocab_size, n).tolist()
               for n, _ in REQUESTS]
    outs = drive(eng, prompts, [g for _, g in REQUESTS])
    assert [len(o) for o in outs] == [g for _, g in REQUESTS]
    return rec.entries, [p + o for p, o in zip(prompts, outs)], eng


# -- (i) the reference against transformers' own class ------------------------

HF_TINY = dict(
    vocab_size=96, hidden_size=64, intermediate_size=80,
    num_hidden_layers=5, num_attention_heads=4, num_key_value_heads=2,
    max_position_embeddings=256, norm_eps=1e-5, rope_theta=1e6,
    conv_bias=False, conv_L_cache=3, block_auto_adjust_ff_dim=False,
    layer_types=[C, C, F, C, F])


@pytest.mark.parametrize("tied", [True, False], ids=["tied", "untied"])
def test_the_reference_is_transformers_lfm2(tmp_path, tied):
    """models/reference.forward on the loader's arrays against
    `Lfm2ForCausalLM` (torch, float32, its non-kernel path): the conv
    mixer (B | C | u in that order, three taps, no activation), head-wise
    QK-norm before RoPE, the block norms and the final `embedding_norm`,
    every norm weight and tap drawn away from its initial value, a kinds
    list with both kinds and a conv lead, the head tied or not."""
    try:
        import torch
        from transformers import Lfm2Config, Lfm2ForCausalLM
    except Exception as e:   # no torch, or a transformers without the class
        pytest.skip(f"transformers' lfm2 cannot be imported: {e}")
    torch.manual_seed(0)
    model = Lfm2ForCausalLM(Lfm2Config(
        **HF_TINY, tie_word_embeddings=tied)).float().eval()
    with torch.no_grad():
        for name, p in model.named_parameters():
            if "conv.conv.weight" in name:
                p.copy_(0.6 * torch.randn_like(p))
            elif p.ndim == 1:
                p.copy_(1 + 0.2 * torch.randn_like(p))
            else:
                p.copy_(2 * torch.randn_like(p) * p.shape[-1] ** -0.5)
    model.save_pretrained(tmp_path, safe_serialization=True)
    ids = np.random.default_rng(0).integers(0, 96, 37)
    with torch.no_grad():
        want = model(torch.tensor(ids)[None]).logits[0].numpy()
    cfg, params = loader.load_model_dir(str(tmp_path), dtype="float32")
    assert cfg.layer_kinds() == ("conv", "conv", "mha", "conv", "mha")
    assert cfg.qk_norm == "head" and cfg.tie_word_embeddings == tied
    assert ("lm_head" in params) == (not tied)
    got = np.asarray(reference.forward(params, jnp.asarray(ids),
                                       **reference.arch_kwargs(cfg)))
    # logits of standard deviation 2: 5e-5 is a relative 2.5e-5 (read: 1.4e-5)
    assert np.std(want) > 1.0
    np.testing.assert_allclose(got, want, atol=5e-5)


def test_the_dense_familys_width_is_adjusted_as_its_mlp_adjusts_it():
    """`Lfm2MLP` under `block_auto_adjust_ff_dim`: two thirds, times the
    multiplier, up to a multiple of `block_multiple_of`."""
    hf = {**HF_TINY, "architectures": ["Lfm2ForCausalLM"],
          "intermediate_size": 12288, "block_auto_adjust_ff_dim": True,
          "block_ffn_dim_multiplier": 1.0, "block_multiple_of": 256}
    assert loader.config_from_hf(hf).intermediate_size == 8192
    assert loader.config_from_hf(
        {**hf, "block_auto_adjust_ff_dim": False}).intermediate_size == 12288


# -- (ii) the served path against the reference --------------------------------

@pytest.fixture(scope="module")
def served_f32():
    """One float32 run of the served path (prefill chunks, mixed steps,
    decode windows), shared by the comparison and by every mutation of
    what it is compared with."""
    with pytest.MonkeyPatch.context() as mp:
        before = LEDGER_STATS.snapshot()
        entries, seqs, eng = served_run(mp)
        params = jax.device_get(eng.params)
        m = eng.metrics()
        delta = {k: v - before[k] for k, v in LEDGER_STATS.snapshot().items()
                 if k.startswith("linattn_")}
        stats = dict(mixed=m.mixed_steps, windows=m.decode_windows,
                     cache={k: (v.shape, str(v.dtype))
                            for k, v in eng.cache.items()},
                     tails=np.asarray(eng.cache["conv_tail"]),
                     slots_used=eng.scheduler.state_slots.used,
                     page_bytes=m.kv_page_bytes, delta=delta,
                     slot_bytes=LEDGER_STATS.state_bytes_per_slot)
    return entries, seqs, params, stats


def test_served_logits_match_the_plain_reference(served_f32):
    entries, seqs, params, stats = served_f32
    largest, median, _ = readings(entries, seqs,
                                  reference_logits(params, seqs))
    assert largest < TOL[0] and median < TOL[1], (largest, median)
    assert stats["mixed"] > 0 and stats["windows"] > 0, stats
    # the 2 attention layers alone hold pages; the 6 conv layers a tail, a
    # slot a decode slot and a prefill-batch row, and the scratch slot
    slots = ENGINE_KW["max_slots"] + EngineConfig().max_prefill_batch + 1
    assert stats["cache"] == {
        "k": ((2, 2, 64, 16, 16), "float32"),
        "v": ((2, 2, 64, 16, 16), "float32"),
        "conv_tail": ((6, slots, 2, 64), "float32")}
    assert stats["slots_used"] == 0          # every sequence finished
    assert stats["page_bytes"] == 16 * TINY.kv_bytes_per_token() \
        == 16 * 2 * 2 * 2 * 16 * 4
    assert stats["slot_bytes"] == TINY.state_bytes_per_slot() \
        == 6 * 2 * 64 * 4


def test_a_finished_sequences_slot_holds_the_references_tail(served_f32):
    """(iii) after DECODE. Nothing clears a slot at its release, so it
    holds what its sequence's last step left: the last two rows of B * u
    after the prompt and every generated token but the last, or after the
    last too where a window emitted it before its own last step (that step
    feeds it, and what it samples is dropped), in all 6 conv layers, the
    lead's first, to float32's rounding; the other lies a whole row
    apart."""
    _, seqs, params, stats = served_f32
    for slot, seq in enumerate(seqs):    # slots go out in order of admission
        held = stats["tails"][:, slot]
        each = [np.abs(held - reference_tails(params, seq[:n])).max()
                for n in (len(seq) - 1, len(seq))]
        assert min(each) < 1e-5 < 1e-2 < max(each), (slot, each)


def test_the_state_series_count_a_tail_only_state(served_f32):
    """The host's accounting feeds the series that mean "a recurrent
    state", whatever layer keeps it: every (token, conv layer) update
    once, the prompt chunks' through the chunk form, a window's and a
    mixed step's one-token rows where the tail rests; the bytes are the
    tails' alone, both ways."""
    *_, stats = served_f32
    d = stats["delta"]
    fed = sum(n + g - 1 for n, g in REQUESTS)      # positions fed
    over = 6 * len(REQUESTS) * ENGINE_KW["decode_steps"]
    assert 6 * fed <= d["linattn_tokens_total"] <= 6 * fed + over
    assert 0 < d["linattn_chunk_tokens_total"] < d["linattn_tokens_total"]
    assert 0 < d["linattn_inplace_updates_total"] \
        < d["linattn_tokens_total"]
    assert d["linattn_window_steps_total"] > 0
    assert d["linattn_state_bytes_total"] % (
        2 * TINY.state_bytes_per_slot()) == 0


def lost_tail_conv(every):
    """`reference.causal_conv` with the tail lost at every `every`-token
    edge: what a served path that dropped it between chunks computes."""
    def conv(x, w):
        k, t = w.shape[0], x.shape[0]
        xp = jnp.concatenate([jnp.zeros((k - 1, x.shape[1]), x.dtype), x])
        at = jnp.arange(t)
        return sum(w[j] * jnp.where(
            (at - (k - 1) + j >= (at // every) * every)[:, None],
            xp[j:j + t], 0.0) for j in range(k))
    return conv


@pytest.mark.parametrize("mutation", [
    "taps-reversed", "b-and-c-swapped", "c-and-u-swapped", "no-gate",
    "head-norm-weights-skipped", "no-expert-bias", "lost-tail-at-8",
    "lost-tail-at-32", "final-norm-skipped", "lead-and-loop-swapped"])
def test_a_model_served_wrong_is_seen(served_f32, monkeypatch, mutation):
    """Each way of computing another function (in the REFERENCE) reads a
    median hundreds of times the limit against what was served."""
    entries, seqs, params, _ = served_f32
    params = jax.tree.map(np.array, params)
    d = TINY.hidden_size
    for key in ("lead0", "run1"):
        w = params[key]["conv_in"]
        if mutation == "taps-reversed":
            params[key]["conv_w"] = params[key]["conv_w"][:, ::-1]
        elif mutation == "b-and-c-swapped":
            params[key]["conv_in"] = np.concatenate(
                [w[..., d:2 * d], w[..., :d], w[..., 2 * d:]], axis=-1)
        elif mutation == "c-and-u-swapped":
            params[key]["conv_in"] = np.concatenate(
                [w[..., :d], w[..., 2 * d:], w[..., d:2 * d]], axis=-1)
    if mutation == "no-gate":
        monkeypatch.setattr(reference, "short_conv", lambda x, lp, tails: (
            reference.causal_conv(
                (x @ lp["conv_in"])[:, :d] * (x @ lp["conv_in"])[:, 2 * d:],
                lp["conv_w"]) @ lp["wo"]))
    elif mutation == "head-norm-weights-skipped":
        for name in ("q_norm", "k_norm"):
            params["run0"][name] = np.ones_like(params["run0"][name])
    elif mutation == "no-expert-bias":
        for key in ("run0", "run1"):
            params[key]["router_bias"] = np.zeros_like(
                params[key]["router_bias"])
    elif mutation.startswith("lost-tail-at-"):
        monkeypatch.setattr(reference, "causal_conv",
                            lost_tail_conv(int(mutation.rsplit("-", 1)[1])))
    elif mutation == "final-norm-skipped":
        params["final_norm"] = np.ones_like(params["final_norm"])
    elif mutation == "lead-and-loop-swapped":
        # the lead's two conv layers taken from the loop's stack instead
        # (and read with its first two): the state's axis out of order
        for name in ("attn_norm", "conv_in", "conv_w", "wo"):
            params["lead0"][name] = params["run1"][name][:2]
    want = reference_logits(params, seqs)
    _, median, p90 = readings(entries, seqs, want, every_position=False)
    if mutation == "lost-tail-at-32":
        # 91 of the 153 served positions lie before the first edge and
        # read what they read: the 90th percentile is what sees it
        assert median < TOL[1] and p90 > 300 * TOL[1], (median, p90)
        return
    assert median > 300 * TOL[1], (mutation, median)


# (iii) after PREFILL, and (ii)'s prompts around a chunk edge: one engine,
# one sequence after another in slot 0, which each finds as the last left it
PREFILL_CASES = {
    # prompt tokens at max_prefill_chunk 32: what the last chunk holds
    "one-token-prompt": 1, "two-token-prompt": 2, "inside-one-chunk": 21,
    "a-whole-chunk": 32, "last-chunk-of-1": 33, "last-chunk-of-2": 34,
    "last-chunk-of-3": 35, "two-whole-chunks": 64, "last-chunk-of-1-again":
    65}


@pytest.fixture(scope="module")
def one_engine():
    with pytest.MonkeyPatch.context() as mp:
        rec = Recorder(mp)
        eng = NativeEngine(TINY, EngineConfig(**ENGINE_KW), seed=0)
        yield rec, eng, jax.device_get(eng.params)


@pytest.mark.parametrize("case", sorted(PREFILL_CASES))
def test_a_prefilled_prompts_tail_is_the_references(one_engine, case):
    """A prompt prefilled in chunks of 32 and ONE token sampled: the slot
    holds the tail after exactly the prompt, whether its last chunk holds
    1 token (a chunk row in the one-token form: the older row comes from
    the chunk before), 2 (both rows its own), 3 or a whole chunk, and a
    prompt shorter than the tail keeps zeros in front. Slot 0 every time,
    stale from the sequence before: a fresh row starts from zeros whatever
    its slot held. Every served logit is the reference's too."""
    rec, eng, params = one_engine
    n = PREFILL_CASES[case]
    prompt = np.random.default_rng(n).integers(2, TINY.vocab_size,
                                               n).tolist()
    del rec.entries[:]
    out = eng.generate(prompt, SamplingParams(
        max_tokens=1, temperature=0.0, ignore_eos=True), f"p-{case}")
    assert len(out) == 1 and eng.scheduler.state_slots.used == 0
    want = reference_tails(params, prompt)
    assert want.shape == (6, 2, 64)
    if n == 1:
        assert not want[:, 0].any() and want[:, 1].any()
    held = np.asarray(eng.cache["conv_tail"])[:, 0]
    np.testing.assert_allclose(held, want, atol=1e-5)
    largest, median, _ = readings(rec.entries, [prompt + out],
                                  reference_logits(params, [prompt + out]))
    assert largest < TOL[0] and median < TOL[1], (largest, median)


def many_rows(eng, prompts, late: int, tokens=14):
    """`prompts` decoding together; the last `late` arrive once every
    earlier one has streamed two tokens (their chunks ride mixed steps of
    more than 8 rows)."""
    got = {f"m{i}": [] for i in range(len(prompts))}
    first = len(prompts) - late

    def add(i):
        eng.add_request(EngineRequest(f"m{i}", prompts[i], SamplingParams(
            max_tokens=tokens if i < first else 5, temperature=0.0,
            ignore_eos=True)))
    for i in range(first):
        add(i)
    sent = late == 0
    for _ in range(400):
        for ev in eng.step():
            if ev.token is not None:
                got[ev.request_id].append(ev.token)
        if not sent and all(len(got[f"m{i}"]) >= 2 for i in range(first)):
            for i in range(first, len(prompts)):
                add(i)
            sent = True
        if sent and not eng.has_work():
            break
    assert sent and not eng.has_work()
    return [p + got[f"m{i}"] for i, p in enumerate(prompts)]


@pytest.mark.parametrize("layout, lengths, late", [
    ("flat", (20, 9, 9, 9, 9, 9, 9, 9, 9, 9, 9, 30), 1),
    ("grid", (30, 17, 22, 19, 31, 18, 25, 29, 20, 27, 23, 21), 0)])
def test_a_step_of_many_rows_in_both_layouts(monkeypatch, layout, lengths,
                                             late):
    """More than 8 busy rows: a [16, 16] plan holds a `cond`. "flat": 11
    decoders beside one late chunk, the mixer's rows read from the compact
    step's flat token rows. "grid": 12 prompts prefilled together, more
    real tokens than the flat width holds, the same program's other
    branch. The conv layers run over the step's rows in either."""
    rec = Recorder(monkeypatch)
    eng = NativeEngine(TINY, EngineConfig(**dict(
        ENGINE_KW, max_slots=12, max_prefill_batch=12, num_pages=128)),
        seed=0)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(2, TINY.vocab_size, n).tolist()
               for n in lengths]
    before = LEDGER_STATS.snapshot()["linattn_flat_steps_total"]
    seqs = many_rows(eng, prompts, late)
    flat = LEDGER_STATS.snapshot()["linattn_flat_steps_total"] - before
    # "grid": every step with a chunk axis holds 12 chunks of 16 or more,
    # 192 real tokens, past the flat width of a [16, 16] step
    assert (flat > 0) == (layout == "flat")
    assert llama.step_compaction(np.zeros((16, 16)))[0] < 192
    largest, median, _ = readings(
        rec.entries, seqs,
        reference_logits(jax.device_get(eng.params), seqs))
    assert largest < TOL[0] and median < TOL[1], (largest, median)


def test_a_reused_slot_starts_from_zero_and_preemption_recomputes(
        monkeypatch):
    """More sequences than slots can hold at once: a finished sequence's
    slot goes to a new one, which must start from zeros; one sequence is
    preempted mid-decode, gives its slot AND its pages back together and
    resumes by recompute. Every logit served is still the reference's."""
    rec = Recorder(monkeypatch)
    eng = NativeEngine(TINY, EngineConfig(**dict(
        ENGINE_KW, max_slots=2, max_prefill_batch=1)), seed=0)
    slots = eng.scheduler.state_slots
    assert slots.n == 3
    rng = np.random.default_rng(9)
    prompts = [rng.integers(2, TINY.vocab_size, n).tolist()
               for n in (33, 25, 19, 27, 22)]
    for i, p in enumerate(prompts):
        eng.add_request(EngineRequest(f"p{i}", p, SamplingParams(
            max_tokens=8, temperature=0.0, ignore_eos=True)))
    got = {f"p{i}": [] for i in range(len(prompts))}
    preempted, held = False, set()
    for _ in range(400):
        for ev in eng.step():
            if ev.token is not None:
                got[ev.request_id].append(ev.token)
        running = [s for s in eng.scheduler.running if s is not None]
        held |= {s.state_slot for s in running}
        if not preempted and len(running) == 2 \
                and all(len(s.output) >= 3 for s in running):
            used, free = slots.used, eng.scheduler.allocator.num_free
            eng.scheduler._preempt_one()
            victim = eng.scheduler.waiting[0]
            assert slots.used == used - 1 and victim.state_slot == -1
            assert not victim.pages and victim.num_cached == 0
            assert eng.scheduler.allocator.num_free > free
            preempted = True
        if not eng.has_work():
            break
    assert preempted and [len(v) for v in got.values()] == [8] * 5
    assert len(held) <= 3 < len(prompts)       # slots were handed on
    assert slots.used == 0
    seqs = [p + got[f"p{i}"] for i, p in enumerate(prompts)]
    largest, median, _ = readings(
        rec.entries, seqs,
        reference_logits(jax.device_get(eng.params), seqs))
    assert largest < TOL[0] and median < TOL[1], (largest, median)


def test_a_kinds_list_with_an_irregular_tail_is_served_too(monkeypatch):
    """The published 24 layers' shape: behind the lead no prefix of the
    kinds repeats, the whole remainder is ONE period of six parts, and the
    served logits are the reference's all the same."""
    period = llama.layer_period(IRREGULAR)
    assert period.count == 1 and period.lead == 1
    assert [part[3] for part in period.parts] == [1, 3, 1, 2, 1, 2]
    entries, seqs, eng = served_run(monkeypatch, cfg=IRREGULAR)
    largest, median, _ = readings(
        entries, seqs, reference_logits(jax.device_get(eng.params), seqs,
                                        cfg=IRREGULAR))
    assert largest < TOL[0] and median < TOL[1], (largest, median)


# -- (iv) the router ---------------------------------------------------------------

def _router_case(seed=0, t=40, d=32, e=16):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.normal(size=(t, d)), jnp.float32),
            jnp.asarray(rng.normal(size=(d, e)) * d ** -0.5, jnp.float32),
            jnp.asarray(0.3 * rng.normal(size=(e,)), jnp.float32))


def test_the_bias_moves_the_pick_and_not_the_weights():
    x, router, bias = _router_case()
    w0, i0 = moe.route_topk(x, router, 4, True, "sigmoid", None,
                            renorm_eps=1e-6)
    w1, i1 = moe.route_topk(x, router, 4, True, "sigmoid", bias,
                            renorm_eps=1e-6)
    assert (np.sort(i0, -1) != np.sort(i1, -1)).any()     # the pick moved
    s = np.asarray(jax.nn.sigmoid(jnp.einsum(
        "td,de->te", x, router, precision="highest")))
    picked = np.take_along_axis(s, np.asarray(i1), axis=-1)
    # the weights are the picked SCORES renormalised: no bias in them
    np.testing.assert_allclose(
        np.asarray(w1), picked / (picked.sum(-1, keepdims=True) + 1e-6),
        rtol=1e-6)
    with_bias = picked + np.asarray(bias)[np.asarray(i1)]
    assert np.abs(np.asarray(w1) - with_bias / with_bias.sum(
        -1, keepdims=True)).max() > 1e-2


def test_the_renormalisation_adds_the_published_term():
    """Scores so small that the term shows: four picked scores of ~1e-6
    sum to ~4e-6, and over (sum + 1e-6) the weights sum to ~0.8, where
    1e-20 gives 1. `route` hands the configuration's constant on."""
    x, router, _ = _router_case()
    x = x.at[:, 0].set(40.0)
    router = router.at[0].set(-0.35)          # every logit about -14
    sums = {eps: float(np.asarray(moe.route_topk(
        x, router, 4, True, "sigmoid", None, renorm_eps=eps)[0]
    ).sum(-1).mean()) for eps in (1e-6, 1e-20)}
    assert 0.5 < sums[1e-6] < 0.95 and abs(sums[1e-20] - 1) < 1e-6
    lp = {"router": router}
    for cfg, eps in ((TINY, 1e-6), (dataclasses.replace(
            TINY, moe_renorm_eps=1e-20), 1e-20)):
        cfg = dataclasses.replace(cfg, moe_router_bias=False,
                                  num_experts_per_tok=4)
        got = float(np.asarray(moe.route(x, lp, cfg)[0]).sum(-1).mean())
        assert abs(got - sums[eps]) < 1e-6
    assert ModelConfig().moe_renorm_eps == 1e-20    # every older family's


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_served_router_is_the_references(seed):
    x, router, bias = _router_case(seed)
    w, idx = moe.route_topk(x, router, 4, True, "sigmoid", bias,
                            renorm_eps=1e-6)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(reference.router_weights(
            x, {"router": router, "router_bias": bias},
            num_experts_per_tok=4, norm_topk_prob=True,
            moe_scoring="sigmoid", renorm_eps=1e-6))
    got = np.zeros_like(want)
    np.put_along_axis(got, np.asarray(idx), np.asarray(w), axis=-1)
    np.testing.assert_allclose(got, want, atol=1e-6)


# -- (v) runs and the period ------------------------------------------------------

def _published():
    with open(os.path.join(ROOT, "benchmark", "configs", "lfm2-8b-a1b",
                           "meta.json")) as f:
        meta = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "configs", "lfm2-8b-a1b",
                           "config.json")) as f:
        return json.load(f), meta


def test_the_cut_is_a_lead_and_three_periods_of_two_parts():
    """Three layer bodies: the lead's scan (conv, dense) before the scan
    over periods, whose parts are F (attention, experts) and C C C (conv,
    experts); the lead's conv layers FIRST on the state's layer axis."""
    cfg = loader.config_from_hf(_published()[0], "lfm2-8b-a1b")
    runs = llama.layer_runs(cfg)
    assert [(r.key, r.first, r.count, r.dense, r.kind, r.store_first)
            for r in runs] == [("lead0", 0, 2, True, "conv", 0),
                               ("run0", 2, 3, False, "mha", 0),
                               ("run1", 3, 9, False, "conv", 2)]
    assert runs[0].is_lead and not runs[1].is_lead
    assert llama.layer_period(cfg) == llama.LayerPeriod(
        3, ((1, 1, 0, 1), (2, 3, 0, 3)), 1)
    assert cfg.num_cache_layers == 3 and cfg.num_state_layers == 11
    # the loop's conv layer j of period p lies at 2 + 3 p + j on the
    # state's axis, behind the lead's 0 and 1
    assert [int(runs[2].store_index(runs[2].first + i)) for i in range(9)] \
        == list(range(2, 11))


def test_the_published_kinds_fall_back_to_one_period():
    hf, meta = _published()
    full = loader.config_from_hf({
        **hf, "num_hidden_layers": 24,
        "layer_types": meta["published"]["layer_types"]}, "lfm2-24")
    assert full.layer_kinds().count("mha") == 6
    period = llama.layer_period(full)
    assert period.count == 1 and period.lead == 1
    assert [part[3] for part in period.parts] == [1, 3] * 4 + [1, 2, 1, 2]
    assert sum(part[3] for part in period.parts) == 22


# (that the step and window programs trace three layer bodies is held where
# the programs' texts are: tests/test_trinity.py, beside their digests; this
# module's Recorder wraps llama.forward until the module ends)


# -- (vi) the loader -----------------------------------------------------------------

def test_the_loader_maps_every_key_of_the_published_config():
    hf, _ = _published()
    cfg = loader.config_from_hf(hf, "lfm2-8b-a1b")
    assert (cfg.hidden_size, cfg.vocab_size, cfg.num_layers) \
        == (2048, 65536, 14)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (32, 8, 64)
    assert cfg.qk_norm == "head" and cfg.rope_theta == 1e6
    assert cfg.rms_norm_eps == 1e-5          # under the key `norm_eps`
    assert cfg.conv_l_cache == 3 and cfg.has_conv and cfg.has_state
    assert cfg.layer_types == tuple(hf["layer_types"])
    assert cfg.first_dense_layers == 2
    assert (cfg.dense_intermediate_size, cfg.intermediate_size) \
        == (7168, 1792)
    assert (cfg.num_experts, cfg.num_experts_per_tok) == (32, 4)
    assert cfg.moe_scoring == "sigmoid" and cfg.moe_router_bias
    assert cfg.norm_topk_prob and cfg.moe_renorm_eps == 1e-6
    assert cfg.moe_routed_scale == 1.0 and cfg.shared_expert_size == 0
    assert cfg.tie_word_embeddings           # the family's default
    assert cfg.moe_dropless and not cfg.window_pool and not cfg.is_mla
    assert cfg.state_leaves() == {"conv_tail": ((2, 2048), "bfloat16")}


@pytest.mark.parametrize("change, word", [
    ({"conv_bias": True}, "conv_bias"),
    ({"layer_types": ["conv"] * 13}, "layer_types"),
    ({"layer_types": ["conv"] * 13 + ["sliding_attention"]}, "layer_types"),
    ({"conv_L_cache": 1}, "conv_L_cache"),
    ({"num_dense_layers": 14}, "num_dense_layers"),
    ({"n_group": 4}, "n_group"), ({"topk_group": 2}, "topk_group"),
    ({"num_shared_experts": 1}, "num_shared_experts"),
    ({"attention_bias": True}, "attention_bias"),
    ({"block_auto_adjust_ff_dim": True}, "block_auto_adjust_ff_dim"),
    ({"rope_scaling": {"rope_type": "linear", "factor": 2.0}},
     "rope_scaling")])
def test_the_loader_refuses_by_key(change, word):
    with pytest.raises(ValueError, match=word):
        loader.config_from_hf({**_published()[0], **change})


def test_an_expert_checkpoint_is_refused_and_a_file_says_what_it_ties(
        monkeypatch):
    hf, _ = _published()
    cfg = loader.config_from_hf(hf, "lfm2-8b-a1b")
    with pytest.raises(ValueError, match="expert block.*not known"):
        loader.load_params_from_hf("nowhere", cfg)
    assert not loader.config_from_hf(
        {**hf, "tie_word_embeddings": False}).tie_word_embeddings
    assert not loader.config_from_hf(
        {**hf, "use_expert_bias": False}).moe_router_bias
    # a file without a conv layer is plain attention: no state
    plain = loader.config_from_hf({**hf, "layer_types": [F] * 14})
    assert not plain.has_state and plain.layer_kinds() == ("mha",) * 14


# -- (vii) the benchmark's configuration ------------------------------------------

def test_the_benchmark_configuration_maps_onto_the_model_config():
    """`config.json` is the catalog row with `num_hidden_layers` and
    `layer_types` cut, and `meta.json`'s `sizes` are ModelConfig's own
    counts."""
    hf, meta = _published()
    cfg = loader.config_from_hf(hf, "lfm2-8b-a1b")
    params = jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), cfg))
    leaves = jax.tree.leaves(params)
    sizes = meta["sizes"]
    assert sum(a.size for a in leaves) == sizes["params"]
    assert sum(a.size * a.dtype.itemsize for a in leaves) \
        == sizes["weights_bytes"]
    lead = {k: v.size // 2 for k, v in params["lead0"].items()}
    attn = {k: v.size // 3 for k, v in params["run0"].items()}
    conv = {k: v.size // 9 for k, v in params["run1"].items()}
    assert sum(conv[k] for k in ("conv_in", "conv_w", "wo")) \
        == sizes["conv_mixer_params"]
    assert sum(attn[k] for k in ("wq", "wk", "wv", "wo", "q_norm",
                                 "k_norm")) == sizes["attention_params"]
    assert sum(lead[k] for k in ("w_gate", "w_up", "w_down")) \
        == sizes["dense_mlp_params"]
    experts = sum(conv[k] for k in ("w_gate", "w_up", "w_down"))
    assert experts == 32 * sizes["expert_params"]
    assert experts + conv["router"] + conv["router_bias"] \
        == sizes["expert_block_params"]
    assert sum(lead.values()) == sizes["lead_layer_params"]
    assert sum(conv.values()) == sizes["conv_expert_layer_params"]
    assert sum(attn.values()) == sizes["attention_expert_layer_params"]
    assert params["embed"].size == sizes["embed_params"]
    assert params["embed"].size * 2 == sizes["embed_bytes"]
    assert "lm_head" not in params
    assert (sizes["expert_layers"], sizes["conv_layers"],
            sizes["attention_layers"]) == (12, cfg.num_state_layers,
                                           cfg.num_cache_layers)
    assert sizes["routed_expert_bytes"] == 12 * experts * 2
    assert sizes["decode_step_fixed_bytes"] \
        == sizes["weights_bytes"] - sizes["routed_expert_bytes"]
    assert sizes["decode_step_bytes_per_expert_hit"] \
        == 12 * sizes["expert_params"] * 2
    assert cfg.kv_bytes_per_token() == sizes["kv_bytes_per_token"] == 6144
    assert cfg.state_bytes_per_slot() == sizes["state_bytes_per_slot"] \
        == 11 * sizes["state_bytes_per_layer"] == 90112
    serve = dict(zip(meta["serve"][::2], meta["serve"][1::2]))
    assert sizes["state_slots"] == int(serve["--max-slots"]) \
        + EngineConfig().max_prefill_batch
    assert sizes["state_bytes_reserved"] \
        == sizes["state_slots"] * sizes["state_bytes_per_slot"]
    assert sizes["kv_page_bytes"] == 64 * sizes["kv_bytes_per_token"]
    assert sizes["kv_pages_reserved_bytes"] \
        == int(serve["--num-pages"]) * sizes["kv_page_bytes"]
    assert sizes["resident_reserved_bytes"] == (
        sizes["weights_bytes"] + sizes["state_bytes_reserved"]
        + sizes["kv_pages_reserved_bytes"])
    # the two new expressions divide by exactly these constants
    for name in ("device.shortconv_window_roofline",
                 "device.shortconv_mixed_roofline"):
        with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                               f"{name}.json")) as f:
            text = f.read()
        assert f'"const": {sizes["decode_step_fixed_bytes"]}' in text
        assert f'"const": {sizes["decode_step_bytes_per_expert_hit"]}' \
            in text
    # nothing but the depth and the kinds' list differs from the catalog
    guide = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(guide):
        with open(guide) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "LFM2-8B-A1B")
        changed = {k for k, v in row["config"].items() if hf.get(k) != v}
        assert changed == {"num_hidden_layers", "layer_types"}
        assert hf["layer_types"] == row["config"]["layer_types"][:14]
        assert meta["source"] == row["source_url"]
        assert meta["published"] == row["config"]


# -- (viii) the host's accounting of a tail-only state ----------------------------

def test_the_state_bytes_of_a_tail_only_state():
    """`_account_linattn` for the benchmark's configuration: a step's live
    rows x 90 112 B x 2, every conv layer's tail once each way; a window
    counts its steps and keeps its bytes apart."""
    cfg = loader.config_from_hf(_published()[0], "lfm2-8b-a1b")
    stats = type("S", (), {k: 0 for k in (
        "linattn_tokens_total", "linattn_inplace_updates_total",
        "linattn_flat_steps_total", "linattn_state_bytes_total",
        "linattn_steps_total", "linattn_window_state_bytes_total",
        "linattn_window_steps_total", "linattn_chunk_tokens_total",
        "state_slots_used")})()
    eng = type("E", (), {})()
    eng.model_cfg = cfg
    eng.ledger = type("L", (), {"stats": stats})()
    eng.scheduler = type("Sch", (), {"state_slots": type(
        "Sl", (), {"used": 8})()})()
    NativeEngine._account_linattn(eng, tokens=71, rows=8, inplace=7,
                                  flat=True)
    assert stats.linattn_state_bytes_total == 8 * 90112 * 2
    assert stats.linattn_tokens_total == 71 * 11
    assert stats.linattn_inplace_updates_total == 7 * 11
    assert stats.linattn_chunk_tokens_total == 71 * 11
    assert (stats.linattn_steps_total, stats.linattn_flat_steps_total) \
        == (1, 1)
    NativeEngine._account_linattn(eng, tokens=64, rows=8, window_steps=8)
    assert stats.linattn_window_state_bytes_total == 8 * 90112 * 2
    assert stats.linattn_window_steps_total == 8
    assert stats.linattn_inplace_updates_total == (7 + 64) * 11
    assert stats.state_slots_used == 8


def test_the_gauges_read_the_model_config():
    eng = NativeEngine(TINY, EngineConfig(**ENGINE_KW), seed=0)
    assert LEDGER_STATS.state_bytes_per_slot == TINY.state_bytes_per_slot()
    assert eng.metrics().kv_page_bytes == 16 * TINY.kv_bytes_per_token()


# -- the benchmark's copy of the reference ------------------------------------------

def test_the_benchmarks_copy_of_the_reference_is_this_one():
    """benchmark/reference/lfm2.py imports nothing from dynamo_tpu and
    must not drift from models/reference.py, tails included
    (benchmark/tests/test_lfm2_cell.py holds the same line from its side,
    and the blocked form the chip runs to it)."""
    spec = importlib.util.spec_from_file_location(
        "bench_ref_lfm2", os.path.join(ROOT, "benchmark", "reference",
                                       "lfm2.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "rehearsal-tiny-lfm2", "config.json")) as f:
        hf = json.load(f)
    cfg = loader.config_from_hf(hf, "tiny")
    params = llama.init_params(jax.random.PRNGKey(5), cfg)
    tokens = np.random.default_rng(5).integers(0, cfg.vocab_size, 60)
    ours_tails, theirs_tails = [], []
    ours = np.asarray(reference.forward(
        params, tokens, tails=ours_tails, **reference.arch_kwargs(cfg)))
    theirs = np.asarray(mod.forward(params, tokens, hf, theirs_tails))
    np.testing.assert_array_equal(ours, theirs)
    assert len(ours_tails) == 11
    for a, b in zip(ours_tails, theirs_tails):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert 0.3 < np.std(ours) < 3.0      # the logits spread over a few nats


# -- what a tail beside no pages is not served with -------------------------------

@pytest.mark.parametrize("engine_kw, model_kw, says", [
    (dict(host_pages=8), {}, "host / disk KV tiers"),
    (dict(host_pages=8, stream_pages=2), {}, "streamed decode"),
    (dict(spec_decode="ngram"), {}, "no rollback"),
    (dict(kv_quant="int8"), {}, "kv_quant='int8'"),
    ({}, dict(quant="int8"), "quant='int8'"),
    ({}, dict(decode_kernel="interpret"), "decode_kernel='interpret'"),
], ids=["host-tier", "streamed-decode", "speculative-verify", "kv-quant",
        "weight-quant", "pallas-decode-kernel"])
def test_what_a_conv_state_is_not_served_with_is_refused(
        engine_kw, model_kw, says):
    with pytest.raises(ValueError, match="short-convolution layers keep a "
                                         "convolution tail") as e:
        NativeEngine(dataclasses.replace(TINY, **model_kw),
                     EngineConfig(**dict(ENGINE_KW, **engine_kw)), seed=0)
    assert says in str(e.value) and "3072 bytes" in str(e.value)


def test_a_mesh_and_the_page_movers_are_refused_and_prefix_reuse_is_off():
    from dynamo_tpu.parallel.mesh import make_mesh
    with pytest.raises(ValueError, match="convolution tail.*mesh"):
        NativeEngine(TINY, EngineConfig(**dict(ENGINE_KW, tp=2)),
                     mesh=make_mesh(tp=2), seed=0)
    eng = NativeEngine(TINY, EngineConfig(**ENGINE_KW), seed=0)
    with pytest.raises(ValueError, match="whole-page extraction"):
        eng.extract_pages([0])
    with pytest.raises(ValueError, match="disagg transfer"):
        eng.allocate_remote(EngineRequest("r", [3, 4, 5], SamplingParams()))
    refuse_unserved(TINY, EngineConfig())
    prompt = list(range(2, 50))
    eng.generate(prompt, SamplingParams(max_tokens=2, temperature=0.0,
                                        ignore_eos=True), "a")
    assert eng.scheduler.peek_prefix(prompt) == 0


def test_a_kinds_list_must_name_every_layer():
    with pytest.raises(ValueError, match="layer_types"):
        dataclasses.replace(TINY, layer_types=(C, F)).layer_kinds()
    with pytest.raises(ValueError, match="layer_types"):
        dataclasses.replace(
            TINY, layer_types=("sliding_attention",) * 8).layer_kinds()
    assert ModelConfig().layer_kinds() == ("mha", "mha")
    assert not ModelConfig().has_conv
