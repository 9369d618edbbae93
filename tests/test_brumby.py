"""Brumby (`brumby`): Qwen3's block with every layer's attention replaced by
gated power retention at degree 2. The first model the engine serves
without a single page: its whole context is a matrix state a key-value head
in the state slots. The plain reference's non-mixer parts against
`transformers`' own `Qwen3ForCausalLM`, the served path against the
reference on LOGITS and on the slots' S and z, the state slots through the
scheduler, what is refused, the loader, the host's accounting and the
benchmark's configuration.

Tiny widths with everything present: 3 layers, GQA 4 / 2 of 16-wide heads
(F = 144 features), head-wise QK-norm, RoPE, an untied head, the gate's
bias drawn over 10..1000 tokens of memory.
"""
import dataclasses
import functools
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.config import (
    EngineConfig, ModelConfig, kv_heads_per_row, kv_row_lanes,
    refuse_unserved,
)
from dynamo_tpu.engine.engine import NativeEngine
from dynamo_tpu.engine.scheduler import EngineRequest, SamplingParams
from dynamo_tpu.models import llama, loader, reference
from dynamo_tpu.observability import profile
from dynamo_tpu.observability.ledger import LEDGER_STATS
from dynamo_tpu.observability.metrics import SCOPES, scope_family
from dynamo_tpu.ops import power_retention as pr
from tests.test_ling import readings
from tests.test_olmoe import ENGINE_KW, Recorder, drive

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY = ModelConfig(
    name="tiny-brumby", vocab_size=128, hidden_size=64, num_layers=3,
    num_heads=4, num_kv_heads=2, head_dim=16, intermediate_size=96,
    qk_norm="head", retention_degree=2, rope_theta=1e6, rms_norm_eps=1e-6,
    dtype="float32", max_model_len=256)
F = 144         # 16 x (16 / 2 + 1)

# Two readings a comparison in float32, over served positions, of max
# |logit difference| over the vocabulary (logits have a standard deviation
# of ~1). Both sides compute in float32 from the same weights; they differ
# in summation order and in the FORM of the mixer: the quadratic form's
# weight (q . k)^2 against phi(q) . phi(k), F products that cancel to it
# (tests/test_retention_kernel.py has the arithmetic). Read on this CPU:
# largest 2.1e-5, median 2.6e-6 (seed 0), so the limits are ten and twelve
# times the readings. The mutations are judged on the median: each must
# read 1000 times its limit.
TOL = (2e-4, 3e-5)
REQUESTS = ((70, 10), (37, 9), (21, 6))


def reference_logits(params, seqs, cfg=TINY):
    arch = reference.arch_kwargs(cfg)
    return [np.asarray(reference.forward(params, jnp.asarray(s), **arch))
            for s in seqs]


def served_run(monkeypatch, cfg=TINY, seed=0, requests=REQUESTS,
               **engine_kw):
    rec = Recorder(monkeypatch)
    eng = NativeEngine(cfg, EngineConfig(**dict(ENGINE_KW, **engine_kw)),
                       seed=seed)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(2, cfg.vocab_size, n).tolist()
               for n, _ in requests]
    outs = drive(eng, prompts, [g for _, g in requests])
    assert [len(o) for o in outs] == [g for _, g in requests]
    return rec.entries, [p + o for p, o in zip(prompts, outs)], eng


# -- (iv) the reference's non-mixer parts against transformers' Qwen3 ----------

HF_TINY = dict(
    vocab_size=96, hidden_size=64, intermediate_size=80,
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, rope_theta=1e6, rms_norm_eps=1e-6, hidden_act="silu",
    tie_word_embeddings=False, max_position_embeddings=256,
    attention_bias=False, use_sliding_window=False, sliding_window=None,
    max_window_layers=2)


def test_everything_but_the_mixer_is_transformers_qwen3(tmp_path,
                                                        monkeypatch):
    """models/reference.forward on the loader's arrays against
    `Qwen3ForCausalLM` (torch, float32), with the reference's mixer
    switched to softmax for this test alone (`attention_retention(...,
    softmax=True)`: Qwen3's own, behind the SAME front): head-wise
    QK-norm, RoPE at theta 1e6, both block norms, the SwiGLU, the final
    norm and the untied head, every norm weight drawn away from 1. The
    file has no gate: the loader is handed zeros under the assumed name."""
    try:
        import torch
        from transformers import Qwen3Config, Qwen3ForCausalLM
    except Exception as e:   # no torch, or a transformers without the class
        pytest.skip(f"transformers' qwen3 cannot be imported: {e}")
    torch.manual_seed(0)
    model = Qwen3ForCausalLM(Qwen3Config(**HF_TINY)).float().eval()
    with torch.no_grad():
        for name, p in model.named_parameters():
            if p.ndim == 1:
                p.copy_(1 + 0.2 * torch.randn_like(p))
            else:
                p.copy_(2 * torch.randn_like(p) * p.shape[-1] ** -0.5)
    model.save_pretrained(tmp_path, safe_serialization=True)
    ids = np.random.default_rng(0).integers(0, 96, 37)
    with torch.no_grad():
        want = model(torch.tensor(ids)[None]).logits[0].numpy()
    cfg = dataclasses.replace(loader.config_from_hf(
        {**HF_TINY, "architectures": ["BrumbyForCausalLM"]}, "tiny"),
        dtype="float32")
    assert cfg.layer_kinds() == ("ret", "ret") and cfg.qk_norm == "head"
    read = loader._read_all_tensors

    def with_a_gate(path):
        raw = read(path)
        for i in range(2):
            raw[f"model.layers.{i}.self_attn.g_proj.weight"] = np.zeros(
                (2, 64), np.float32)
        return raw
    monkeypatch.setattr(loader, "_read_all_tensors", with_a_gate)
    params = loader.load_params_from_hf(str(tmp_path), cfg, "float32")
    monkeypatch.setattr(reference, "attention_retention", functools.partial(
        reference.attention_retention, softmax=True))
    got = np.asarray(reference.forward(params, jnp.asarray(ids),
                                       **reference.arch_kwargs(cfg)))
    assert np.std(want) > 0.05
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


# -- (iii) the served path against the reference --------------------------------

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
        after = LEDGER_STATS.snapshot()
        delta = {k: v - before[k] for k, v in after.items()
                 if k.startswith(("linattn_", "attn_"))}
        stats = dict(mixed=m.mixed_steps, windows=m.decode_windows,
                     cache={k: (v.shape, str(v.dtype))
                            for k, v in eng.cache.items()},
                     held={k: np.asarray(v) for k, v in eng.cache.items()},
                     slots_used=eng.scheduler.state_slots.used,
                     page_bytes=m.kv_page_bytes, delta=delta,
                     slot_bytes=LEDGER_STATS.state_bytes_per_slot,
                     token_bytes=LEDGER_STATS.kv_bytes_per_token,
                     row_lanes=LEDGER_STATS.kv_row_lanes)
    return entries, seqs, params, stats


def test_served_logits_match_the_plain_reference(served_f32):
    entries, seqs, params, stats = served_f32
    largest, median, _ = readings(entries, seqs,
                                  reference_logits(params, seqs))
    assert largest < TOL[0] and median < TOL[1], (largest, median)
    assert stats["mixed"] > 0 and stats["windows"] > 0, stats


def test_the_engine_allocates_no_pool_leaf(served_f32):
    """No K / V leaf at all: the cache dict is the two state leaves, a slot
    a decode slot and a prefill-batch row and the scratch slot of the
    slot-addressed update; the page gauges read 0 and no `attn.*` series
    moved."""
    *_, stats = served_f32
    slots = ENGINE_KW["max_slots"] + EngineConfig().max_prefill_batch + 1
    assert stats["cache"] == {
        "ret_s": ((3, slots, 2, 16, F), "float32"),
        "ret_z": ((3, slots, 2, F), "float32")}
    assert stats["slots_used"] == 0          # every sequence finished
    assert stats["page_bytes"] == stats["token_bytes"] == 0
    assert stats["row_lanes"] == 0
    assert stats["slot_bytes"] == TINY.state_bytes_per_slot() \
        == 3 * 2 * (16 * F + F) * 4
    assert {k: v for k, v in stats["delta"].items()
            if k.startswith("attn_")} == {
        k: 0 for k in stats["delta"] if k.startswith("attn_")}


def test_the_state_series_count_both_forms(served_f32):
    """(viii) `_account_linattn` for this state: every (token, layer)
    update once, the prompt chunks' through the chunk form, a window's and
    a mixed step's one-token rows in place, and the bytes a step's live
    rows x layers x (S + z) x 2."""
    *_, stats = served_f32
    d = stats["delta"]
    fed = sum(n + g - 1 for n, g in REQUESTS)      # positions fed
    over = 3 * len(REQUESTS) * ENGINE_KW["decode_steps"]
    assert 3 * fed <= d["linattn_tokens_total"] <= 3 * fed + over
    assert 0 < d["linattn_chunk_tokens_total"] < d["linattn_tokens_total"]
    assert 0 < d["linattn_inplace_updates_total"] \
        < d["linattn_tokens_total"]
    assert d["linattn_window_steps_total"] > 0
    assert d["linattn_state_bytes_total"] % (
        2 * 3 * 2 * (16 * F + F) * 4) == 0


def test_account_linattn_reads_this_states_bytes():
    """A window of 2 steps over 3 live rows, then a mixed step of 5 live
    rows and 40 real tokens of which 4 rows held one: rows x layers x
    34 344 960-like bytes x 2, the kernel's updates counted a (row,
    layer)."""
    eng = NativeEngine(TINY, EngineConfig(**ENGINE_KW), seed=0)
    per_slot = TINY.state_bytes_per_slot()
    before = LEDGER_STATS.snapshot()
    eng._account_linattn(6, 6, 2)
    eng._account_linattn(40, 5, inplace=4, flat=True)
    d = {k: v - before[k] for k, v in LEDGER_STATS.snapshot().items()
         if k.startswith("linattn_")}
    assert d["linattn_state_bytes_total"] == 2 * (6 + 5) * per_slot
    assert d["linattn_window_state_bytes_total"] == 2 * 6 * per_slot
    assert d["linattn_inplace_updates_total"] == 3 * (6 + 4)
    assert d["linattn_tokens_total"] == 3 * 46
    assert d["linattn_chunk_tokens_total"] == 3 * 40
    assert (d["linattn_steps_total"], d["linattn_window_steps_total"],
            d["linattn_flat_steps_total"]) == (3, 2, 1)


@pytest.mark.parametrize("mutation", [
    "gate-of-one", "no-gate-bias", "degree-one", "no-rope", "no-q-norm",
    "no-k-norm", "softmax-mixer", "swapped-groups"])
def test_a_model_served_wrong_is_seen(served_f32, mutation, monkeypatch):
    """Each way of computing another function (a leaf or a step left out
    of the REFERENCE) reads a median a thousand times the limit against
    what was served."""
    entries, seqs, params, _ = served_f32
    arch = reference.arch_kwargs(TINY)
    layers = dict(params["layers"])
    if mutation == "gate-of-one":
        layers["ret_wg"] = np.zeros_like(layers["ret_wg"])
        layers["ret_bg"] = np.full_like(layers["ret_bg"], 40.0)
    elif mutation == "no-gate-bias":
        layers["ret_bg"] = np.zeros_like(layers["ret_bg"])
    elif mutation == "degree-one":
        # |q . k| for its square: the mask alone changes
        real = jnp.einsum

        def first_power(spec, *ops):
            out = real(spec, *ops)
            return jnp.sqrt(jnp.abs(out)) if spec == "qcgd,kcd->cgqk" \
                else out
        monkeypatch.setattr(reference.jnp, "einsum", first_power)
    elif mutation == "no-rope":
        arch["rope_theta"] = None
    elif mutation == "no-q-norm":
        layers["q_norm"] = np.ones_like(layers["q_norm"])
    elif mutation == "no-k-norm":
        layers["k_norm"] = np.ones_like(layers["k_norm"])
    elif mutation == "softmax-mixer":
        monkeypatch.setattr(
            reference, "attention_retention", functools.partial(
                reference.attention_retention, softmax=True))
    elif mutation == "swapped-groups":
        # query head h reads the OTHER key-value head
        layers["wk"] = np.concatenate(
            [layers["wk"][..., 16:], layers["wk"][..., :16]], axis=-1)
    params = dict(params, layers=layers)
    want = [np.asarray(reference.forward(params, jnp.asarray(s), **arch))
            for s in seqs]
    _, median, _ = readings(entries, seqs, want, every_position=False)
    assert median > 1000 * TOL[1], (mutation, median)


def test_the_kernels_body_serves_the_same_logits(monkeypatch):
    """The slot-addressed Pallas kernel's body (interpreted: what a CPU
    can run of it) in the served path, windows and mixed steps alike."""
    monkeypatch.setattr(pr, "retention_step_slots_impl",
                        lambda: "interpret")
    entries, seqs, eng = served_run(monkeypatch, seed=1)
    largest, median, _ = readings(
        entries, seqs, reference_logits(jax.device_get(eng.params), seqs))
    assert largest < TOL[0] and median < TOL[1], (largest, median)


@pytest.mark.parametrize("last", [1, 2, 16])
def test_a_prompt_whose_last_chunk_holds_few_tokens(monkeypatch, last):
    """Prompts of 32 + 1, 32 + 2 and 32 + 16 tokens beside a decoder: the
    last chunk of one token is a ONE-TOKEN row of its mixed step (the
    kernel's, not the chunk form's), of two the chunk form's shortest."""
    entries, seqs, eng = served_run(
        monkeypatch, seed=last, requests=((40, 8), (32 + last, 5), (9, 4)))
    largest, median, _ = readings(
        entries, seqs, reference_logits(jax.device_get(eng.params), seqs))
    assert largest < TOL[0] and median < TOL[1], (largest, median)


@pytest.mark.parametrize("slots, rows", [(4, 3), (12, 11)],
                         ids=["a-step-of-4-rows", "a-step-of-16-rows"])
def test_a_mixed_step_in_either_layout(monkeypatch, slots, rows):
    """Up to 8 busy slots a [Bb, Tb] plan is its own grid; past 8 a
    [16, 16] plan holds a `cond`, and the mixer's rows are read from the
    compact step's flat token rows. A late prompt joins `rows` decoders."""
    rec = Recorder(monkeypatch)
    eng = NativeEngine(TINY, EngineConfig(**dict(
        ENGINE_KW, max_slots=slots, num_pages=128)), seed=0)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(2, TINY.vocab_size, n).tolist()
               for n in (20,) + (9,) * (rows - 1) + (30,)]
    before = LEDGER_STATS.snapshot()["linattn_flat_steps_total"]
    got = {f"m{i}": [] for i in range(len(prompts))}
    for i, p in enumerate(prompts[:-1]):
        eng.add_request(EngineRequest(f"m{i}", p, SamplingParams(
            max_tokens=14, temperature=0.0, ignore_eos=True)))
    late = False
    for _ in range(300):
        for ev in eng.step():
            if ev.token is not None:
                got[ev.request_id].append(ev.token)
        if not late and all(len(got[f"m{i}"]) >= 2 for i in range(rows)):
            eng.add_request(EngineRequest(
                f"m{rows}", prompts[-1], SamplingParams(
                    max_tokens=5, temperature=0.0, ignore_eos=True)))
            late = True
        if late and not eng.has_work():
            break
    assert late and len(got[f"m{rows}"]) == 5
    flat = LEDGER_STATS.snapshot()["linattn_flat_steps_total"] - before
    if rows > 8:
        assert flat > 0
    seqs = [p + got[f"m{i}"] for i, p in enumerate(prompts)]
    largest, median, _ = readings(
        rec.entries, seqs,
        reference_logits(jax.device_get(eng.params), seqs))
    assert largest < TOL[0] and median < TOL[1], (largest, median)


# -- the slot's S and z -----------------------------------------------------------

def _state_after(params, seq, tokens):
    """The reference's (S, z) of every layer after `tokens` tokens."""
    tails = []
    reference.forward(params, jnp.asarray(seq[:tokens]),
                      **reference.arch_kwargs(TINY), tails=tails)
    return tails


def test_a_finished_sequences_slot_holds_the_references_state(served_f32):
    """Nothing clears a slot at its release, so it holds what its
    sequence's last step left: the per-token recurrence after the prompt
    and every generated token but the last, or after the last too where a
    window emitted it before its own last step. Every layer's S and z, to
    float32's rounding, relative to each leaf's largest entry."""
    _, seqs, params, stats = served_f32
    for slot, seq in enumerate(seqs):    # slots go out in order of admission
        near = []
        for fed in (len(seq) - 1, len(seq)):
            want = _state_after(params, seq, fed)
            near.append(max(
                float(np.abs(stats["held"][leaf][l, slot] - w).max()
                      / np.abs(w).max())
                for l, pair in enumerate(want)
                for leaf, w in zip(("ret_s", "ret_z"), pair)))
        assert min(near) < 2e-5 and max(near) > 1e-3, near


def test_the_slot_after_prefill_is_the_state_after_the_prompt(monkeypatch):
    """A prompt of three chunks and ONE generated token: the slot holds
    the recurrence after the prompt alone (the chunk form across two
    edges, never the kernel)."""
    eng = NativeEngine(TINY, EngineConfig(**ENGINE_KW), seed=2)
    prompt = np.random.default_rng(2).integers(2, 128, 75).tolist()
    eng.generate(prompt, SamplingParams(max_tokens=1, temperature=0.0,
                                        ignore_eos=True))
    want = _state_after(jax.device_get(eng.params), prompt, 75)
    for l, (s, z) in enumerate(want):
        np.testing.assert_allclose(np.asarray(eng.cache["ret_s"][l, 0]), s,
                                   rtol=2e-5, atol=2e-5 * np.abs(s).max())
        np.testing.assert_allclose(np.asarray(eng.cache["ret_z"][l, 0]), z,
                                   rtol=2e-5, atol=2e-5 * np.abs(z).max())


# -- the state slots through the scheduler --------------------------------------

def test_a_reused_slot_starts_from_zero_and_preemption_recomputes(
        monkeypatch):
    """More sequences than slots can hold at once: a finished sequence's
    slot goes to a new one, which must start from zeros; one sequence is
    preempted mid-decode, gives its slot back and resumes by recompute.
    Admission is bounded by the state slots. Every logit served is still
    the reference's."""
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
        assert slots.used <= 3
        if not preempted and len(running) == 2 \
                and all(len(s.output) >= 3 for s in running):
            used = slots.used
            eng.scheduler._preempt_one()
            victim = eng.scheduler.waiting[0]
            assert slots.used == used - 1 and victim.state_slot == -1
            assert victim.num_cached == 0
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


def test_prefix_reuse_is_off_for_a_state():
    eng = NativeEngine(TINY, EngineConfig(**ENGINE_KW), seed=0)
    prompt = list(range(2, 50))
    eng.generate(prompt, SamplingParams(max_tokens=2, temperature=0.0,
                                        ignore_eos=True), "a")
    assert eng.scheduler.peek_prefix(prompt) == 0
    seq = eng.scheduler.add_request(EngineRequest(
        "b", prompt, SamplingParams(max_tokens=2)))
    assert seq.num_cached == 0


# -- (v) ModelConfig ---------------------------------------------------------------

def test_the_kind_lies_on_the_states_axis_alone():
    assert TINY.layer_kinds() == ("ret",) * 3
    assert TINY.num_cache_layers == 0 == TINY.num_window_layers
    assert TINY.num_state_layers == 3 and TINY.has_state
    assert TINY.has_retention and not (TINY.has_ssm or TINY.has_conv)
    assert TINY.kv_cache_leaves() == {} == TINY.window_cache_leaves()
    assert TINY.state_leaves() == {"ret_s": ((2, 16, F), "float32"),
                                   "ret_z": ((2, F), "float32")}
    assert TINY.retention_features == F == pr.features(16)
    assert TINY.state_bytes_per_slot() == 3 * 2 * (16 * F + F) * 4
    assert TINY.kv_bytes_per_token() == 0
    # 16-wide heads would share a pool row, had the model a pool
    assert kv_heads_per_row(TINY) == 1 and kv_row_lanes(TINY) == 0
    assert llama.init_cache(TINY, 8, 16) == {}
    run, = llama.layer_runs(TINY)
    assert (run.key, run.kind, run.count, run.store_first) \
        == ("layers", "ret", 3, 0)
    assert llama.layer_period(TINY) is None
    assert not llama.step_attention_rows(TINY, 64)
    assert llama.mix_splits(TINY, 2, 16)
    state = llama.init_state(TINY, 5)
    assert {k: v.shape for k, v in state.items()} == {
        "ret_s": (3, 6, 2, 16, F), "ret_z": (3, 6, 2, F)}
    # the older models keep their kinds and stores
    plain = ModelConfig()
    assert plain.layer_kinds() == ("mha", "mha") and not plain.has_state
    assert set(plain.kv_cache_leaves()) == {"k", "v"}


def test_the_seeded_gate_spans_a_chunk_and_more():
    """g between 0.9 and 0.999 a head (memory 10..1000 tokens), no two
    heads alike: neither all ones nor forgetting within ten tokens."""
    params = llama.init_params(jax.random.PRNGKey(0), TINY)
    g = np.asarray(jax.nn.sigmoid(params["layers"]["ret_bg"]))
    assert g.shape == (3, 2) and 0.9 <= g.min() < g.max() <= 0.999
    assert len(set(g.reshape(-1).tolist())) == 6
    for name in ("q_norm", "k_norm", "attn_norm", "mlp_norm"):
        w = np.asarray(params["layers"][name])
        assert 0.02 < np.std(w) < 0.3 and abs(np.mean(w) - 1) < 0.1, name


@pytest.mark.parametrize("engine_kw, model_kw, says", [
    (dict(host_pages=8), {}, "host / disk KV tiers"),
    (dict(spec_decode="ngram"), {}, "no rollback"),
    (dict(kv_quant="int8"), {},
     "kv_quant='int8': no layer of it holds a page"),
    ({}, dict(quant="int8"), "quant='int8'"),
    ({}, dict(decode_kernel="interpret"),
     "decode_kernel='interpret': no layer of it holds a page"),
], ids=["host-tier", "speculative-verify", "kv-quant", "weight-quant",
        "pallas-decode-kernel"])
def test_what_the_state_is_not_served_with_is_refused(engine_kw, model_kw,
                                                      says):
    with pytest.raises(ValueError, match="power-retention layers keep "
                       "their whole context in a recurrent state") as e:
        NativeEngine(dataclasses.replace(TINY, **model_kw),
                     EngineConfig(**dict(ENGINE_KW, **engine_kw)), seed=0)
    assert says in str(e.value)
    assert str(TINY.state_bytes_per_slot()) in str(e.value)


def test_a_mesh_and_the_page_movers_are_refused_and_nothing_else():
    from dynamo_tpu.parallel.mesh import make_mesh
    with pytest.raises(ValueError, match="recurrent state.*mesh"):
        NativeEngine(TINY, EngineConfig(**dict(ENGINE_KW, tp=2)),
                     mesh=make_mesh(tp=2), seed=0)
    eng = NativeEngine(TINY, EngineConfig(**ENGINE_KW), seed=0)
    with pytest.raises(ValueError, match="whole-page extraction"):
        eng.extract_pages([0])
    with pytest.raises(ValueError, match="disagg transfer"):
        eng.allocate_remote(EngineRequest("r", [3, 4, 5], SamplingParams()))
    refuse_unserved(TINY, EngineConfig())


# -- (vi) the loader ------------------------------------------------------------------

def _config(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name,
                           "config.json")) as f:
        return json.load(f)


def test_the_loader_maps_qwen3s_names_and_the_assumed_gate(monkeypatch):
    """Every leaf from its tensor, transposed where a projection; the
    gate from `self_attn.g_proj`, its bias zeros where the file has none;
    a file without the gate's weight is refused by name."""
    cfg = dataclasses.replace(loader.config_from_hf(
        _config("rehearsal-tiny-brumby"), "tiny"), dtype="float32")
    rng = np.random.default_rng(0)
    d, hd, h, hkv, f, v = 64, 32, 4, 2, 96, 512
    shapes = {"input_layernorm.weight": (d,),
              "post_attention_layernorm.weight": (d,),
              "self_attn.q_proj.weight": (h * hd, d),
              "self_attn.k_proj.weight": (hkv * hd, d),
              "self_attn.v_proj.weight": (hkv * hd, d),
              "self_attn.o_proj.weight": (d, h * hd),
              "self_attn.q_norm.weight": (hd,),
              "self_attn.k_norm.weight": (hd,),
              "self_attn.g_proj.weight": (hkv, d),
              "mlp.gate_proj.weight": (f, d), "mlp.up_proj.weight": (f, d),
              "mlp.down_proj.weight": (d, f)}
    raw = {f"model.layers.{i}.{name}": rng.normal(size=shape).astype(
        np.float32) for i in range(3) for name, shape in shapes.items()}
    raw.update({"model.embed_tokens.weight": rng.normal(size=(v, d)),
                "model.norm.weight": rng.normal(size=(d,)),
                "lm_head.weight": rng.normal(size=(v, d))})
    raw["model.layers.1.self_attn.g_proj.bias"] = np.arange(
        2, dtype=np.float32)
    monkeypatch.setattr(loader, "_read_all_tensors", lambda path: raw)
    params = loader.load_params_from_hf("nowhere", cfg)
    want = jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), cfg))
    assert jax.tree.map(lambda a: (a.shape, str(a.dtype)), params) \
        == jax.tree.map(lambda a: (a.shape, str(a.dtype)), want)
    np.testing.assert_array_equal(
        params["layers"]["ret_wg"][2],
        raw["model.layers.2.self_attn.g_proj.weight"].T)
    np.testing.assert_array_equal(params["layers"]["ret_bg"],
                                  [[0, 0], [0, 1], [0, 0]])
    np.testing.assert_array_equal(
        params["layers"]["q_norm"][0],
        raw["model.layers.0.self_attn.q_norm.weight"])
    del raw["model.layers.0.self_attn.g_proj.weight"]
    with pytest.raises(ValueError, match="g_proj.weight.*assumed"):
        loader.load_params_from_hf("nowhere", cfg)


@pytest.mark.parametrize("key, value", [
    ("use_sliding_window", True), ("retention_degree", 3), ("degree", 4),
    ("rope_scaling", {"rope_type": "yarn", "factor": 2.0}),
    ("clip_qkv", 8.0)])
def test_what_is_not_modelled_is_refused_by_key(key, value):
    with pytest.raises(ValueError, match=key):
        loader.config_from_hf({**_config("brumby-14b"), key: value})


# -- (vii) the benchmark's reference and configuration ---------------------------

def _benchmark_reference():
    spec = importlib.util.spec_from_file_location(
        "bench_ref_brumby", os.path.join(
            ROOT, "benchmark", "reference", "brumby.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_benchmarks_copy_of_the_reference_is_this_one():
    """benchmark/reference/brumby.py imports nothing from dynamo_tpu and
    must not drift from models/reference.py
    (benchmark/tests/test_brumby_cell.py holds the same line from its
    side, and the blocked form the chip runs to it)."""
    mod = _benchmark_reference()
    hf = _config("rehearsal-tiny-brumby")
    cfg = loader.config_from_hf(hf, "tiny")
    params = llama.init_params(jax.random.PRNGKey(5), cfg)
    tokens = np.random.default_rng(5).integers(0, cfg.vocab_size, 60)
    ours = np.asarray(reference.forward(params, tokens,
                                        **reference.arch_kwargs(cfg)))
    np.testing.assert_array_equal(
        ours, np.asarray(mod.forward(params, tokens, hf)))
    assert 0.3 < np.std(ours) < 3.0      # the logits spread over a few nats


def test_the_benchmark_configuration_maps_onto_the_model_config():
    """`config.json` is the catalog row with `num_hidden_layers` cut, and
    `meta.json`'s `sizes` (every constant a per-layer metric uses) are
    ModelConfig's own counts."""
    hf = _config("brumby-14b")
    with open(os.path.join(ROOT, "benchmark", "configs", "brumby-14b",
                           "meta.json")) as f:
        meta = json.load(f)
    cfg = loader.config_from_hf(hf, "brumby-14b")
    assert cfg.layer_kinds() == ("ret",) * 8 and cfg.num_cache_layers == 0
    assert (cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size) \
        == (5120, 17408, 151936)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (40, 8, 128)
    assert cfg.qk_norm == "head" and cfg.rope_theta == 1e6
    assert cfg.rms_norm_eps == 1e-6 and not cfg.tie_word_embeddings
    assert cfg.state_leaves() == {
        "ret_s": ((8, 128, 8320), "float32"),
        "ret_z": ((8, 8320), "float32")}
    params = jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), cfg))
    leaves = jax.tree.leaves(params)
    sizes = meta["sizes"]
    assert sum(a.size for a in leaves) == sizes["params"]
    assert sum(a.size * a.dtype.itemsize for a in leaves) \
        == sizes["weights_bytes"]
    layer = {k: v.size // 8 for k, v in params["layers"].items()}
    assert sum(layer[k] for k in ("wq", "wk", "wv", "wo", "q_norm", "k_norm",
                                  "ret_wg", "ret_bg")) \
        == sizes["mixer_params"]
    assert sum(layer[k] for k in ("w_gate", "w_up", "w_down")) \
        == sizes["mlp_params"]
    assert sum(layer.values()) == sizes["layer_params"]
    assert params["embed"].size * 2 == sizes["embed_bytes"] \
        == sizes["head_bytes"]
    assert cfg.retention_features == sizes["retention_features"]
    assert cfg.state_bytes_per_slot() == sizes["state_bytes_per_slot"] \
        == 8 * sizes["state_bytes_per_layer"]
    assert sizes["retention_step_bytes_per_update"] \
        == 2 * sizes["state_bytes_per_layer"]
    assert cfg.kv_bytes_per_token() == sizes["kv_bytes_per_token"] == 0
    serve = dict(zip(meta["serve"][::2], meta["serve"][1::2]))
    assert sizes["state_slots"] == int(serve["--max-slots"]) \
        + int(serve["--max-prefill-batch"])
    # the leaf holds the scratch slot too
    assert sizes["state_bytes_reserved"] \
        == (sizes["state_slots"] + 1) * sizes["state_bytes_per_slot"]
    assert sizes["decode_step_fixed_bytes"] \
        == sizes["weights_bytes"] - sizes["embed_bytes"]
    assert sizes["resident_reserved_bytes"] \
        == sizes["weights_bytes"] + sizes["state_bytes_reserved"]
    # the constants the new expressions hold are these
    metrics = os.path.join(ROOT, "benchmark", "layer_metrics")
    for name, key in (("device.retention_step_roofline",
                       "retention_step_bytes_per_update"),
                      ("device.retention_window_roofline",
                       "decode_step_fixed_bytes"),
                      ("device.retention_mixed_roofline",
                       "decode_step_fixed_bytes")):
        with open(os.path.join(metrics, name + ".json")) as f:
            assert f'"const": {sizes[key]}' in json.dumps(json.load(f))
    # nothing but the depth differs from the catalog's row
    guide = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(guide):
        with open(guide) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Brumby-14B-Base")
        changed = {k for k, v in row["config"].items() if hf.get(k) != v}
        assert changed == {"num_hidden_layers"}
        assert meta["source"] == row["source_url"]


# -- tracing --------------------------------------------------------------------------

@pytest.mark.parametrize("path, leaf", [
    ("jit(engine_decode_window_full)/step/layers.body/retention.step/"
     "retention_step_slots/pallas_call", "retention.step"),
    ("jit(engine_step)/step/layers.body/while/body/retention.chunk/"
     "bcgf,bcvf->bcgv/dot_general", "retention.chunk"),
    ("jit(engine_step)/step/layers.body/retention.step/retention.phi/mul",
     "retention.phi"),
    ("jit(engine_step)/step/layers.body/retention.front/btd,dc->btc/"
     "dot_general", "retention.front"),
    ("jit(engine_step)/step/layers.body/retention.out/bte,ed->btd/"
     "dot_general", "retention.out")])
def test_the_mixers_time_lands_in_its_scopes(path, leaf):
    """The reducer of a capture (observability/profile.scope_of) puts the
    kernel's op under `retention.step` and never under `unscoped`; all
    five scopes are on the closed list, in one family."""
    assert leaf in SCOPES
    assert profile.scope_of(path) == leaf
    assert scope_family(leaf) == "retention"
