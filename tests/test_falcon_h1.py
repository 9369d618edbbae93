"""Falcon-H1 (`falcon_h1`): a Mamba-2 state-space mixer BESIDE grouped-query
attention in every block, so one layer holds K / V pages and a recurrent
state at once; muP multipliers on every branch. The plain reference
(dynamo_tpu/models/reference.py) against `transformers`' own class, the
served path against the reference on LOGITS, the three forms of the scan
against each other, the state slots through the scheduler, what is refused,
and the benchmark's configuration.

Tiny widths with everything present: 3 blocks, GQA 4 / 2, 6 mixer heads of 8
in 2 groups with a state of 16, a biased convolution of 4 taps, an untied
head, and EVERY multiplier away from 1.
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
from dynamo_tpu.ops import linear_attention as la
from dynamo_tpu.ops import state_space as ss
from tests.test_ling import readings
from tests.test_olmoe import ENGINE_KW, Recorder, drive

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY = ModelConfig(
    name="tiny-falcon-h1", vocab_size=128, hidden_size=64, num_layers=3,
    num_heads=4, num_kv_heads=2, head_dim=16, intermediate_size=96,
    mamba_d_ssm=48, mamba_n_heads=6, mamba_d_head=8, mamba_n_groups=2,
    mamba_d_state=16, mamba_d_conv=4, embed_scale=5.5,
    lm_head_multiplier=0.05, attention_in_multiplier=0.9, key_multiplier=0.3,
    attention_out_multiplier=0.4, ssm_in_multiplier=0.25,
    ssm_multipliers=(0.35, 0.25, 0.18, 0.5, 0.36), ssm_out_multiplier=0.09,
    mlp_multipliers=(0.18, 0.11), rope_theta=1e11, rms_norm_eps=1e-5,
    dtype="float32", max_model_len=256)

# Two readings a comparison in float32, over served positions, of max
# |logit difference| over the vocabulary (logits have a standard deviation
# of ~1 and span ~8): the largest, held to 1e-4, and the median, held to
# 3e-5. Both sides compute in float32 from the same weights; they differ in
# summation order and in the FORM of the mixer (the quadratic form over
# blocks of a chunk and a one-token form over state slots against the
# per-token recurrence) and of attention (pages against the whole
# sequence). Read on this CPU: largest 5.7e-6, median 1.5e-6 (seed 0), so
# the limits are eighteen and twenty times the readings. The mutations are
# judged on the median: each must read 1000 times its limit.
TOL = (1e-4, 3e-5)
REQUESTS = ((70, 10), (37, 9), (21, 6))


def reference_logits(params, seqs, cfg=TINY):
    arch = reference.arch_kwargs(cfg)
    return [np.asarray(reference.forward(params, jnp.asarray(s), **arch))
            for s in seqs]


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


# -- (a) the reference against transformers' own class ------------------------

HF_TINY = dict(
    vocab_size=96, hidden_size=64, intermediate_size=80,
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, mamba_d_ssm=48, mamba_n_heads=6, mamba_d_head=8,
    mamba_n_groups=2, mamba_d_state=16, mamba_d_conv=4, mamba_chunk_size=8,
    mamba_conv_bias=True, mamba_proj_bias=False,
    mamba_norm_before_gate=False, mamba_rms_norm=True, mamba_expand=2,
    embedding_multiplier=5.5, lm_head_multiplier=0.05,
    attention_in_multiplier=0.9, key_multiplier=0.3,
    attention_out_multiplier=0.4, ssm_in_multiplier=0.25,
    ssm_multipliers=[0.35, 0.25, 0.18, 0.5, 0.36], ssm_out_multiplier=0.09,
    mlp_multipliers=[0.18, 0.11], rope_theta=1e11, rms_norm_eps=1e-5,
    tie_word_embeddings=False, max_position_embeddings=256,
    attention_bias=False, mlp_bias=False, projectors_bias=False)


def test_the_reference_is_transformers_falcon_h1(tmp_path):
    """models/reference.forward on the loader's arrays against
    `FalconH1ForCausalLM` (torch, float32, its non-kernel path), every
    multiplier away from 1, a bias on the convolution, 2 groups, an untied
    head, and every neutral leaf (A_log, D, dt_bias, the norms) drawn away
    from its initial value. The prompt is not a multiple of the published
    chunk (8): transformers pads it."""
    try:
        import torch
        from transformers import FalconH1Config, FalconH1ForCausalLM
    except Exception as e:   # no torch, or a transformers without the class
        pytest.skip(f"transformers' falcon_h1 cannot be imported: {e}")
    torch.manual_seed(0)
    model = FalconH1ForCausalLM(FalconH1Config(**HF_TINY)).float().eval()
    with torch.no_grad():
        for name, p in model.named_parameters():
            if name.endswith("A_log"):
                p.copy_(torch.log(torch.arange(1, p.numel() + 1).float())
                        + 0.1 * torch.randn_like(p))
            elif name.endswith("dt_bias"):
                p.copy_(-2 + torch.randn_like(p))
            elif "conv1d.weight" in name:
                p.copy_(0.5 * torch.randn_like(p))
            elif "conv1d.bias" in name:
                p.copy_(0.2 * torch.randn_like(p))
            elif p.ndim == 1:
                p.copy_(1 + 0.2 * torch.randn_like(p))
            else:
                p.copy_(2 * torch.randn_like(p) * p.shape[-1] ** -0.5)
    model.save_pretrained(tmp_path, safe_serialization=True)
    ids = np.random.default_rng(0).integers(0, 96, 37)
    with torch.no_grad():
        want = model(torch.tensor(ids)[None]).logits[0].numpy()
    cfg, params = loader.load_model_dir(str(tmp_path), dtype="float32")
    assert cfg.layer_kinds() == ("par", "par") and cfg.embed_scale == 5.5
    got = np.asarray(reference.forward(params, jnp.asarray(ids),
                                       **reference.arch_kwargs(cfg)))
    assert np.std(want) > 0.05
    np.testing.assert_allclose(got, want, atol=2e-6)


def test_a_state_space_checkpoint_under_other_names_is_refused(monkeypatch):
    """The loader tells transformers' `falcon_h1` tensor names by the
    file's own, not by the mechanism: a model with a state-space mixer
    whose tensors are named otherwise is refused, and a model without one
    never takes these names."""
    cfg = loader.config_from_hf(
        {**HF_TINY, "architectures": ["FalconH1ForCausalLM"]}, "tiny")
    assert cfg.has_ssm
    monkeypatch.setattr(loader, "_read_all_tensors", lambda path: {
        "model.layers.0.mixer.in_proj.weight": np.zeros((2, 2), np.float32)})
    with pytest.raises(ValueError, match="falcon_h1 names them"):
        loader.load_params_from_hf("nowhere", cfg)


# -- (b) the served path against the reference --------------------------------

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
    # EVERY layer lies on the cache's layer axis AND on the state's, a slot
    # a decode slot and a prefill-batch row, and the scratch slot of the
    # slot-addressed update (llama.init_state)
    slots = ENGINE_KW["max_slots"] + EngineConfig().max_prefill_batch + 1
    assert stats["cache"] == {
        "k": ((3, 2, 64, 16, 16), "float32"),
        "v": ((3, 2, 64, 16, 16), "float32"),
        "ssm_s": ((3, slots, 6, 8, 16), "float32"),
        "ssm_conv": ((3, slots, 3, 48 + 2 * 2 * 16), "float32")}
    assert stats["slots_used"] == 0          # every sequence finished
    assert stats["page_bytes"] == 16 * TINY.kv_bytes_per_token() \
        == 16 * 3 * 2 * 2 * 16 * 4
    assert stats["slot_bytes"] == TINY.state_bytes_per_slot() \
        == 3 * (6 * 8 * 16 * 4 + 3 * 112 * 4)


def test_the_state_series_count_both_forms(served_f32):
    """The host's accounting feeds the series that mean "a recurrent
    state": every (token, layer) update once, the prompt chunks' through
    the chunk form, a window's and a mixed step's one-token rows in place."""
    *_, stats = served_f32
    d = stats["delta"]
    fed = sum(n + g - 1 for n, g in REQUESTS)      # positions fed
    # a window's steps past a row's last token are counted with it
    over = 3 * len(REQUESTS) * ENGINE_KW["decode_steps"]
    assert 3 * fed <= d["linattn_tokens_total"] <= 3 * fed + over
    assert 0 < d["linattn_chunk_tokens_total"] < d["linattn_tokens_total"]
    assert 0 < d["linattn_inplace_updates_total"] \
        < d["linattn_tokens_total"]
    assert d["linattn_window_steps_total"] > 0
    assert d["linattn_state_bytes_total"] % (
        2 * TINY.state_bytes_per_slot()) == 0


@pytest.mark.parametrize("mutation", [
    "no-ssm-branch", "no-conv-bias", "no-dt-bias", "no-d-skip", "one-group",
    "no-key-multiplier", "attention-out-multiplier", "ssm-in-multiplier",
    "ssm-multipliers-alike", "mlp-gate-multiplier", "lm-head-multiplier"])
def test_a_model_served_wrong_is_seen(served_f32, mutation):
    """Each way of computing another function (a branch, a leaf or a
    multiplier left out of the REFERENCE) reads a median a thousand times
    the limit against what was served."""
    entries, seqs, params, _ = served_f32
    arch = reference.arch_kwargs(TINY)
    par = dict(arch["par"])
    layers = dict(params["layers"])
    if mutation == "no-ssm-branch":
        par["without_ssm"] = True
    elif mutation == "no-conv-bias":
        layers["ssm_conv_b"] = np.zeros_like(layers["ssm_conv_b"])
    elif mutation == "no-dt-bias":
        layers["ssm_dt_bias"] = np.zeros_like(layers["ssm_dt_bias"])
    elif mutation == "no-d-skip":
        layers["ssm_d"] = np.zeros_like(layers["ssm_d"])
    elif mutation == "one-group":
        # every head reads group 0's B and C
        g, n, ds = TINY.mamba_n_groups, TINY.mamba_d_state, TINY.mamba_d_ssm
        w = np.array(layers["ssm_in"])
        for lo in (2 * ds, 2 * ds + g * n):
            w[:, :, lo + n:lo + 2 * n] = w[:, :, lo:lo + n]
        layers["ssm_in"] = w
    elif mutation == "no-key-multiplier":
        par["key_multiplier"] = 1.0
    elif mutation == "attention-out-multiplier":
        par["attention_out_multiplier"] = 1.0
    elif mutation == "ssm-in-multiplier":
        par["ssm"] = dict(par["ssm"], in_multiplier=1.0)
    elif mutation == "ssm-multipliers-alike":
        par["ssm"] = dict(par["ssm"], multipliers=(0.35,) * 5)
    elif mutation == "mlp-gate-multiplier":
        arch["mlp_multipliers"] = (1.0, arch["mlp_multipliers"][1])
    elif mutation == "lm-head-multiplier":
        arch["lm_head_multiplier"] = 1.0
    arch["par"] = par
    params = dict(params, layers=layers)
    want = [np.asarray(reference.forward(params, jnp.asarray(s), **arch))
            for s in seqs]
    _, median, _ = readings(entries, seqs, want, every_position=False)
    assert median > 1000 * TOL[1], (mutation, median)


def test_the_kernels_body_serves_the_same_logits(monkeypatch):
    """The slot-addressed Pallas kernel's body (interpreted: what a CPU
    can run of it) in the served path, windows and mixed steps alike."""
    monkeypatch.setattr(ss, "ssd_step_slots_impl", lambda: "interpret")
    entries, seqs, eng = served_run(monkeypatch, seed=1)
    largest, median, _ = readings(
        entries, seqs, reference_logits(jax.device_get(eng.params), seqs))
    assert largest < TOL[0] and median < TOL[1], (largest, median)


def test_a_mixed_step_of_many_rows_takes_the_flat_branch(monkeypatch):
    """More than 8 busy slots: a [16, 16] plan holds a `cond`, and the
    mixer's rows are read from the compact step's flat token rows."""
    rec = Recorder(monkeypatch)
    eng = NativeEngine(TINY, EngineConfig(**dict(
        ENGINE_KW, max_slots=12, num_pages=128)), seed=0)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(2, TINY.vocab_size, n).tolist()
               for n in (20, 9, 9, 9, 9, 9, 9, 9, 9, 9, 9, 30)]
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
        if not late and all(len(got[f"m{i}"]) >= 2 for i in range(11)):
            eng.add_request(EngineRequest("m11", prompts[-1], SamplingParams(
                max_tokens=5, temperature=0.0, ignore_eos=True)))
            late = True
        if late and not eng.has_work():
            break
    assert late and len(got["m11"]) == 5
    assert LEDGER_STATS.snapshot()["linattn_flat_steps_total"] > before
    seqs = [p + got[f"m{i}"] for i, p in enumerate(prompts)]
    largest, median, _ = readings(
        rec.entries, seqs,
        reference_logits(jax.device_get(eng.params), seqs))
    assert largest < TOL[0] and median < TOL[1], (largest, median)


# -- (c) the three forms of the scan ------------------------------------------

def _scan_inputs(rng, b, t, h=4, p=8, g=2, n=16):
    f = jnp.float32
    return dict(
        x=jnp.asarray(rng.normal(size=(b, t, h, p)), f),
        dt=jnp.asarray(np.log1p(np.exp(rng.normal(size=(b, t, h)) - 2)), f),
        b=jnp.asarray(rng.normal(size=(b, t, g, n)), f),
        c=jnp.asarray(rng.normal(size=(b, t, g, n)), f),
        a=-jnp.arange(1, h + 1, dtype=f),
        d=jnp.asarray(rng.normal(size=(h,)), f),
        s=jnp.asarray(rng.normal(size=(b, h, p, n)), f))


@pytest.mark.parametrize("lengths, chunk, block", [
    ((150, 97, 1), 64, 64), ((150, 97, 1), 32, 16), ((40, 5, 33), 16, 16),
    ((130, 64, 129), 128, 64)], ids=str)
def test_the_chunk_form_is_the_per_token_form(lengths, chunk, block):
    """`ssd_chunk` over chunks of `chunk` tokens (blocks of `block`) with
    the state carried from chunk to chunk, rows of different lengths that
    are no multiple of either and padded at dt = 0, against `ssd_step` a
    token at a time from the same initial state."""
    rng = np.random.default_rng(len(lengths) + chunk)
    t_max = -(-max(lengths) // chunk) * chunk
    v = _scan_inputs(rng, len(lengths), t_max)
    valid = jnp.arange(t_max)[None, :] < jnp.asarray(lengths)[:, None]
    dt = jnp.where(valid[:, :, None], v["dt"], 0.0)
    want_y, s = [], v["s"]
    for t in range(t_max):
        y, s_next = ss.ssd_step(v["x"][:, t], dt[:, t], v["a"], v["b"][:, t],
                                v["c"][:, t], v["d"], s)
        # a row past its end keeps its state: dt = 0 IS that
        np.testing.assert_array_equal(
            np.asarray(s_next)[~np.asarray(valid[:, t])],
            np.asarray(s)[~np.asarray(valid[:, t])])
        want_y.append(y)
        s = s_next
    want_y = jnp.stack(want_y, axis=1)
    got_y, s_c = [], v["s"]
    for lo in range(0, t_max, chunk):
        y, s_c = ss.ssd_chunk(
            v["x"][:, lo:lo + chunk], dt[:, lo:lo + chunk], v["a"],
            v["b"][:, lo:lo + chunk], v["c"][:, lo:lo + chunk], v["d"], s_c,
            block=block)
        got_y.append(y)
    got_y = jnp.concatenate(got_y, axis=1)
    m = np.asarray(valid)
    np.testing.assert_allclose(np.asarray(got_y)[m], np.asarray(want_y)[m],
                               atol=3e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(s_c), np.asarray(s), atol=3e-5,
                               rtol=1e-5)


@pytest.mark.parametrize("impl", ["plain", "interpret"])
def test_the_slot_form_is_the_per_token_form(impl):
    """`ssd_step_slots` over the whole leaf: live rows updated in their
    slots, a fresh row from zeros whatever its slot held, dead rows and
    every other slot (the scratch slot too) left as they were, the other
    layer untouched."""
    rng = np.random.default_rng(7)
    v = _scan_inputs(rng, 5, 1)
    leaf = jnp.asarray(rng.normal(size=(2, 7, 4, 8, 16)), jnp.float32)
    slots = jnp.asarray([3, -1, 0, 5, -1], jnp.int32)
    fresh = jnp.asarray([False, False, True, False, False])
    args = (v["x"][:, 0], v["dt"][:, 0], v["a"], v["b"][:, 0], v["c"][:, 0],
            v["d"])
    y, out = ss.ssd_step_slots(leaf, jnp.int32(1), slots, *args, fresh,
                               impl=impl, heads_per_block=2)
    s0 = np.array(leaf[1, jnp.asarray([3, 6, 0, 5, 6])])
    s0[2] = 0.0
    want_y, want_s = ss.ssd_step(*args, jnp.asarray(s0))
    live = np.asarray(slots) >= 0
    np.testing.assert_allclose(np.asarray(y)[live], np.asarray(want_y)[live],
                               atol=2e-6)
    for row in np.flatnonzero(live):
        np.testing.assert_allclose(np.asarray(out[1, slots[row]]),
                                   np.asarray(want_s[row]), atol=2e-6)
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(leaf[0]))
    others = np.asarray([1, 2, 4, 6])
    np.testing.assert_array_equal(np.asarray(out[1, others]),
                                  np.asarray(leaf[1, others]))


def test_the_convolutions_tail_is_carried_with_its_bias():
    """`conv_with_tail` over chunks and `conv_one_token` a token at a
    time, each continuing from the tail the last left, against the plain
    reference's convolution of the whole sequence; a bias on all three."""
    rng = np.random.default_rng(2)
    t, c, k = 45, 12, 4
    x = jnp.asarray(rng.normal(size=(t, c)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(k, c)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=(c,)), jnp.float32)
    want = np.asarray(reference.causal_conv(x, w) + bias)
    tail = jnp.zeros((1, k - 1, c), jnp.float32)
    got = []
    for lo, hi in ((0, 16), (16, 32)):      # two chunks, then 13 tokens
        y, tail = la.conv_with_tail(x[None, lo:hi], tail, w,
                                    jnp.asarray([hi - lo]), bias)
        got.append(np.asarray(y[0]))
    # a padded chunk: 5 real tokens of 16, the tail stops at the 5th
    pad = jnp.concatenate([x[32:37], jnp.zeros((11, c))])[None]
    y, tail = la.conv_with_tail(pad, tail, w, jnp.asarray([5]), bias)
    got.append(np.asarray(y[0, :5]))
    for i in range(37, t):
        y, tail = la.conv_one_token(x[None, i], tail, w, bias)
        got.append(np.asarray(y))
    np.testing.assert_allclose(np.concatenate(got), want, atol=1e-5)


# -- (d) the state slots through the scheduler --------------------------------

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


# -- (e), (f) the benchmark's reference and configuration ---------------------

def _benchmark_reference():
    spec = importlib.util.spec_from_file_location(
        "bench_ref_falcon_h1", os.path.join(
            ROOT, "benchmark", "reference", "falcon_h1.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _config(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name,
                           "config.json")) as f:
        return json.load(f)


def test_the_benchmarks_copy_of_the_reference_is_this_one():
    """benchmark/reference/falcon_h1.py imports nothing from dynamo_tpu
    and must not drift from models/reference.py
    (benchmark/tests/test_falcon_h1_cell.py holds the same line from its
    side, and the blocked form the chip runs to it)."""
    mod = _benchmark_reference()
    hf = _config("rehearsal-tiny-falcon-h1")
    cfg = loader.config_from_hf(hf, "tiny")
    params = llama.init_params(jax.random.PRNGKey(5), cfg)
    tokens = np.random.default_rng(5).integers(0, cfg.vocab_size, 60)
    ours = np.asarray(reference.forward(params, tokens,
                                        **reference.arch_kwargs(cfg)))
    np.testing.assert_array_equal(
        ours, np.asarray(mod.forward(params, tokens, hf)))
    assert 0.3 < np.std(ours) < 3.0      # the logits spread over a few nats


def test_a_finished_sequences_slot_holds_the_references_state(monkeypatch):
    """What the chip check reads back (benchmark/checks/
    reference_logits_falcon_h1.py): nothing clears a slot at its release,
    so it holds what its sequence's last step left: the per-token
    recurrence after the prompt and every generated token but the last,
    or after the last too where a window emitted it before its own last
    step (that step feeds it, and what it samples is dropped), in every
    block and head, to float32's rounding. `nearest_state` tells which;
    the other lies a token apart, and so nearly does a state rounded to
    bfloat16 after every token: the control the check's limit must fail."""
    mod = _benchmark_reference()
    spec = importlib.util.spec_from_file_location(
        "bench_check_falcon_h1", os.path.join(
            ROOT, "benchmark", "checks", "reference_logits_falcon_h1.py"))
    check = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(check)
    hf = _config("rehearsal-tiny-falcon-h1")
    cfg = dataclasses.replace(loader.config_from_hf(hf, "tiny"),
                              dtype="float32")
    _, seqs, eng = served_run(monkeypatch, cfg=cfg)
    assert eng.scheduler.state_slots.used == 0 and not eng.has_work()
    params = jax.device_get(eng.params)
    held = np.asarray(eng.cache["ssm_s"])

    def read(slot, seq, **control):
        _, both = mod.forward_blocked(
            params, jnp.asarray(seq), hf, positions=[0],
            state_tokens=len(seq) - 1, **control)
        assert both.shape == (2, 3, 8, 8, 16)
        each = [np.asarray(check.state_distances(held[:, slot], want))
                for want in both]
        return check.nearest_state(held[:, slot], both, len(seq) - 1), each

    fed = []
    for slot, seq in enumerate(seqs):    # slots go out in order of admission
        got, each = read(slot, seq)
        one_more = got["state_fed"] - (len(seq) - 1)
        fed.append(one_more)
        assert got["state_largest"] == each[one_more].max() < 1e-5
        assert each[1 - one_more].min() > 1e-2
    assert set(fed) == {0, 1}, fed       # both cases, in this one run
    got, _ = read(0, seqs[0], state_dtype=jnp.dtype("bfloat16"))
    assert got["state_fed"] == len(seqs[0]) - 1 + fed[0]
    assert got["state_first_p90"] > 2e-3


def test_the_benchmark_configuration_maps_onto_the_model_config():
    """`config.json` is the catalog row with `num_hidden_layers` cut, and
    `meta.json`'s `sizes` are ModelConfig's own counts."""
    hf = _config("falcon-h1-34b")
    with open(os.path.join(ROOT, "benchmark", "configs", "falcon-h1-34b",
                           "meta.json")) as f:
        meta = json.load(f)
    cfg = loader.config_from_hf(hf, "falcon-h1-34b")
    assert cfg.layer_kinds() == ("par",) * 6
    assert (cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size) \
        == (5120, 21504, 261120)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (20, 4, 128)
    assert cfg.state_leaves() == {
        "ssm_s": ((32, 128, 256), "float32"),
        "ssm_conv": ((3, 5120), "bfloat16")}
    assert cfg.rope_theta == 1e11 and not cfg.tie_word_embeddings
    assert cfg.lm_head_multiplier == 1 / 128
    params = jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), cfg))
    leaves = jax.tree.leaves(params)
    sizes = meta["sizes"]
    assert sum(a.size for a in leaves) == sizes["params"]
    assert sum(a.size * a.dtype.itemsize for a in leaves) \
        == sizes["weights_bytes"]
    block = {k: v.size // 6 for k, v in params["layers"].items()}
    assert sum(v for k, v in block.items() if k.startswith("ssm_")) \
        == sizes["mixer_params"]
    assert sum(block[k] for k in ("wq", "wk", "wv", "wo")) \
        == sizes["attention_params"]
    assert sum(block[k] for k in ("w_gate", "w_up", "w_down")) \
        == sizes["mlp_params"]
    assert sum(block.values()) == sizes["block_params"]
    assert params["embed"].size * 2 == sizes["embed_bytes"] \
        == sizes["head_bytes"]
    assert cfg.state_bytes_per_slot() == sizes["state_bytes_per_slot"]
    assert cfg.kv_bytes_per_token() == sizes["kv_bytes_per_token"]
    serve = dict(zip(meta["serve"][::2], meta["serve"][1::2]))
    assert sizes["state_slots"] == int(serve["--max-slots"]) \
        + int(serve["--max-prefill-batch"])
    assert sizes["state_bytes_reserved"] \
        == sizes["state_slots"] * sizes["state_bytes_per_slot"]
    assert sizes["kv_pages_reserved_bytes"] \
        == int(serve["--num-pages"]) * 64 * sizes["kv_bytes_per_token"]
    assert sizes["decode_step_fixed_bytes"] \
        == sizes["weights_bytes"] - sizes["embed_bytes"]
    assert sizes["resident_reserved_bytes"] == (
        sizes["weights_bytes"] + sizes["state_bytes_reserved"]
        + sizes["kv_pages_reserved_bytes"])
    # nothing but the depth differs from the catalog's row
    guide = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(guide):
        with open(guide) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Falcon-H1-34B-Instruct")
        changed = {k for k, v in row["config"].items() if hf.get(k) != v}
        assert changed == {"num_hidden_layers"}
        assert meta["source"] == row["source_url"]


@pytest.mark.parametrize("key, value", [
    ("mamba_norm_before_gate", True), ("mamba_rms_norm", False),
    ("mamba_conv_bias", False), ("attention_bias", True),
    ("mamba_proj_bias", True), ("projectors_bias", True),
    ("attn_layer_indices", [0, 2]), ("mamba_use_mlp", False),
    ("rope_scaling", {"rope_type": "linear", "factor": 2.0}),
    ("mamba_n_heads", 30)])
def test_what_is_not_modelled_is_refused_by_key(key, value):
    with pytest.raises(ValueError, match=key):
        loader.config_from_hf({**_config("falcon-h1-34b"), key: value})


# -- (g) what a state beside K / V pages is not served with -------------------

@pytest.mark.parametrize("engine_kw, model_kw, says", [
    (dict(host_pages=8), {}, "host / disk KV tiers"),
    (dict(host_pages=8, stream_pages=2), {}, "streamed decode"),
    (dict(spec_decode="ngram"), {}, "no rollback"),
    (dict(kv_quant="int8"), {}, "kv_quant='int8'"),
    ({}, dict(quant="int8"), "quant='int8'"),
    ({}, dict(decode_kernel="interpret"), "decode_kernel='interpret'"),
], ids=["host-tier", "streamed-decode", "speculative-verify", "kv-quant",
        "weight-quant", "pallas-decode-kernel"])
def test_what_a_state_beside_pages_is_not_served_with_is_refused(
        engine_kw, model_kw, says):
    with pytest.raises(ValueError, match="state-space mixer.*recurrent "
                                         "state") as e:
        NativeEngine(dataclasses.replace(TINY, **model_kw),
                     EngineConfig(**dict(ENGINE_KW, **engine_kw)), seed=0)
    assert says in str(e.value)


def test_a_mesh_and_the_page_movers_are_refused_and_nothing_else():
    from dynamo_tpu.parallel.mesh import make_mesh
    with pytest.raises(ValueError, match="recurrent state.*mesh"):
        NativeEngine(TINY, EngineConfig(**dict(ENGINE_KW, tp=2)),
                     mesh=make_mesh(tp=2), seed=0)
    eng = NativeEngine(TINY, EngineConfig(**ENGINE_KW), seed=0)
    with pytest.raises(ValueError, match="whole-page extraction"):
        eng.extract_pages([0])
    with pytest.raises(ValueError, match="whole-page injection"):
        eng.inject_pages([0], None, None)
    with pytest.raises(ValueError, match="shared KV pool"):
        eng.attach_kv_pool(object(), "w0")
    with pytest.raises(ValueError, match="disagg transfer"):
        eng.allocate_remote(EngineRequest("r", [3, 4, 5], SamplingParams()))
    # the plain engine passes, and so does a model without any other store
    refuse_unserved(TINY, EngineConfig())
    refuse_unserved(ModelConfig(), EngineConfig(), feature="anything")


def test_prefix_reuse_is_off_for_a_state_beside_pages():
    eng = NativeEngine(TINY, EngineConfig(**ENGINE_KW), seed=0)
    prompt = list(range(2, 50))
    eng.generate(prompt, SamplingParams(max_tokens=2, temperature=0.0,
                                        ignore_eos=True), "a")
    assert eng.scheduler.peek_prefix(prompt) == 0
    seq = eng.scheduler.add_request(EngineRequest(
        "b", prompt, SamplingParams(max_tokens=2)))
    assert seq.num_cached == 0 and not seq.pages


def test_the_older_models_keep_their_kinds_and_stores():
    """One kind a layer and one store a kind for every model but this
    one, whose kind counts on both axes."""
    assert TINY.num_cache_layers == TINY.num_state_layers == 3
    assert TINY.has_state and TINY.has_ssm
    plain = ModelConfig()
    assert plain.layer_kinds() == ("mha", "mha") and not plain.has_state
    assert plain.state_leaves() == {} and plain.state_bytes_per_slot() == 0
    from tests.test_ling import TINY as LING
    assert LING.has_state and not LING.has_ssm
    assert LING.num_cache_layers == 1 and LING.num_state_layers == 7
    assert set(LING.state_leaves()) == {"kda_s", "kda_conv"}
    run, = llama.layer_runs(TINY)
    assert (run.key, run.kind, run.store_first) == ("layers", "par", 0)
