"""Ling-3.0-flash-VL's language model, the parts around the model: the
recurrent-state slots through `Scheduler` (taken at the first chunk, freed
at finish / abort / preemption, which is by recompute), the pipelined decode
loop's fallback, what is refused and where, and the configuration file.
tests/test_ling.py has the served path against the reference.
"""
import dataclasses
import json
import os

import jax
import numpy as np
import pytest

from dynamo_tpu.engine.config import (
    EngineConfig, ModelConfig, refuse_unserved,
)
from dynamo_tpu.engine.engine import NativeEngine
from dynamo_tpu.engine.kv_cache import StateSlots
from dynamo_tpu.engine.scheduler import EngineRequest, SamplingParams
from dynamo_tpu.models import llama
from dynamo_tpu.models.loader import config_from_hf
from tests.test_ling import TINY, TOL, readings, reference_logits
from tests.test_olmoe import ENGINE_KW, Recorder

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- the state slots through the scheduler ------------------------------------

def _requests(eng, n, length=20, gen=6, prefix="s"):
    rng = np.random.default_rng(11)
    for i in range(n):
        eng.add_request(EngineRequest(
            f"{prefix}{i}", rng.integers(2, TINY.vocab_size, length).tolist(),
            SamplingParams(max_tokens=gen, temperature=0.0,
                           ignore_eos=True)))


def test_state_slots_are_taken_at_the_first_chunk_and_freed():
    eng = NativeEngine(TINY, EngineConfig(**ENGINE_KW), seed=0)
    slots = eng.scheduler.state_slots
    assert slots.n == ENGINE_KW["max_slots"] + 8 and slots.used == 0
    _requests(eng, 3, length=40, gen=30)
    assert slots.used == 0                   # queued: nothing held yet
    eng.step()
    held = {s.request_id: s.state_slot for s in
            list(eng.scheduler.waiting) + [
                r for r in eng.scheduler.running if r is not None]}
    assert slots.used == sum(v >= 0 for v in held.values()) > 0
    assert len({v for v in held.values() if v >= 0}) == slots.used
    # abort frees at once, whether prefilling or decoding
    for _ in range(3):
        eng.step()
    before = slots.used
    assert eng.abort("s0") and slots.used == before - 1
    while eng.has_work():
        eng.step()
    assert slots.used == 0


def test_no_free_state_slot_blocks_admission_like_a_missing_decode_slot():
    pool = StateSlots(2)
    a, b = pool.take(), pool.take()
    assert {a, b} == {0, 1} and pool.take() == -1 and pool.used == 2
    pool.give(a)
    assert pool.take() == a
    with pytest.raises(AssertionError):
        pool.give(b), pool.give(b)


def test_preempt_then_resume_recomputes_the_state(monkeypatch):
    """A preempted sequence gives its slot back and resumes by recompute
    from position 0 (no prefix to reclaim): every logit it is served
    afterwards is still the reference's."""
    rec = Recorder(monkeypatch)
    eng = NativeEngine(TINY, EngineConfig(**ENGINE_KW), seed=0)
    rng = np.random.default_rng(9)
    prompts = [rng.integers(2, TINY.vocab_size, n).tolist()
               for n in (33, 25)]
    for i, p in enumerate(prompts):
        eng.add_request(EngineRequest(f"p{i}", p, SamplingParams(
            max_tokens=12, temperature=0.0, ignore_eos=True)))
    got = {"p0": [], "p1": []}
    preempted = False
    for _ in range(200):
        for ev in eng.step():
            if ev.token is not None:
                got[ev.request_id].append(ev.token)
        running = [s for s in eng.scheduler.running if s is not None]
        if not preempted and len(running) == 2 \
                and all(len(s.output) >= 3 for s in running):
            used = eng.scheduler.state_slots.used
            eng.scheduler._preempt_one()
            assert eng.scheduler.state_slots.used == used - 1
            victim = eng.scheduler.waiting[0]
            assert victim.state_slot == -1 and victim.num_cached == 0
            preempted = True
        if not eng.has_work():
            break
    assert preempted and [len(v) for v in got.values()] == [12, 12]
    seqs = [p + got[f"p{i}"] for i, p in enumerate(prompts)]
    largest, median, _ = readings(
        rec.entries, seqs,
        reference_logits(jax.device_get(eng.params), seqs))
    assert largest < TOL[0] and median < TOL[1], (largest, median)


def test_prefix_reuse_is_off_and_says_so(caplog):
    eng = NativeEngine(TINY, EngineConfig(**ENGINE_KW), seed=0)
    prompt = list(range(2, 50))
    eng.generate(prompt, SamplingParams(max_tokens=2, temperature=0.0,
                                        ignore_eos=True), "a")
    with caplog.at_level("INFO", logger="dynamo_tpu.engine.scheduler"):
        eng.scheduler._prefix_off_logged = False
        assert eng.scheduler.peek_prefix(prompt) == 0
        assert eng.scheduler.peek_prefix(prompt) == 0
    assert sum("prefix reuse is off" in r.message
               for r in caplog.records) == 1
    seq = eng.scheduler.add_request(EngineRequest(
        "b", prompt, SamplingParams(max_tokens=2)))
    assert seq.num_cached == 0 and not seq.pages


def test_a_discarded_follow_up_window_is_committed_not_rerun(monkeypatch):
    """pipeline_depth 2: a window in flight when another row ends on a
    stop id (which no plan foresees) has ADVANCED the surviving rows'
    states; it is committed for them and not run again. Same tokens as
    the synchronous loop, and every served logit the reference's."""
    rng = np.random.default_rng(21)
    prompts = [rng.integers(2, TINY.vocab_size, 12).tolist()
               for _ in range(3)]

    def run(depth, stops=(), mp=None):
        rec = Recorder(mp) if mp is not None else None
        eng = NativeEngine(TINY, EngineConfig(**dict(
            ENGINE_KW, pipeline_depth=depth)), seed=0)
        for i, p in enumerate(prompts):
            eng.add_request(EngineRequest(f"w{i}", p, SamplingParams(
                max_tokens=30, temperature=0.0, ignore_eos=True,
                stop_token_ids=list(stops) if i == 0 else [])))
        got = {f"w{i}": [] for i in range(3)}
        for _ in range(300):
            for ev in eng.step():
                if ev.token is not None:
                    got[ev.request_id].append(ev.token)
            if not eng.has_work():
                break
        return got, eng, rec
    free, *_ = run(1)
    # request 0 stops on the first token it generates for the first time
    # at its 10th step or later: mid-window, in the pipeline's steady state
    stop = next(t for i, t in enumerate(free["w0"])
                if i >= 9 and t not in free["w0"][:i])
    want, *_ = run(1, (stop,))
    assert len(want["w0"]) < 30 == len(want["w1"])
    got, eng, rec = run(2, (stop,), monkeypatch)
    assert eng.pipeline_fallbacks > 0, "the fallback was not exercised"
    assert got == want
    seqs = [p + got[f"w{i}"] for i, p in enumerate(prompts)]
    largest, median, _ = readings(
        rec.entries, seqs,
        reference_logits(jax.device_get(eng.params), seqs), strays=True)
    assert largest < TOL[0] and median < TOL[1], (largest, median)


# -- what is refused, in one place --------------------------------------------

@pytest.mark.parametrize("engine_kw, model_kw, says", [
    (dict(host_pages=8), {}, "host / disk KV tiers"),
    (dict(host_pages=8, stream_pages=2), {}, "streamed decode"),
    (dict(spec_decode="ngram"), {}, "no rollback"),
    (dict(kv_quant="int8"), {}, "kv_quant='int8'"),
    ({}, dict(quant="int8"), "quant='int8'"),
    ({}, dict(decode_kernel="interpret"), "decode_kernel='interpret'"),
], ids=["host-tier", "streamed-decode", "speculative-verify", "kv-quant",
        "weight-quant", "pallas-decode-kernel"])
def test_what_a_recurrent_state_is_not_served_with_is_refused(
        engine_kw, model_kw, says):
    with pytest.raises(ValueError, match="recurrent state") as e:
        NativeEngine(dataclasses.replace(TINY, **model_kw),
                     EngineConfig(**dict(ENGINE_KW, **engine_kw)), seed=0)
    assert says in str(e.value)


def test_a_mesh_is_refused():
    from dynamo_tpu.parallel.mesh import make_mesh
    with pytest.raises(ValueError, match="recurrent state.*mesh"):
        NativeEngine(TINY, EngineConfig(**dict(ENGINE_KW, tp=2)),
                     mesh=make_mesh(tp=2), seed=0)


def test_page_moves_are_refused_by_name():
    eng = NativeEngine(TINY, EngineConfig(**ENGINE_KW), seed=0)
    with pytest.raises(ValueError, match="whole-page extraction"):
        eng.extract_pages([0])
    with pytest.raises(ValueError, match="whole-page injection"):
        eng.inject_pages([0], None, None)
    with pytest.raises(ValueError, match="shared KV pool"):
        eng.attach_kv_pool(object(), "w0")
    with pytest.raises(ValueError, match="disagg transfer"):
        eng.allocate_remote(EngineRequest("r", [3, 4, 5], SamplingParams()))
    # every other model passes the same call
    refuse_unserved(ModelConfig(), EngineConfig(), feature="anything")


def test_a_share_needs_the_dropless_dispatch():
    cfg = dataclasses.replace(TINY, num_experts=8, experts_held=4,
                              expert_first=0, moe_n_group=1,
                              moe_topk_group=1)
    with pytest.raises(ValueError, match="experts_held=4"):
        NativeEngine(cfg, EngineConfig(**ENGINE_KW), seed=0)


# -- the configuration file ----------------------------------------------------

def _published():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "ling-3.0-flash-vl", "config.json")) as f:
        return json.load(f)


def test_the_benchmark_configuration_maps_onto_the_model_config():
    cfg = config_from_hf(_published(), name="ling")
    assert cfg.layer_kinds() == ("kda", "kda", "kda", "kda", "kda", "mla",
                                 "kda", "kda")
    assert [(r.key, r.first, r.count, r.dense, r.kind, r.store_first)
            for r in llama.layer_runs(cfg)] == [
        ("run0", 0, 2, True, "kda", 0), ("run1", 2, 3, False, "kda", 2),
        ("run2", 5, 1, False, "mla", 0), ("run3", 6, 2, False, "kda", 5)]
    assert (cfg.num_experts, cfg.experts_held, cfg.expert_first,
            cfg.moe_n_group, cfg.moe_topk_group) == (512, 128, 0, 8, 4)
    assert (cfg.hidden_size, cfg.num_heads, cfg.linear_head_dim,
            cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.head_dim, cfg.intermediate_size,
            cfg.dense_intermediate_size, cfg.shared_expert_size,
            cfg.num_experts_per_tok, cfg.vocab_size) == (
        2560, 32, 128, 512, 128, 64, 128, 768, 6144, 768, 8, 39296)
    assert cfg.mla_qk_norm and cfg.mla_gate and cfg.moe_router_bias
    assert cfg.moe_scoring == "sigmoid" and cfg.moe_routed_scale == 2.5
    assert cfg.linear_gate_lower_bound == -5.0 and cfg.rms_norm_eps == 1e-6
    assert cfg.kv_cache_leaves() == {"k": (1, 576)}
    assert cfg.kv_bytes_per_token() == 1152
    assert cfg.state_bytes_per_slot() == 7 * (32 * 128 * 128 * 4
                                              + 3 * 12288 * 2)


@pytest.mark.parametrize("key, value", [
    ("use_kda_lora", True), ("use_mla_nope", True), ("use_nGPT", True),
    ("value_norm", True), ("up_proj_norm", True),
    ("scale_router_input", True), ("q_lora_rank", 1536),
    ("kda_safe_gate", False), ("num_nextn_predict_layers", 1),
    ("score_function", "softmax"),
    ("gated_attention_proj_granularity_type", "element_wise"),
    ("expert_swiglu_limit_list", [0] * 7 + [4] + [0] * 34)])
def test_what_is_not_modelled_is_refused_by_key(key, value):
    with pytest.raises(ValueError, match=key):
        config_from_hf({**_published(), key: value})


def test_a_checkpoint_is_refused(tmp_path):
    from dynamo_tpu.models.loader import load_params_from_hf
    (tmp_path / "model.safetensors").write_bytes(b"")
    with pytest.raises(ValueError, match="no checkpoint mapping"):
        load_params_from_hf(str(tmp_path), config_from_hf(_published()))


def test_the_older_models_have_one_kind_and_no_state():
    for cfg in (ModelConfig(), ModelConfig(num_experts=4),
                ModelConfig(kv_lora_rank=16, qk_nope_head_dim=8,
                            qk_rope_head_dim=4, num_experts=16,
                            first_dense_layers=1)):
        assert not cfg.has_state and cfg.state_leaves() == {}
        assert cfg.num_cache_layers == cfg.num_layers
        assert all(r.store_first == r.first for r in llama.layer_runs(cfg))
        assert llama.init_state(cfg, 4) == {}


# -- one compile a window program ---------------------------------------------

def test_a_window_program_compiles_once_staged_or_chained(caplog):
    """A freshly staged window and a chained one (fed the last window's
    device-resident carry) are one compile of one program: the staged
    operands are put with the sharding a window's outputs have. Beside an
    uncommitted staged carry XLA compiled every window program twice, the
    second time wherever its first chained window fell: inside the
    measured window of `ling-3.0-flash-vl.decode-closed` (PERF.md
    section 6, PR 33). Any model."""
    eng = NativeEngine(ModelConfig(dtype="float32"), EngineConfig(**dict(
        ENGINE_KW, pipeline_depth=2)), seed=0)
    rng = np.random.default_rng(0)
    for i in range(2):
        eng.add_request(EngineRequest(
            f"c{i}", rng.integers(2, 100, 12).tolist(), SamplingParams(
                max_tokens=40, temperature=0.0, ignore_eos=True)))
    jax.config.update("jax_log_compiles", True)
    try:
        with caplog.at_level("WARNING", logger="jax._src.dispatch"):
            while eng.has_work():
                eng.step()
    finally:
        jax.config.update("jax_log_compiles", False)
    assert eng.pipeline_overlapped > 0       # chained windows did run
    windows = [k for k in eng._seen_programs if k[0] == "window"]
    compiled = [r for r in caplog.records if "Finished XLA compilation of "
                "jit(engine_decode_window" in r.getMessage()]
    assert len(compiled) == len(windows) > 0, (len(compiled), windows)


@pytest.mark.parametrize("spec, cap, want", [
    ("", 512, EngineConfig.prefill_buckets),
    ("16,32,64,128,256", 256, (16, 32, 64, 128, 256)),
    ("64,16", 40, (16, 64)),
    ("16,32", 64, None), ("0,64", 64, None)],
    ids=["default", "the-cells", "sorted", "cap-has-no-bucket", "no-token"])
def test_the_launchers_prefill_buckets(spec, cap, want):
    """--prefill-buckets names the ladder of chunk buckets (a deployment
    of short prompts drops the buckets it never fills; the benchmark's
    warm-up sizes its probes from the ladder it reads off the engine);
    --max-prefill-chunk keeps its meaning and must have a bucket."""
    from dynamo_tpu.run import chunk_buckets
    if want is None:
        with pytest.raises(ValueError, match="--prefill-buckets"):
            chunk_buckets(spec, cap)
    else:
        assert chunk_buckets(spec, cap) == want


@pytest.mark.parametrize("flags, want", [
    ([], (EngineConfig.max_prefill_batch, EngineConfig.decode_steps)),
    (["--max-prefill-batch", "3", "--decode-steps", "4"], (3, 4))],
    ids=["default", "the-cell"])
def test_the_launchers_batch_and_window_flags(monkeypatch, flags, want):
    """--max-prefill-batch and --decode-steps are `EngineConfig`'s own
    fields: the prompts whose chunks may share a step (and the state slots
    beyond --max-slots), and the steps of a full decode window. Left out,
    the engine's defaults."""
    import asyncio
    import sys

    import dynamo_tpu.engine.engine as engine_mod
    from dynamo_tpu import run as launcher

    class Built(Exception):
        pass

    def record(model_cfg, eng_cfg, **kw):
        raise Built(eng_cfg)

    monkeypatch.setattr(engine_mod, "NativeEngine", record)
    monkeypatch.setattr(sys, "argv", ["dynamo_tpu.run", "in=none",
                                      "out=native", "tiny", *flags])
    with pytest.raises(Built) as built:
        asyncio.run(launcher.amain())
    cfg = built.value.args[0]
    assert (cfg.max_prefill_batch, cfg.decode_steps) == want
