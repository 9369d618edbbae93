"""OLMoE through the served path against the plain reference
(dynamo_tpu/models/reference.py), on LOGITS, not sampled tokens.

A tiny seeded OLMoE (2 layers, hidden 64, 4 heads of 16, 16 experts of 32,
4 a token, QK-norm, router weights not renormalised) is driven through the
real NativeEngine: prompts that cross page and chunk boundaries prefill in
chunks, one of them riding mixed steps beside a running decode, then every
request decodes through the cache and the decode window, with padding rows
in every batch. Every logits array the model functions produce on the way
is recorded (a jax.debug.callback around llama.forward / decode_forward)
and compared, position by position, with the reference's ONE full forward
pass over prompt + generated tokens.

The tolerance is shown to be tight: the same comparison FAILS when the
router renormalises, QK-norm is skipped or applied per head, one assignment
in a hundred is dropped, or the engine computes in a lower precision than
the configuration states.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.config import EngineConfig, ModelConfig
from dynamo_tpu.engine.engine import NativeEngine
from dynamo_tpu.engine.scheduler import EngineRequest, SamplingParams
from dynamo_tpu.models import llama, reference
from dynamo_tpu.ops import moe

TINY = ModelConfig(
    name="tiny-olmoe", vocab_size=128, hidden_size=64, intermediate_size=32,
    num_layers=2, num_heads=4, num_kv_heads=4, head_dim=16,
    rope_theta=10000.0, rms_norm_eps=1e-5, max_model_len=256,
    qk_norm=True, num_experts=16, num_experts_per_tok=4,
    norm_topk_prob=False, dtype="float32")

ENGINE_KW = dict(page_size=16, num_pages=64, max_slots=4,
                 max_prefill_chunk=32, prefill_buckets=(8, 16, 32),
                 max_model_len=256, decode_steps=4, pipeline_depth=1)

# Two readings a comparison: the largest and the median, over positions, of
# max |logit difference| over the vocabulary. Logits are O(1) (unit-variance
# rows through a fan-in-scaled head). Why these limits:
# float32: both sides compute in float32 from the same weights and differ in
# summation order only (paged attention merges blocks, the grouped matmul
# sums per expert). The served path read a largest difference of 1.9e-6 to
# 2.8e-6 over seeds 0..3 on this CPU, so 5e-5 is eighteen times the worst
# reading; the mildest mutation (one assignment in a hundred dropped) reads
# 1.05, twenty thousand times the limit.
# bfloat16: the weights are the same bf16 values on both sides (the
# reference upcasts them), but the engine rounds every activation and the
# stored K/V to 8 bits of mantissa, and now and then that flips a near-tie
# between the 4th and 5th expert, which moves a position by a whole expert's
# weighted output: largest 0.39 to 0.58, median 0.018 to 0.029 over the same
# seeds, so the limits are 0.8 and 0.04. A wrong router or QK-norm reads a
# median of 0.52 to 0.77. A dropped assignment IS the size of such a flip,
# so in bfloat16 one in a hundred reads 1.05 / 0.052, a thin margin: that
# mutation is decided in float32, where nothing else moves the numbers.
TOL = {"float32": (5e-5, 5e-5), "bfloat16": (0.8, 0.04)}


def within(readings, dtype) -> bool:
    return all(r < t for r, t in zip(readings, TOL[dtype]))


# (prompt length, generated): 70 crosses four 16-token pages and takes
# three 32-token chunks; 37 arrives while the first decodes, so its chunks
# ride mixed steps; 21 is admitted beside two running decodes
REQUESTS = ((70, 10), (37, 9), (21, 6))


class Recorder:
    """Wraps llama.forward / llama.decode_forward: every call's logits
    leave the jitted program through a debug callback, with the tokens,
    positions and validity mask that say whose they are."""

    def __init__(self, monkeypatch):
        self.entries = []      # (token, position, logits [V])
        fwd, dec = llama.forward, llama.decode_forward

        def forward(params, cfg, tokens, cache, meta, *a, last_idx=None,
                    **kw):
            out = fwd(params, cfg, tokens, cache, meta, *a,
                      last_idx=last_idx, **kw)
            every, real = out[0], meta.write_idx >= 0
            if last_idx is not None:
                # the engine's step hands the head the sampled rows only
                # (PR 32): those are recorded as served, and every other
                # position from the same step over the grid, which the
                # compaction tests hold it to (tests/test_mixed_steps.py)
                rows = jnp.arange(tokens.shape[0])
                jax.debug.callback(
                    self._keep, tokens[rows, last_idx],
                    meta.positions[rows, last_idx], real[rows, last_idx],
                    every)
                every = fwd(params, cfg, tokens, cache, meta, *a, **kw)[0]
            jax.debug.callback(self._keep, tokens, meta.positions, real,
                               every)
            return out

        def decode_forward(params, cfg, tokens, cache, page_table,
                           prefix_lens, positions, valid=None, **kw):
            out = dec(params, cfg, tokens, cache, page_table, prefix_lens,
                      positions, valid=valid, **kw)
            jax.debug.callback(self._keep, tokens, positions, valid, out[0])
            return out

        monkeypatch.setattr(llama, "forward", forward)
        monkeypatch.setattr(llama, "decode_forward", decode_forward)

    def _keep(self, tokens, positions, valid, logits):
        tokens, positions, valid = (np.asarray(a).reshape(-1)
                                    for a in (tokens, positions, valid))
        logits = np.asarray(logits, np.float32).reshape(len(tokens), -1)
        for i in np.nonzero(valid)[0]:
            self.entries.append((int(tokens[i]), int(positions[i]),
                                 logits[i]))


def drive(eng, prompts, gens):
    """Request 0 first; 1 once 0 has streamed two tokens; 2 once 1 has
    streamed one: admissions land mid-decode, so prefill chunks ride mixed
    steps. Returns each request's generated tokens."""
    ids = [f"r{i}" for i in range(len(prompts))]
    got = {rid: [] for rid in ids}
    waiting = list(zip(ids, prompts, gens))
    gate = {"r1": ("r0", 2), "r2": ("r1", 1)}

    def admit():
        while waiting:
            rid, prompt, n = waiting[0]
            after, count = gate.get(rid, (None, 0))
            if after is not None and len(got[after]) < count:
                return
            waiting.pop(0)
            eng.add_request(EngineRequest(rid, prompt, SamplingParams(
                max_tokens=n, temperature=0.0, ignore_eos=True)))

    admit()
    done = set()
    for _ in range(400):
        for ev in eng.step():
            if ev.token is not None:
                got[ev.request_id].append(ev.token)
            if ev.finished:
                done.add(ev.request_id)
        admit()
        if len(done) == len(ids):
            break
    assert len(done) == len(ids), sorted(done)
    return [got[rid] for rid in ids]


def served_against_reference(monkeypatch, engine_cfg, seed=0,
                             reference_cfg=TINY):
    """((largest, median) over positions of max |logit difference|, the
    engine): every position the served path computed, each once or more."""
    rec = Recorder(monkeypatch)
    eng = NativeEngine(engine_cfg, EngineConfig(**ENGINE_KW), seed=seed)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(2, TINY.vocab_size, n).tolist()
               for n, _ in REQUESTS]
    outs = drive(eng, prompts, [g for _, g in REQUESTS])
    seqs = [p + o for p, o in zip(prompts, outs)]
    assert [len(o) for o in outs] == [g for _, g in REQUESTS]
    # the reference reads the ENGINE's weights, upcast: one full forward
    # pass a sequence, no cache
    params = jax.device_get(eng.params)
    arch = reference.arch_kwargs(reference_cfg)
    want = [np.asarray(reference.forward(params, jnp.asarray(s), **arch))
            for s in seqs]
    found, seen = [], [set() for _ in seqs]
    for token, pos, logits in rec.entries:
        errs = [(float(np.max(np.abs(logits - want[i][pos]))), i)
                for i, s in enumerate(seqs)
                if pos < len(s) and s[pos] == token]
        assert errs, f"token {token} at {pos} belongs to no request"
        err, who = min(errs)
        seen[who].add(pos)
        found.append(err)
    for s, got in zip(seqs, seen):      # every fed position was compared
        assert got >= set(range(len(s) - 1)), sorted(
            set(range(len(s) - 1)) - got)
    return (max(found), float(np.median(found))), eng


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_served_logits_match_the_plain_reference(monkeypatch, dtype):
    cfg = dataclasses.replace(TINY, dtype=dtype)
    readings, eng = served_against_reference(monkeypatch, cfg)
    assert within(readings, dtype), readings
    m = eng.metrics()
    assert m.mixed_steps > 0 and m.decode_windows > 0, m
    assert eng.moe_routed_tokens > 0 and eng.moe_dropped_tokens == 0


def _per_head_qk_norm(xn, lp, cfg):
    """QK-norm as several other models apply it: per head, after the
    split, each head with its own slice of the weight vector."""
    plain = dataclasses.replace(cfg, qk_norm=False)
    q, k, v = _QKV(xn, lp, plain)
    hd = cfg.head_dim

    def per_head(a, w):
        heads = a.reshape(a.shape[:-1] + (-1, hd))
        w = w.reshape(-1, hd)
        return llama.rms_norm(heads, w, cfg.rms_norm_eps).reshape(a.shape)
    return per_head(q, lp["q_norm"]), per_head(k, lp["k_norm"]), v


def _skip_qk_norm(xn, lp, cfg):
    return _QKV(xn, lp, dataclasses.replace(cfg, qk_norm=False))


_QKV = llama.qkv_proj


def _drop_one_in_a_hundred(x, router, k, renorm, *more):
    """The router, with every hundredth (token, choice) pair's weight set
    to zero: that assignment's expert output never reaches the sum, which
    is what a dispatch that drops it does."""
    weights, idx = _ROUTE(x, router, k, renorm, *more)
    flat = jnp.arange(weights.size).reshape(weights.shape)
    return jnp.where(flat % 100 == 37, 0.0, weights), idx


_ROUTE = moe.route_topk

MUTATIONS = {
    "router_renormalises": dict(cfg=dict(norm_topk_prob=True)),
    "qk_norm_skipped": dict(patch=(llama, "qkv_proj", _skip_qk_norm)),
    "qk_norm_per_head": dict(patch=(llama, "qkv_proj", _per_head_qk_norm)),
    "one_percent_dropped": dict(
        patch=(moe, "route_topk", _drop_one_in_a_hundred)),
    # the configuration states float32; the engine computes in bfloat16
    "lower_precision": dict(cfg=dict(dtype="bfloat16"), only="float32"),
}


@pytest.mark.parametrize("name,dtype", [
    (name, dtype) for name in sorted(MUTATIONS)
    for dtype in ("float32", "bfloat16")
    # the precision below bfloat16 is not served: no such case
    if MUTATIONS[name].get("only", dtype) == dtype])
def test_the_tolerance_is_tight(monkeypatch, name, dtype):
    """Each way of serving ANOTHER model under OLMoE's name fails the
    comparison above; in float32 by three orders of magnitude or more."""
    mutation = MUTATIONS[name]
    cfg = dataclasses.replace(TINY, **{"dtype": dtype,
                                       **mutation.get("cfg", {})})
    if "patch" in mutation:
        monkeypatch.setattr(*mutation["patch"])
    readings, _ = served_against_reference(monkeypatch, cfg)
    assert not within(readings, dtype), (name, readings)
    if dtype == "float32":
        assert readings[0] > 1000 * TOL[dtype][0], (name, readings)


def test_dropless_dispatch_equals_the_dense_oracle_under_skew():
    """E=64, k=8, routing skewed so that one expert takes half the tokens'
    first choice and several experts take nothing: the sorted dispatch
    equals the all-experts oracle (`_moe_mlp`'s form) within float32
    rounding, drops nothing, and padding rows route nowhere."""
    cfg = ModelConfig(name="skew", dtype="float32", hidden_size=32,
                      intermediate_size=16, num_experts=64,
                      num_experts_per_tok=8, norm_topk_prob=False)
    params = llama.init_params(jax.random.PRNGKey(3), cfg)
    lp = dict(jax.tree.map(lambda a: a[0], params["layers"]))
    rng = np.random.default_rng(11)
    b, t = 4, 24
    x = 0.3 * rng.standard_normal((b, t, cfg.hidden_size)).astype(
        np.float32)
    x[:, ::2, 0] = 6.0     # a feature only the even tokens have ...
    x[:, 1::2, 0] = 0.0
    x[..., 1] = 1.0        # ... and one every token has
    router = 0.2 * rng.standard_normal((cfg.hidden_size, 64)).astype(
        np.float32)
    router[0] = 0.0
    router[0, 5] = 4.0     # expert 5 listens to the first: half the tokens
    router[1, :40] = 0.0
    router[1, 40:] = -30.0  # experts 40..63 are never among the 8
    lp["router"] = jnp.asarray(router)
    valid = np.ones((b, t), bool)
    valid[1, 20:] = False
    valid[3] = False                 # a whole padding row
    out, stats = jax.jit(lambda a, v: moe.moe_dropless_mlp(
        a, lp, cfg, valid=v))(jnp.asarray(x), jnp.asarray(valid))
    oracle = llama._moe_mlp(jnp.asarray(x), lp, cfg)
    np.testing.assert_allclose(np.asarray(out)[valid],
                               np.asarray(oracle)[valid],
                               rtol=2e-5, atol=2e-5)
    assert np.all(np.asarray(out)[~valid] == 0.0)
    n_valid = int(valid.sum())
    assert float(stats["moe_dropped"]) == 0.0
    assert float(stats["moe_routed"]) == n_valid * 8
    assert float(stats["moe_expert_rows"]) >= n_valid * 8
    assert float(stats["moe_layer_calls"]) == 1.0
    # the skew is what the docstring says
    _, idx = moe.route_topk(jnp.asarray(x), lp["router"], 8, False)
    counts = np.bincount(np.asarray(idx)[valid].reshape(-1), minlength=64)
    assert counts[5] >= n_valid // 2 and np.sum(counts == 0) >= 8, counts
    assert float(stats["moe_experts_hit"]) == float(np.sum(counts > 0))


def test_the_grouped_matmul_kernel_agrees_with_ragged_dot(monkeypatch):
    """The megablox kernel's code (Pallas interpreter here; compiled on
    the chip, tools/moe_dispatch_bench.py) against `jax.lax.ragged_dot`,
    through the whole dispatch: skewed groups, an empty group, groups that
    straddle a row tile, and rows that no group owns, which come out 0."""
    cfg = ModelConfig(name="gmm", dtype="float32", hidden_size=128,
                      intermediate_size=128, num_experts=8,
                      num_experts_per_tok=2, norm_topk_prob=False)
    params = llama.init_params(jax.random.PRNGKey(2), cfg)
    lp = dict(jax.tree.map(lambda a: a[0], params["layers"]))
    rng = np.random.default_rng(2)
    x = rng.standard_normal((3, 40, 128)).astype(np.float32)
    x[..., 0] = 1.0                  # a feature every token has, which
    lp["router"] = lp["router"].at[:, 3].set(0.0).at[0, 3].set(-50.0)
    x = jnp.asarray(x)               # ... keeps expert 3 out of any top 2
    valid = np.ones((3, 40), bool)
    valid[2, 11:] = False
    want, want_stats = moe.moe_dropless_mlp(x, lp, cfg,
                                            valid=jnp.asarray(valid))
    monkeypatch.setattr(moe, "grouped_matmul_impl", lambda: "gmm-interpret")
    monkeypatch.setattr(moe, "GMM_TILING", (16, 128, 128))
    got, stats = moe.moe_dropless_mlp(x, lp, cfg, valid=jnp.asarray(valid))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    assert np.all(np.asarray(got)[~valid] == 0.0)
    for key in ("moe_routed", "moe_dropped", "moe_experts_hit"):
        assert float(stats[key]) == float(want_stats[key]), key
    assert float(stats["moe_experts_hit"]) == 7
    # tile rounding: every visited (group, tile) pair counts 16 rows
    routed = float(stats["moe_routed"])
    assert routed <= float(stats["moe_expert_rows"]) <= routed + 7 * 2 * 15
    assert float(stats["moe_expert_rows"]) % 16 == 0
    # the layer's experts read IN the model's stacked leaves, by index:
    # what the layer scan hands the kernel (no slice, so no copy)
    stacks = {k: jnp.stack([jnp.zeros_like(lp[k]), lp[k], lp[k] * 2])
              for k in llama.EXPERT_LEAVES}
    for impl in ("gmm-interpret", "ragged_dot"):
        monkeypatch.setattr(moe, "grouped_matmul_impl", lambda i=impl: i)
        stacked, _ = jax.jit(lambda a, lid: moe.moe_dropless_mlp(
            a, {**lp, **stacks}, cfg, valid=jnp.asarray(valid),
            layer=lid))(x, jnp.int32(1))
        np.testing.assert_allclose(np.asarray(stacked), np.asarray(want),
                                   rtol=2e-5, atol=2e-5, err_msg=impl)


def _all_shapes(jaxpr):
    """Every output shape in a jaxpr, nested jaxprs (pjit, scan, while)
    included, with the primitive that made it."""
    for eqn in jaxpr.eqns:
        for var in eqn.outvars:
            yield eqn.primitive.name, tuple(getattr(var.aval, "shape", ()))
        for value in eqn.params.values():
            inner = getattr(value, "jaxpr", value)
            if hasattr(inner, "eqns"):
                yield from _all_shapes(inner)


def test_no_expert_by_capacity_one_hot_on_the_dropless_path():
    """No [.., E, C] dispatch or combine tensor: on the dropless path the
    expert count meets a token axis in the router's [n, E] probabilities
    and nowhere else; the capacity path, asked the same, is caught."""
    e, k, b, t = 40, 8, 4, 16        # 40: no other axis has that size
    cfg = dataclasses.replace(TINY, num_experts=e, num_experts_per_tok=k)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    x = jnp.zeros((b, t, cfg.hidden_size), jnp.float32)

    def offenders(fn):
        shapes = set(_all_shapes(jax.make_jaxpr(fn)(x).jaxpr))
        return sorted((p, s) for p, s in shapes
                      if e in s and s not in ((b * t, e), (e,))
                      and not (len(s) == 3 and s[0] == e))   # the weights
    assert offenders(lambda a: moe.moe_dropless_mlp(a, lp, cfg)[0]) == []
    assert offenders(lambda a: moe.moe_dispatch_mlp(a, lp, cfg)) != []


def test_loader_round_trips_olmoe_tensor_names(tmp_path):
    """A safetensors directory with OLMoE's names (`self_attn.q_norm`,
    `mlp.gate`, `mlp.experts.N.{gate,up,down}_proj`) loads into the
    stacked leaves, transposed, and a `clip_qkv` that is not null is
    refused."""
    from safetensors.numpy import save_file

    from dynamo_tpu.models.loader import (
        config_from_hf, load_params_from_hf)
    hf = {"architectures": ["OlmoeForCausalLM"], "model_type": "olmoe",
          "hidden_size": 32, "intermediate_size": 16,
          "num_hidden_layers": 2, "num_attention_heads": 2,
          "num_key_value_heads": 2, "num_experts": 4,
          "num_experts_per_tok": 2, "norm_topk_prob": False,
          "clip_qkv": None, "vocab_size": 64, "rope_theta": 10000,
          "rms_norm_eps": 1e-5, "max_position_embeddings": 128,
          "tie_word_embeddings": False, "rope_scaling": None}
    cfg = dataclasses.replace(config_from_hf(hf), dtype="float32")
    assert (cfg.qk_norm, cfg.num_experts, cfg.num_experts_per_tok,
            cfg.norm_topk_prob, cfg.intermediate_size) == (
        True, 4, 2, False, 16)
    with pytest.raises(ValueError, match="clip_qkv"):
        config_from_hf(dict(hf, clip_qkv=8.0))
    # Mixtral keeps its renormalised routing through the same field
    mixtral = config_from_hf({**hf, "architectures": ["MixtralForCausalLM"],
                              "num_local_experts": 4})
    assert mixtral.norm_topk_prob and not mixtral.qk_norm

    rng = np.random.default_rng(0)
    d, f, e, v = 32, 16, 4, 64

    def r(*shape):
        return rng.standard_normal(shape).astype(np.float32)
    raw = {"model.embed_tokens.weight": r(v, d),
           "model.norm.weight": r(d), "lm_head.weight": r(v, d)}
    for i in range(2):
        p = f"model.layers.{i}."
        raw.update({
            p + "input_layernorm.weight": r(d),
            p + "post_attention_layernorm.weight": r(d),
            p + "self_attn.q_proj.weight": r(d, d),
            p + "self_attn.k_proj.weight": r(d, d),
            p + "self_attn.v_proj.weight": r(d, d),
            p + "self_attn.o_proj.weight": r(d, d),
            p + "self_attn.q_norm.weight": r(d),
            p + "self_attn.k_norm.weight": r(d),
            p + "mlp.gate.weight": r(e, d)})
        for j in range(e):
            raw[p + f"mlp.experts.{j}.gate_proj.weight"] = r(f, d)
            raw[p + f"mlp.experts.{j}.up_proj.weight"] = r(f, d)
            raw[p + f"mlp.experts.{j}.down_proj.weight"] = r(d, f)
    save_file(raw, str(tmp_path / "model.safetensors"))
    params = load_params_from_hf(str(tmp_path), cfg)
    want = jax.eval_shape(lambda: llama.init_params(jax.random.PRNGKey(0),
                                                    cfg))
    assert jax.tree.map(lambda a: a.shape, params) == jax.tree.map(
        lambda a: a.shape, want)
    layers = params["layers"]
    np.testing.assert_array_equal(
        layers["q_norm"][1], raw["model.layers.1.self_attn.q_norm.weight"])
    np.testing.assert_array_equal(
        layers["k_norm"][0], raw["model.layers.0.self_attn.k_norm.weight"])
    np.testing.assert_array_equal(
        layers["router"][1], raw["model.layers.1.mlp.gate.weight"].T)
    np.testing.assert_array_equal(
        layers["w_gate"][1, 3],
        raw["model.layers.1.mlp.experts.3.gate_proj.weight"].T)
    np.testing.assert_array_equal(
        layers["w_up"][0, 2],
        raw["model.layers.0.mlp.experts.2.up_proj.weight"].T)
    np.testing.assert_array_equal(
        layers["w_down"][1, 0],
        raw["model.layers.1.mlp.experts.0.down_proj.weight"].T)
    # and the loaded tree runs: the served function on loaded weights
    # equals the reference on the same weights
    tokens = jnp.asarray(rng.integers(0, v, 12))
    got = reference.forward(params, tokens, **reference.arch_kwargs(cfg))
    assert np.isfinite(np.asarray(got)).all() and got.shape == (12, v)


def test_which_dispatch_a_configuration_gets():
    """Selected by what the configuration says, never by an option: more
    than eight experts take the dropless dispatch on one device; Mixtral's
    eight keep the capacity form (PERF.md section 6, PR 27), as does any
    mesh."""
    olmoe = dataclasses.replace(TINY, num_experts=64, num_experts_per_tok=8)
    mixtral = ModelConfig(num_experts=8, num_experts_per_tok=2)
    assert olmoe.moe_dropless and not mixtral.moe_dropless
    assert not ModelConfig().moe_dropless
    assert llama._use_dropless(olmoe, None)
    assert not llama._use_dropless(mixtral, None)
    assert not llama._use_dropless(
        dataclasses.replace(olmoe, moe_impl="dense"), None)
    layers = {"w_gate": 1, "w_up": 2, "w_down": 3, "router": 4}
    assert llama.split_expert_stacks(layers, mixtral, None) == (layers, None)
    assert llama.split_expert_stacks(layers, olmoe, None) == (
        {"router": 4}, {"w_gate": 1, "w_up": 2, "w_down": 3})


def test_a_many_expert_model_is_refused_on_a_mesh():
    from dynamo_tpu.parallel.mesh import make_mesh
    cfg = dataclasses.replace(TINY, num_experts=64, num_experts_per_tok=8)
    with pytest.raises(ValueError, match="num_experts=64"):
        NativeEngine(cfg, EngineConfig(**dict(ENGINE_KW, tp=2)),
                     mesh=make_mesh(tp=2), seed=0)


def test_moe_series_reach_the_ledger(monkeypatch):
    """The five llm_engine_moe_*_total series move with a served step,
    from the aux the step already returns."""
    from dynamo_tpu.observability.ledger import LEDGER_STATS, LedgerStats
    names = ("moe_routed_total", "moe_dropped_total",
             "moe_expert_rows_total", "moe_experts_hit_total",
             "moe_layer_calls_total")
    assert set(names) <= set(LedgerStats.FIELDS)
    before = {n: getattr(LEDGER_STATS, n) for n in names}
    eng = NativeEngine(TINY, EngineConfig(**ENGINE_KW), seed=0)
    eng.generate(list(range(3, 40)), SamplingParams(
        max_tokens=6, temperature=0.0, ignore_eos=True), "ledger")
    delta = {n: getattr(LEDGER_STATS, n) - before[n] for n in names}
    assert delta["moe_dropped_total"] == 0
    assert delta["moe_routed_total"] == eng.moe_routed_tokens > 0
    assert delta["moe_expert_rows_total"] >= delta["moe_routed_total"]
    assert delta["moe_layer_calls_total"] >= 2 * 3       # layers x steps
    hit = delta["moe_experts_hit_total"] / delta["moe_layer_calls_total"]
    assert 1 <= hit <= TINY.num_experts


def test_the_benchmarks_copy_of_the_reference_has_not_drifted():
    """benchmark/reference/olmoe.py imports nothing from dynamo_tpu, so it
    is a copy; the two give identical logits on the tiny configuration."""
    import importlib.util
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "reference", "olmoe.py")
    spec = importlib.util.spec_from_file_location("bench_ref_olmoe", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    params = llama.init_params(jax.random.PRNGKey(5), TINY)
    tokens = np.random.default_rng(5).integers(0, TINY.vocab_size, 40)
    ours = reference.forward(params, jnp.asarray(tokens),
                             **reference.arch_kwargs(TINY))
    hf = {"num_attention_heads": 4, "num_key_value_heads": 4,
          "hidden_size": 64, "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
          "num_experts": 16, "num_experts_per_tok": 4,
          "norm_topk_prob": False, "architectures": ["OlmoeForCausalLM"]}
    theirs = mod.forward(params, tokens, hf)
    np.testing.assert_array_equal(np.asarray(ours), np.asarray(theirs))
