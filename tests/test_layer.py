"""One transformer layer: models/llama.layer_front / layer_back / lm_logits.

forward(), decode_forward(), the pipeline stage (models/pp._stage) and
streamed decode (engine/streaming.py) call the same two halves and the same
head; each supplies only its cache update and its attention. Pinned here:
(1) every path goes through every shared piece, so a fifth copy of the layer
cannot come back unnoticed; (2) what the shared halves put into the two hot
programs' layer scans is the reference layer's projections and nothing more:
no collective (the pp stage's `reduce` must not leak into the single-mesh
paths) and no doubled projection.
"""
import collections
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.analysis.jaxpr_audit import iter_eqns
from dynamo_tpu.engine import streaming
from dynamo_tpu.engine.config import ModelConfig
from dynamo_tpu.models import llama, reference
from dynamo_tpu.models.llama import AttnMetadata
from dynamo_tpu.models.loader import config_from_hf
from dynamo_tpu.models.pp import pp_forward
from dynamo_tpu.ops import attention
from dynamo_tpu.parallel.mesh import make_mesh

CFG = ModelConfig(dtype="float32", num_layers=2, max_model_len=64)
PAGE, NPAGES, B, TQ = 8, 16, 2, 8


def _prefill_inputs():
    rng = np.random.RandomState(0)
    tokens = rng.randint(1, CFG.vocab_size, (B, TQ)).astype(np.int32)
    positions = np.tile(np.arange(TQ, dtype=np.int32), (B, 1))
    page_table = np.arange(B * 4, dtype=np.int32).reshape(B, 4)
    write_idx = page_table[:, :1] * PAGE + positions
    return (jnp.asarray(tokens),
            AttnMetadata(jnp.asarray(positions), jnp.asarray(page_table),
                         jnp.full((B,), TQ, jnp.int32),
                         jnp.asarray(write_idx)))


def _run_forward(params):
    tokens, meta = _prefill_inputs()
    return llama.forward(params, CFG, tokens,
                         llama.init_cache(CFG, NPAGES, PAGE), meta)[0]


def _run_decode_forward(params):
    tokens, meta = _prefill_inputs()
    return llama.decode_forward(
        params, CFG, tokens[:, 0], llama.init_cache(CFG, NPAGES, PAGE),
        meta.page_table, jnp.full((B,), 3, jnp.int32),
        jnp.full((B,), 3, jnp.int32))[0]


def _run_pp_forward(params):
    tokens, meta = _prefill_inputs()
    mesh = make_mesh(pp=2, tp=1, devices=jax.devices()[:2])
    return pp_forward(params, CFG, tokens,
                      llama.init_cache(CFG, NPAGES, PAGE), meta, mesh)[0]


def _run_streamed(params):
    """The streamed layer's device functions, as StreamingDecoder chains
    them for one chunk with nothing resident: embed, (start, finish) a
    layer, final."""
    tokens, _ = _prefill_inputs()
    cache = llama.init_cache(CFG, NPAGES, PAGE)
    x = streaming._stream_embed(CFG, params, tokens[0])
    for lid in range(CFG.num_layers):
        _, _, _, acc, _, l = streaming._stream_layer_start(
            CFG, False, params, jnp.int32(lid), x,
            jnp.arange(TQ, dtype=jnp.int32), cache["k"], cache["v"], None,
            None, jnp.zeros((1,), jnp.int32), jnp.zeros((1,), jnp.int32))
        x = streaming._stream_layer_finish(CFG, params, jnp.int32(lid), x,
                                           acc, l)
    return streaming._stream_final(CFG, params, x[-1])


def _run_forward_step(params, real_chunk):
    """forward() as the engine's step calls it (`last_idx`), at [16, 16]:
    a grid of 256 cells over 128 flat rows. Every row holds `real_chunk`
    real tokens: 4 a row (64) run the compact branch of each layer half,
    16 a row (256) the grid branch."""
    b, tq, pages = 16, 16, 4
    tokens = np.random.RandomState(0).randint(
        1, CFG.vocab_size, (b, tq)).astype(np.int32)
    positions = np.minimum(np.arange(tq, dtype=np.int32), real_chunk - 1)
    page_table = np.arange(b * pages, dtype=np.int32).reshape(b, pages)
    write_idx = np.where(np.arange(tq) < real_chunk,
                         page_table[:, :1] * PAGE + positions, -1)
    meta = AttnMetadata(
        jnp.asarray(np.tile(positions, (b, 1))), jnp.asarray(page_table),
        jnp.full((b,), real_chunk, jnp.int32),
        jnp.asarray(write_idx.astype(np.int32)))
    assert bool(llama.step_compaction(write_idx)[1]) == (real_chunk == 4)
    return llama.forward(
        params, CFG, jnp.asarray(tokens),
        llama.init_cache(CFG, b * pages, PAGE), meta,
        last_idx=jnp.full((b,), real_chunk - 1, jnp.int32))[0]


PATHS = {"forward": _run_forward, "decode_forward": _run_decode_forward,
         "pp_forward": _run_pp_forward, "streamed": _run_streamed,
         "forward_step_compact": lambda p: _run_forward_step(p, 4),
         "forward_step_grid": lambda p: _run_forward_step(p, 16)}


def _other_model(name):
    """`llama.<name>`, computing another model: v doubled, the residual
    stream shifted, the logits shifted."""
    real = getattr(llama, name)

    def front(*a, **kw):
        q, k, v = real(*a, **kw)
        return q, k, v * 2

    def back(*a, **kw):
        x, stats = real(*a, **kw)
        return x + 1, stats

    def logits(*a, **kw):
        return real(*a, **kw) + 1

    return {"layer_front": front, "layer_back": back,
            "lm_logits": logits}[name]


@pytest.mark.parametrize("piece", ["layer_front", "layer_back", "lm_logits"])
@pytest.mark.parametrize("path", sorted(PATHS))
def test_every_path_runs_the_one_layer(monkeypatch, path, piece):
    """Swap one shared piece for another model's: every path's logits move.
    A path that kept (or regrew) its own copy of the layer would not see
    the swap. Fails on any tree without the three functions."""
    params = llama.init_params(jax.random.PRNGKey(0), CFG)
    before = np.asarray(PATHS[path](params))
    monkeypatch.setattr(llama, piece, _other_model(piece))
    after = np.asarray(PATHS[path](params))
    assert before.shape == after.shape and np.isfinite(after).all()
    assert np.abs(after - before).max() > 1e-3, (path, piece)


# -- what the shared halves put into the hot programs --------------------------

_DOTS = ("dot_general", "ragged_dot_general")
_COLLECTIVES = ("psum", "psum2", "all_gather", "all_to_all", "ppermute",
                "pmax", "pmin", "reduce_scatter", "psum_invariant")


def _prims(jaxpr) -> collections.Counter:
    return collections.Counter(e.primitive.name for e in iter_eqns(jaxpr))


def _dots(fn, *args) -> int:
    prims = _prims(jax.make_jaxpr(fn)(*args).jaxpr)
    return sum(prims[p] for p in _DOTS)


def _layer_scan(closed, num_layers) -> collections.Counter:
    """Primitive counts of THE scan over layers in a traced forward."""
    scans = [e for e in iter_eqns(closed.jaxpr)
             if e.primitive.name == "scan"
             and e.params["length"] == num_layers]
    assert len(scans) == 1, len(scans)
    return _prims(scans[0].params["jaxpr"].jaxpr)


def _i32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


@pytest.mark.parametrize("name", ["rehearsal-tiny", "rehearsal-tiny-moe",
                                  "rehearsal-tiny-olmoe"])
def test_layer_scans_hold_the_reference_layers_projections(name):
    """The benchmark's tiny Mistral / Mixtral / OLMoE: the layer scan of
    forward() and of decode_forward() holds no collective, and as many
    matmuls as the plain reference's layer spells out for the projections
    (wq, wk, wv, wo) plus what the path's own attention op and the
    configuration's MLP hold when traced alone."""
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "..", "benchmark", "configs", name,
                           "config.json")) as f:
        cfg = config_from_hf(json.load(f), name=name)
    nl, h, hkv, hd, d = (cfg.num_layers, cfg.num_heads, cfg.num_kv_heads,
                         cfg.head_dim, cfg.hidden_size)
    b, tq, pb = 4, 8, 4
    dt = jnp.dtype(cfg.dtype)
    params = jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), cfg))
    cache = jax.eval_shape(lambda: llama.init_cache(cfg, NPAGES, PAGE))
    lp = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype),
                      params["layers"])

    def arr(*shape):
        return jax.ShapeDtypeStruct(shape, dt)

    # the reference layer's attention is wq, wk, wv, scores, values, wo
    arch = reference.arch_kwargs(cfg)
    ref_lp = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.float32), lp)
    attn_arch = {k: arch[k] for k in (
        "num_heads", "num_kv_heads", "head_dim", "rope_theta",
        "rms_norm_eps", "qk_norm")}
    projections = _dots(
        lambda x, p: reference.attention(x, p, **attn_arch),
        jax.ShapeDtypeStruct((tq, d), jnp.float32), ref_lp) - 2
    assert projections == 4
    mlp = _dots(lambda x, p: llama._mlp_block(x, p, cfg, None, None)[0],
                arr(b, tq, d), lp)
    if not cfg.is_moe:
        # dense: the layer IS the reference's, matmul for matmul
        assert mlp == _dots(reference.dense_mlp,
                            jax.ShapeDtypeStruct((tq, d), jnp.float32),
                            ref_lp)

    paged = _dots(
        lambda q, kc, vc, pt, kl, pos: attention.paged_attention(
            q, kc, vc, pt, kl, pos, layer=jnp.int32(0)),
        arr(b, tq, h, hd), cache["k"], cache["v"], _i32(b, pb), _i32(b),
        _i32(b, tq))
    deferred = _dots(
        lambda q, kc, vc, kn, vn, pt, pl: attention.decode_attention_deferred(
            q, kc, vc, kn, vn, pt, pl, layer=jnp.int32(0)),
        arr(b, h, hd), cache["k"], cache["v"], arr(b, hkv, hd),
        arr(b, hkv, hd), _i32(b, pb), _i32(b))

    fwd = _layer_scan(jax.make_jaxpr(
        lambda p, c, t, pos, pt, kl, wi: llama.forward(
            p, cfg, t, c, AttnMetadata(pos, pt, kl, wi), with_aux=True))(
        params, cache, _i32(b, tq), _i32(b, tq), _i32(b, pb), _i32(b),
        _i32(b, tq)), nl)
    dec = _layer_scan(jax.make_jaxpr(
        lambda p, c, t, pt, pl, pos: llama.decode_forward(
            p, cfg, t, c, pt, pl, pos, with_aux=True))(
        params, cache, _i32(b), _i32(b, pb), _i32(b), _i32(b)), nl)
    for prims, attn in ((fwd, paged), (dec, deferred)):
        assert not [p for p in _COLLECTIVES if prims[p]], prims
        assert sum(prims[p] for p in _DOTS) == projections + attn + mlp, (
            prims, projections, attn, mlp)
    # the engine's step (`last_idx`) at a grid larger than its flat rows:
    # the layer's token-wise halves twice, once a branch of their `cond`s.
    # Attention ONCE, between them and outside both, where the row form
    # does not pay at this shape; where it does, inside the back half's:
    # the grid form in one branch, the row form in the other (one query a
    # row, and a chunk row's own inside its loop: the same two matmuls
    # each)
    step = _layer_scan(jax.make_jaxpr(
        lambda p, c, t, pos, pt, kl, wi, last: llama.forward(
            p, cfg, t, c, AttnMetadata(pos, pt, kl, wi), with_aux=True,
            last_idx=last))(
        params, cache, _i32(16, 16), _i32(16, 16), _i32(16, pb), _i32(16),
        _i32(16, 16), _i32(16)), nl)
    assert step["cond"] == 2 and not [p for p in _COLLECTIVES if step[p]]
    forms = 3 if llama.step_attention_rows(cfg, 16) else 1
    assert sum(step[p] for p in _DOTS) \
        == 2 * (projections + mlp) + forms * paged, (
        step, projections, paged, mlp)
