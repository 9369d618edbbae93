"""Llama-family decoder (also hosts the Mixtral-style MoE MLP variant).

Functional JAX, TPU-first:
- parameters are a pytree of arrays **stacked over layers** and the layer loop
  is a `lax.scan`, so XLA compiles one layer body regardless of depth;
- all matmuls are bf16 on the MXU; softmax/normalization accumulate in f32;
- tensor parallelism is expressed as PartitionSpecs over a named mesh axis
  "tp" (see param_shardings) — XLA inserts the all-reduces over ICI;
- the KV cache is paged ([layers, pages, page_size, kv_heads, head_dim]) and
  attention runs against it in both prefill and decode (ops/attention.py).

Covers the architecture of DeepSeek-R1-Distill-Llama-8B / Llama-3-70B (the
reference's canonical + scale-out configs, reference:
examples/llm/configs/disagg_router.yaml, BASELINE.md) and Mixtral-8x7B when
cfg.num_experts > 0.
"""
# dynalint: hot-path — every op here runs inside jitted decode/prefill programs;
# host syncs (.item(), device_get, float()) are dynalint R6 findings
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from dynamo_tpu.engine.config import ModelConfig
from dynamo_tpu.ops.attention import (
    _softcap, decode_attention_deferred, decode_attention_split,
    kv_write_plan, paged_attention, stored_kv_rows, write_kv_rows,
)
from dynamo_tpu.ops.kv_quant import cache_keys
from dynamo_tpu.ops.kv_quant import validate_mode as _validate_kv_quant
from dynamo_tpu.ops.moe import (
    moe_dispatch_mlp, moe_dispatch_mlp_sharded, moe_dropless_mlp, route_topk,
)
from dynamo_tpu.ops.quant import is_quantized, wmat
from dynamo_tpu.ops.paged_attention import (
    combine_self_attention, decode_paged_attention,
    decode_paged_attention_prefix, decode_paged_attention_prefix_sharded,
    decode_paged_attention_sharded,
)

Params = Dict[str, Any]


def _decode_kernel_mode(cfg: ModelConfig) -> Optional[str]:
    """Resolve the decode-attention implementation at trace time.

    Returns "tpu" / "interpret" to use the ragged Pallas kernel (the ONE
    decode-attention kernel, ops/paged_attention.py — per-row page-walk
    lengths cover plain, packed, and prefix-window rows in a single
    program), None for the XLA gather path. On multi-device meshes the
    kernel runs under shard_map over "tp" (auto-sharded jit cannot
    partition a pallas_call).

    "auto" IS the gather path, on every platform: the one on-chip
    comparison (llama3-1b, batch 8, kv~300-600, the kernels the ragged
    one replaced) had the deferred-write gather decode at 7.5 ms/step
    against 34 ms for Pallas — per-(seq, head, page) dots of
    [G<=8, 128] x [rows, 128] are fixed-overhead bound on the MXU, while
    the gather path's single big einsum amortizes. The ragged kernel
    walks the same pages with the same dot shapes and has not been timed
    on a chip (chip_smoke.py's kernel phase checks that it compiles and
    agrees with the gather path, not how fast it is). "on" requests the
    compiled kernel and raises where it cannot serve; "interpret" is the
    CPU test path exercising the kernel code."""
    mode = cfg.decode_kernel
    if mode in ("off", "auto"):
        return None
    if cfg.attn_softcap or cfg.sliding_window or cfg.query_scale:
        # Gemma-2 logit soft-caps / sliding windows live only in the
        # gather paths; the Pallas kernel has no hook for them
        if mode == "on":
            raise ValueError(
                "decode_kernel='on' requested but the model uses "
                "soft-caps/sliding windows/query scaling the Pallas kernel "
                "has no hooks for; use decode_kernel='auto'")
        import logging
        logging.getLogger(__name__).warning(
            "decode_kernel=%r: the model's soft-caps/sliding windows/query "
            "scaling have no hooks in the Pallas kernel; using the XLA "
            "gather path", mode)
        return None
    if mode == "interpret":
        return "interpret"
    return "tpu"


@dataclasses.dataclass
class AttnMetadata:
    """Everything the paged forward pass needs besides tokens.

    All arrays are bucketed to static shapes by the scheduler.
    """

    positions: jax.Array    # [B, Tq] int32 absolute positions
    page_table: jax.Array   # [B, Pb] int32
    kv_lens: jax.Array      # [B] int32 (valid kv length AFTER this step)
    write_idx: jax.Array    # [B, Tq] int32 flat slot indices (<0 = padding)


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# -- init ---------------------------------------------------------------------

def init_params(rng: jax.Array, cfg: ModelConfig) -> Params:
    """Random-init parameters (stacked over layers)."""
    dt = _dtype(cfg)
    d, hd = cfg.hidden_size, cfg.head_dim
    h, hkv, f, l = cfg.num_heads, cfg.num_kv_heads, cfg.intermediate_size, cfg.num_layers
    keys = jax.random.split(rng, 12)

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32) * fan_in ** -0.5).astype(dt)

    layers = {
        "attn_norm": jnp.ones((l, d), dt),
        "wq": dense(keys[0], (l, d, h * hd), d),
        "wk": dense(keys[1], (l, d, hkv * hd), d),
        "wv": dense(keys[2], (l, d, hkv * hd), d),
        "wo": dense(keys[3], (l, h * hd, d), h * hd),
        "mlp_norm": jnp.ones((l, d), dt),
    }
    if cfg.post_norms:
        layers.update({
            "post_attn_norm": jnp.ones((l, d), dt),
            "post_mlp_norm": jnp.ones((l, d), dt),
        })
    if cfg.attn_bias:
        layers.update({
            "wq_b": jnp.zeros((l, h * hd), dt),
            "wk_b": jnp.zeros((l, hkv * hd), dt),
            "wv_b": jnp.zeros((l, hkv * hd), dt),
        })
    if cfg.qk_norm:
        # seeded, not ones: a norm weight of one would hide a check that
        # skipped it or applied it per head with the first head's slice
        layers.update({
            "q_norm": (1.0 + 0.1 * jax.random.normal(
                keys[10], (l, h * hd), jnp.float32)).astype(dt),
            "k_norm": (1.0 + 0.1 * jax.random.normal(
                keys[11], (l, hkv * hd), jnp.float32)).astype(dt),
        })
    if cfg.is_moe:
        e = cfg.num_experts
        layers.update({
            "router": dense(keys[4], (l, d, e), d),
            "w_gate": dense(keys[5], (l, e, d, f), d),
            "w_up": dense(keys[6], (l, e, d, f), d),
            "w_down": dense(keys[7], (l, e, f, d), f),
        })
    else:
        layers.update({
            "w_gate": dense(keys[5], (l, d, f), d),
            "w_up": dense(keys[6], (l, d, f), d),
            "w_down": dense(keys[7], (l, f, d), f),
        })
    params: Params = {
        "embed": dense(keys[8], (cfg.vocab_size, d), d),
        "layers": layers,
        "final_norm": jnp.ones((d,), dt),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = dense(keys[9], (d, cfg.vocab_size), d)
    if cfg.vision is not None:
        from dynamo_tpu.models import vision
        params["vision"] = vision.init_params(keys[10], cfg)
    return params


def param_shardings(cfg: ModelConfig) -> Params:
    """PartitionSpecs matching init_params' tree; mesh axes ("dp", "tp").

    Megatron-style TP (reference delegates TP to engines via
    --tensor-parallel-size, reference: launch/dynamo-run/src/lib.rs +
    engines/sglang/worker.rs:285-320; here it is first-class): attention heads
    and MLP hidden dim shard over "tp"; XLA inserts the psum after wo/w_down.
    MoE experts shard over "tp" as well (expert-parallel uses the same axis
    until the dedicated "ep" mesh is used — see models/moe notes).
    """
    layers = {
        "attn_norm": P(None, None),
        "wq": P(None, None, "tp"),
        "wk": P(None, None, "tp"),
        "wv": P(None, None, "tp"),
        "wo": P(None, "tp", None),
        "mlp_norm": P(None, None),
    }
    if cfg.post_norms:
        layers.update({
            "post_attn_norm": P(None, None),
            "post_mlp_norm": P(None, None),
        })
    if cfg.attn_bias:
        layers.update({
            "wq_b": P(None, "tp"),
            "wk_b": P(None, "tp"),
            "wv_b": P(None, "tp"),
        })
    if cfg.qk_norm:
        layers.update({"q_norm": P(None, "tp"), "k_norm": P(None, "tp")})
    if cfg.is_moe:
        # experts shard over "ep", each expert's FFN dim over "tp"; on
        # meshes without those axes (size 1) the specs are no-ops
        layers.update({
            "router": P(None, None, None),
            "w_gate": P(None, "ep", None, "tp"),
            "w_up": P(None, "ep", None, "tp"),
            "w_down": P(None, "ep", "tp", None),
        })
    else:
        layers.update({
            "w_gate": P(None, None, "tp"),
            "w_up": P(None, None, "tp"),
            "w_down": P(None, "tp", None),
        })
    out: Params = {
        "embed": P(None, None),
        "layers": layers,
        "final_norm": P(None),
    }
    if not cfg.tie_word_embeddings:
        out["lm_head"] = P(None, "tp")
    if cfg.vision is not None:
        from dynamo_tpu.models import vision
        out["vision"] = vision.param_shardings(cfg)
    return out


def cache_sharding(cfg: ModelConfig) -> P:
    """KV cache [L, Hkv, P, ps, hd]: shard kv heads over tp.

    Head-major so one (head, page) slice is a contiguous [ps, hd] block —
    the decode kernel's DMA unit (ops/paged_attention.py)."""
    del cfg
    return P(None, "tp", None, None, None)


def cache_scale_sharding(cfg: ModelConfig) -> P:
    """KV scale arrays [L, Hkv, P, ps]: kv heads over tp, like the values."""
    del cfg
    return P(None, "tp", None, None)


def cache_shardings(cfg: ModelConfig) -> Dict[str, P]:
    """Per-leaf PartitionSpecs matching init_cache's dict layout."""
    out = {"k": cache_sharding(cfg), "v": cache_sharding(cfg)}
    if _validate_kv_quant(cfg.kv_quant):
        out["k_scale"] = cache_scale_sharding(cfg)
        out["v_scale"] = cache_scale_sharding(cfg)
    return out


def init_cache(cfg: ModelConfig, num_pages: int, page_size: int) -> Dict[str, jax.Array]:
    dt = _dtype(cfg)
    shape = (cfg.num_layers, cfg.num_kv_heads, num_pages, page_size, cfg.head_dim)
    if _validate_kv_quant(cfg.kv_quant):
        # int8 pages + per-row f32 scales (ops/kv_quant.py): the scale
        # array shares the page axis (2) with the values, so every
        # page-indexed move (extract/inject/offload/transfer) carries
        # the scales with the same ids
        return {"k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "k_scale": jnp.zeros(shape[:-1], jnp.float32),
                "v_scale": jnp.zeros(shape[:-1], jnp.float32)}
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


# -- forward ------------------------------------------------------------------

def rms_norm(x: jax.Array, w: jax.Array, eps: float,
             plus_one: bool = False) -> jax.Array:
    xf = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    if plus_one:
        # Gemma stores the norm weight as a delta from 1 and applies it in
        # f32 before the downcast (HF GemmaRMSNorm)
        return (xf * scale * (1.0 + w.astype(jnp.float32))).astype(x.dtype)
    return (xf * scale).astype(x.dtype) * w


def mlp_activation(gate: jax.Array, cfg: ModelConfig) -> jax.Array:
    """GLU gate activation in f32: SiLU (llama) or tanh-GELU (Gemma)."""
    gf = gate.astype(jnp.float32)
    a = (jax.nn.gelu(gf, approximate=True) if cfg.mlp_act == "gelu_tanh"
         else jax.nn.silu(gf))
    return a.astype(gate.dtype)


def scale_embeds(x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Gemma multiplies embedding outputs by sqrt(hidden) (in x.dtype)."""
    if cfg.embed_scale:
        return x * jnp.asarray(cfg.embed_scale, x.dtype)
    return x


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [B, T, H, hd]; positions: [B, T]."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, jnp.float32) / hd))  # [hd/2]
    angles = positions[..., None].astype(jnp.float32) * freqs          # [B, T, hd/2]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


@jax.named_scope("moe")
def _moe_mlp(x: jax.Array, lp: Params, cfg: ModelConfig) -> jax.Array:
    """Dense-compute MoE (top-k routing, all experts evaluated then masked).

    TPU-friendly for moderate expert counts: one big batched einsum over the
    expert axis keeps the MXU busy and avoids dynamic shapes. A ragged
    all-to-all EP dispatch over a dedicated "ep" axis is the scale-out path
    (parallel/expert.py).
    """
    b, t, d = x.shape
    e, k = cfg.num_experts, cfg.num_experts_per_tok
    weights, idx = route_topk(x, lp["router"], k,
                              cfg.norm_topk_prob)              # [B, T, k]
    one_hot = jax.nn.one_hot(idx, e, dtype=jnp.float32)        # [B, T, k, E]
    combine = jnp.einsum("btk,btke->bte", weights, one_hot)    # [B, T, E]

    gate = jnp.einsum("btd,edf->betf", x, wmat(lp["w_gate"], x.dtype))
    up = jnp.einsum("btd,edf->betf", x, wmat(lp["w_up"], x.dtype))
    act = mlp_activation(gate, cfg) * up
    down = jnp.einsum("betf,efd->betd", act,
                      wmat(lp["w_down"], x.dtype))             # [B, E, T, D]
    return jnp.einsum("betd,bte->btd", down.astype(jnp.float32), combine).astype(x.dtype)


@jax.named_scope("mlp")
def _dense_mlp(x: jax.Array, lp: Params, cfg: ModelConfig) -> jax.Array:
    gate = jnp.einsum("btd,df->btf", x, wmat(lp["w_gate"], x.dtype))
    up = jnp.einsum("btd,df->btf", x, wmat(lp["w_up"], x.dtype))
    act = mlp_activation(gate, cfg) * up
    return jnp.einsum("btf,fd->btd", act, wmat(lp["w_down"], x.dtype))


def qkv_proj(xn: jax.Array, lp: Params, cfg: ModelConfig):
    """x -> (q [B, T, H*hd], k, v [B, T, Hkv*hd]), before the split into
    heads and RoPE. With `cfg.qk_norm` (OLMoE) q and k each pass an
    RMSNorm over the WHOLE projection, all heads together, with its own
    weight vector; what reaches the cache is the normed, rotated k."""
    q = jnp.einsum("btd,de->bte", xn, wmat(lp["wq"], xn.dtype))
    k = jnp.einsum("btd,de->bte", xn, wmat(lp["wk"], xn.dtype))
    v = jnp.einsum("btd,de->bte", xn, wmat(lp["wv"], xn.dtype))
    if cfg.attn_bias:
        q, k, v = q + lp["wq_b"], k + lp["wk_b"], v + lp["wv_b"]
    if cfg.qk_norm:
        with jax.named_scope("attention.qk_norm"):
            q = rms_norm(q, lp["q_norm"], cfg.rms_norm_eps)
            k = rms_norm(k, lp["k_norm"], cfg.rms_norm_eps)
    return q, k, v


EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


def _use_dropless(cfg: ModelConfig, mesh) -> bool:
    return (cfg.is_moe and cfg.moe_impl == "dispatch" and cfg.moe_dropless
            and (mesh is None or mesh.size == 1))


def split_expert_stacks(layers: Params, cfg: ModelConfig, mesh):
    """(the leaves the layer scan slices, the expert stacks it does not).
    On the dropless path the stacked [L, E, ...] expert leaves stay out of
    the scan's xs and are handed to the layer whole with its index: the
    grouped-matmul kernel reads a layer's experts where they lie, where a
    slice of the stack would be copied before every call. Quantized
    leaves are dequantized a layer at a time and stay in the scan."""
    if not _use_dropless(cfg, mesh) or is_quantized(layers["w_gate"]):
        return layers, None
    return ({k: v for k, v in layers.items() if k not in EXPERT_LEAVES},
            {k: layers[k] for k in EXPERT_LEAVES})


def _mlp_block(xn: jax.Array, lp: Params, cfg: ModelConfig, mesh,
               token_valid, stacks=None, lid=None):
    """The layer's MLP: (out, stats). stats is None except on the MoE
    dispatch paths, where it is ops/moe.py's `moe_stats` dict. `stacks`
    and `lid`: split_expert_stacks' second half and this layer's index."""
    if not cfg.is_moe:
        return _dense_mlp(xn, lp, cfg), None
    if cfg.moe_impl == "dense":
        return _moe_mlp(xn, lp, cfg), None
    if mesh is not None and mesh.shape.get("ep", 1) > 1:
        # explicit O(E/ep) per-shard dispatch (ops/moe.py sharded path)
        return moe_dispatch_mlp_sharded(
            xn, lp, cfg, mesh, return_dropped=True, valid=token_valid)
    if _use_dropless(cfg, mesh):
        if stacks is not None:
            return moe_dropless_mlp(xn, {**lp, **stacks}, cfg,
                                    valid=token_valid, layer=lid)
        return moe_dropless_mlp(xn, lp, cfg, valid=token_valid)
    return moe_dispatch_mlp(xn, lp, cfg, return_dropped=True,
                            valid=token_valid)


def _sum_stats(stats) -> dict:
    """Per-layer (and per-step) stacks of MoE stats -> one scalar each."""
    return {k: jnp.sum(v) for k, v in (stats or {}).items()}


# -- the layer ----------------------------------------------------------------
# One transformer layer, cut where streamed decode runs host code between
# the halves. Between them each caller does what is really its own: the
# cache update and the attention. Callers: forward(), decode_forward(),
# models/pp._stage, engine/streaming._stream_layer_start / _finish.

def layer_front(x: jax.Array, lp: Params, cfg: ModelConfig,
                positions: jax.Array, heads: tuple):
    """x [B, T, D] -> q [B, T, H, hd], k, v [B, T, Hkv, hd]: attention
    norm, QKV projection (bias, QK-norm), split into heads, RoPE on q and
    k. `heads` = (H, Hkv) as the caller holds them: a "tp" shard of a
    manual mesh passes its local counts."""
    b, t = x.shape[:2]
    h, hkv = heads
    xn = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps, cfg.norm_plus_one)
    q, k, v = qkv_proj(xn, lp, cfg)
    q = apply_rope(q.reshape(b, t, h, cfg.head_dim), positions,
                   cfg.rope_theta)
    k = apply_rope(k.reshape(b, t, hkv, cfg.head_dim), positions,
                   cfg.rope_theta)
    return q, k, v.reshape(b, t, hkv, cfg.head_dim)


def layer_back(x: jax.Array, attn: jax.Array, lp: Params, cfg: ModelConfig,
               mlp, reduce=None):
    """(x [B, T, D], attn [B, T, ...heads]) -> (next x, the MLP's stats):
    output projection, residual, MLP norm, `mlp(xn, lp)` -> (out, stats),
    residual, with Gemma's post-norms where the configuration has them.
    `reduce` sums a partial product over the caller's manual "tp" axis; it
    comes BEFORE the post-norm, which is nonlinear and must see the whole
    output, not a shard's partial sum."""
    b, t = x.shape[:2]
    out = jnp.einsum("bte,ed->btd", attn.reshape(b, t, -1),
                     wmat(lp["wo"], x.dtype))
    if reduce is not None:
        out = reduce(out)
    if cfg.post_norms:
        out = rms_norm(out, lp["post_attn_norm"], cfg.rms_norm_eps,
                       cfg.norm_plus_one)
    x = x + out
    xn = rms_norm(x, lp["mlp_norm"], cfg.rms_norm_eps, cfg.norm_plus_one)
    out, stats = mlp(xn, lp)
    if reduce is not None:
        out = reduce(out)
    if cfg.post_norms:
        out = rms_norm(out, lp["post_mlp_norm"], cfg.rms_norm_eps,
                       cfg.norm_plus_one)
    return x + out, stats


def lm_head(params: Params, cfg: ModelConfig) -> jax.Array:
    """The [D, V] head operand: the embedding transposed when tied (may be
    a quantized leaf)."""
    return (params["embed"].T if cfg.tie_word_embeddings
            else params["lm_head"])


def lm_logits(x: jax.Array, final_norm: jax.Array, head: jax.Array,
              cfg: ModelConfig) -> jax.Array:
    """x [..., D] -> float32 logits [..., V]: final norm, head matmul,
    final soft-cap. Takes the two arrays, not `params`: a pp stage holds
    stage-local ones."""
    x = rms_norm(x, final_norm, cfg.rms_norm_eps, cfg.norm_plus_one)
    logits = jnp.einsum("...d,dv->...v", x, wmat(head, x.dtype))
    return _softcap(logits.astype(jnp.float32), cfg.final_softcap)


def decode_forward(
    params: Params,
    cfg: ModelConfig,
    tokens: jax.Array,        # [B] int32 — one token per sequence
    cache: Dict[str, jax.Array],
    page_table: jax.Array,    # [B, Pb]
    prefix_lens: jax.Array,   # [B] — valid kv BEFORE this token (0 = pad)
    positions: jax.Array,     # [B] — absolute position of this token
    valid: Optional[jax.Array] = None,  # [B] bool, real (non-pad) slots
    mesh=None,
    with_aux: bool = False,
    window: Optional[tuple] = None,  # split-KV window fast path, see below
) -> tuple:
    """Deferred-write decode step: the KV cache is READ-ONLY.

    Returns (last_logits [B, V] f32, k_new [L, B, Hkv, hd],
    v_new [L, B, Hkv, hd], aux) — the caller scatters the new kv rows into
    the cache in ONE in-place update per step (engine._scatter_new_kv).
    The pool is read in place: a layer gathers the pages its rows name
    from the stacked leaves by (layer, page), or the kernel streams them;
    no [Hkv, P, ps, hd] slice of a layer's pool is ever formed. Rationale:
    threading cache slices through the layer scan's outputs made XLA copy
    the whole cache every step (~8 ms for the 1B flagship — the round-2
    decode gap; the same defect cost forward() a fifth of the device's
    time until PR 26, PERF.md section 6); attention instead adds the
    current token via an explicit self-term (ops/attention.
    decode_attention_deferred, ops/paged_attention.
    combine_self_attention), which is exact because decode is causal.

    `window`: window-decode fast path — (k_base, v_base [L, Hkv, B, Lb,
    hd], k_win, v_win [L, Hkv, B, Nw, hd], base_lens [B], win_lens [B]).
    The caller gathered each slot's VALID prefix pages once per decode
    window (base, read-only; Lb is bucketed to the true kv length, not
    the admission-time allocation) and accumulates each step's new kv
    rows into the small window buffer AFTER this call returns; attention
    merges base + window + current-token self-term in one joint softmax
    (ops/attention.decode_attention_split). Kills both the per-step page
    gather (~2.5 ms/step, 1B @ b8) and the full-allocation-width reads
    of the round-3 single-buffer design.
    """
    heads = (cfg.num_heads, cfg.num_kv_heads)
    kernel_mode = _decode_kernel_mode(cfg)
    kvq = bool(_validate_kv_quant(cfg.kv_quant))
    lw = cfg.layer_windows()
    layer_wnd = None if lw is None else jnp.asarray(lw, jnp.int32)
    # ids validated at admission (_validate_prompt); decode feeds only
    # committed sampler outputs  # dynalint: disable-next-line=R1
    x = scale_embeds(jnp.take(params["embed"], tokens, axis=0),
                     cfg)[:, None]  # [B, 1, D]
    layer_ids = jnp.arange(cfg.num_layers, dtype=jnp.int32)
    moe_aux = cfg.is_moe and cfg.moe_impl == "dispatch"
    token_valid = valid[:, None] if (moe_aux and valid is not None) else None

    def layer_step(x, xs):
        if layer_wnd is not None:
            xs, wnd = xs[:-1], xs[-1]
        else:
            wnd = None
        if window is not None:
            lp, lid, kb, vb, kw, vw = xs
        else:
            lp, lid = xs
        q, k, v = layer_front(x, lp, cfg, positions[:, None], heads)
        k_new, v_new = k[:, 0], v[:, 0]                  # [B, Hkv, hd]
        if window is not None:
            attn = decode_attention_split(
                q[:, 0], kb, vb, kw, vw, k_new, v_new, base_lens, win_lens,
                softcap=cfg.attn_softcap, window=wnd,
                q_scale=cfg.query_scale)
        elif kernel_mode is not None:
            interp = kernel_mode == "interpret"
            # int8 caches hand the kernels the raw pages plus the scale
            # stacks; dequantization folds into the in-kernel score/prob
            # rows (ops/paged_attention.py)  # dynalint: kv-codec
            scales = ((cache["k_scale"], cache["v_scale"]) if kvq
                      else (None, None))
            if mesh is not None and mesh.size > 1:
                acc, m, l = decode_paged_attention_prefix_sharded(
                    # dynalint: kv-codec — kernels dequantize in-read
                    q[:, 0], cache["k"], cache["v"], lid[None], page_table,
                    prefix_lens, mesh, interpret=interp,
                    k_scale=scales[0], v_scale=scales[1])
            else:
                acc, m, l = decode_paged_attention_prefix(
                    # dynalint: kv-codec — kernels dequantize in-read
                    q[:, 0], cache["k"], cache["v"], lid[None], page_table,
                    prefix_lens, interpret=interp,
                    k_scale=scales[0], v_scale=scales[1])
            attn = combine_self_attention(q[:, 0], k_new, v_new, acc, m, l)
        else:
            # gather fallback: the stacked leaves go in whole and only
            # this layer's pages come out (ops/attention.gather_values,
            # which dequantizes an int8 pool right after the gather)
            # dynalint: kv-codec — consumer gathers by (layer, page)
            scales = ((cache["k_scale"], cache["v_scale"]) if kvq
                      else (None, None))
            attn = decode_attention_deferred(
                # dynalint: kv-codec — consumer dequantizes at gather
                q[:, 0], cache["k"], cache["v"], k_new, v_new,
                page_table, prefix_lens, softcap=cfg.attn_softcap,
                window=wnd, q_scale=cfg.query_scale,
                k_scale=scales[0], v_scale=scales[1], layer=lid)
        x, drop_stats = layer_back(
            x, attn, lp, cfg, lambda xn, lp: _mlp_block(
                xn, lp, cfg, mesh, token_valid, expert_stacks, lid))
        ys = (k_new, v_new, drop_stats) if moe_aux else (k_new, v_new)
        return x, ys

    scan_layers, expert_stacks = split_expert_stacks(params["layers"], cfg,
                                                     mesh)
    if window is not None:
        kb_all, vb_all, kw_all, vw_all, base_lens, win_lens = window
        xs = (scan_layers, layer_ids, kb_all, vb_all, kw_all, vw_all)
    else:
        xs = (scan_layers, layer_ids)
    if layer_wnd is not None:
        xs = xs + (layer_wnd,)
    x, ys = jax.lax.scan(layer_step, x, xs)
    if moe_aux:
        k_news, v_news, drops = ys
    else:
        (k_news, v_news), drops = ys, None
    aux = _sum_stats(drops)
    logits = lm_logits(x[:, 0], params["final_norm"], lm_head(params, cfg),
                       cfg)
    if with_aux:
        return logits, k_news, v_news, aux
    return logits, k_news, v_news


def forward(
    params: Params,
    cfg: ModelConfig,
    tokens: jax.Array,            # [B, Tq] int32
    cache: Dict[str, jax.Array],  # {"k","v"}: [L, Hkv, P, ps, hd]
    meta: AttnMetadata,
    input_embeds: Optional[jax.Array] = None,  # [B, Tq, D] overrides tokens
    embeds_mask: Optional[jax.Array] = None,   # [B, Tq] bool: mix per-token
    sp_mesh=None,  # Mesh with an "sp" axis: ring-attention prefill
    mesh=None,     # multi-device Mesh: shard_map the decode kernel over "tp"
    with_aux: bool = False,  # also return the summed ops/moe.py moe_stats
) -> tuple:
    """One paged forward step. Returns (logits [B, Tq, V], updated cache),
    plus an aux dict when with_aux=True (MoE capacity-drop counters summed
    over layers; empty for non-dispatch models).

    The pool stays where it is: the stacked leaves ride the layer scan's
    CARRY, never its xs / ys. A layer scatters the rows it produced into
    them at (layer, head, page, slot) (ops/attention.write_kv_rows) and
    then gathers the pages its rows name by (layer, page)
    (gather_pages): write, then read, the arithmetic of a per-layer
    write_kv_pages + paged_attention bit for bit, in place in the
    donated buffers. As xs / ys every layer sliced its whole pool out of
    the stack, re-laid it out for the scatter and back and copied it into
    the stacked output: eight moves of 134 MB a layer for a few hundred
    kilobytes of new rows (PERF.md section 6, PR 26). On an int8 pool the scale leaves travel
    the same way.

    When sp_mesh is given, prefill (Tq > 1) runs ring attention with the
    sequence sharded over "sp" (ops/ring_attention.py) instead of attending
    to the paged cache — the engine guarantees such prefills are whole-prompt
    single chunks with no cached prefix (engine.py asserts, prefix matching
    disabled), so chunk-internal attention IS the full attention.
    """
    b, tq = tokens.shape
    heads = (cfg.num_heads, cfg.num_kv_heads)
    kvq = bool(_validate_kv_quant(cfg.kv_quant))

    if input_embeds is None:
        # admission validated the ids  # dynalint: disable-next-line=R1
        x = jnp.take(params["embed"], tokens, axis=0)
    elif embeds_mask is not None:
        # multimodal prefill: image-patch positions take the vision
        # encoder's projected embeds, text positions take the token embeds
        # (the token ids at masked positions are hashing salts, not real
        # vocab ids — see scheduler._admit)
        x = jnp.where(embeds_mask[..., None],
                      input_embeds.astype(_dtype(cfg)),
                      # masked positions carry salts by design; the where
                      # drops their NaN embed rows
                      # dynalint: disable-next-line=R1
                      jnp.take(params["embed"], tokens, axis=0))
    else:
        x = input_embeds.astype(_dtype(cfg))
    # HF Gemma scales whatever enters the first layer (token embeds and
    # caller-supplied inputs_embeds alike)
    x = scale_embeds(x, cfg)

    use_kernel = tq == 1 and _decode_kernel_mode(cfg) is not None
    use_ring = sp_mesh is not None and tq > 1
    lw = cfg.layer_windows()
    layer_wnd = None if lw is None else jnp.asarray(lw, jnp.int32)
    if use_ring and (cfg.attn_softcap or cfg.query_scale
                     or lw is not None):
        raise NotImplementedError(
            "ring-attention (sp) prefill does not support attention "
            "soft-caps, sliding windows, or query-scale overrides; run "
            "Gemma-2-class models with sp=1 (chunked paged prefill)")
    if use_ring:
        from jax.sharding import NamedSharding
        from dynamo_tpu.ops.ring_attention import ring_attention
        # shard the token axis so layernorm/projections parallelize over sp
        x = jax.lax.with_sharding_constraint(
            x, NamedSharding(sp_mesh, P(None, "sp", None)))
        # padding slots carry position == last valid; mark keys invalid by
        # index (valid tokens occupy the first kv_len slots of the chunk)
        idx = jnp.arange(tq, dtype=jnp.int32)[None, :]
        kv_positions = jnp.where(idx < meta.kv_lens[:, None],
                                 meta.positions, -1)

    def layer_step(carry, layer):
        x, pool = carry            # pool: (k, v[, k_scale, v_scale]) stacks
        lp, lid = layer[:2]
        wnd = layer[2] if layer_wnd is not None else None
        q, k, v = layer_front(x, lp, cfg, meta.positions, heads)
        # rows as stored (an int8 pool quantizes them here, at capture);
        # [B, Tq, Hkv, ...] -> this layer's [1, B*Tq, Hkv, ...]
        pool = write_kv_rows(
            pool, tuple(r.reshape((1, b * tq) + r.shape[2:])
                        for r in stored_kv_rows(k, v, kvq)),
            write_plan, lid[None])
        kc, vc = pool[:2]
        ksc, vsc = pool[2:] if kvq else (None, None)
        if use_kernel:
            # decode hot path: stream pages HBM->VMEM, no materialized gather
            interp = _decode_kernel_mode(cfg) == "interpret"
            if mesh is not None and mesh.size > 1:
                attn = decode_paged_attention_sharded(
                    q[:, 0], kc, vc, meta.page_table, meta.kv_lens, mesh,
                    interpret=interp, k_scale=ksc, v_scale=vsc,
                    layer=lid[None])[:, None]
            else:
                attn = decode_paged_attention(
                    q[:, 0], kc, vc, meta.page_table, meta.kv_lens,
                    interpret=interp, k_scale=ksc, v_scale=vsc,
                    layer=lid[None])[:, None]
        elif use_ring:
            attn = ring_attention(q, k, v, meta.positions, kv_positions,
                                  sp_mesh)
        else:
            attn = paged_attention(q, kc, vc, meta.page_table, meta.kv_lens,
                                   meta.positions, softcap=cfg.attn_softcap,
                                   window=wnd, q_scale=cfg.query_scale,
                                   k_scale=ksc, v_scale=vsc, layer=lid)
        x, drop_stats = layer_back(
            x, attn, lp, cfg, lambda xn, lp: _mlp_block(
                xn, lp, cfg, mesh, token_valid, expert_stacks, lid))
        return (x, pool), drop_stats

    moe_aux = cfg.is_moe and cfg.moe_impl == "dispatch"
    # real (non-padding) positions: padding slots carry write_idx < 0
    token_valid = meta.write_idx >= 0 if moe_aux else None
    write_plan = kv_write_plan(meta.write_idx)
    # the stacked leaves ride the scan's carry whole, in the stored
    # representation  # dynalint: kv-codec — values are encoded at the
    # write (stored_kv_rows) and decoded at the gather (gather_values)
    pool = (cache["k"], cache["v"])
    if kvq:
        # dynalint: kv-codec — scale leaves ride the carry next to values
        pool = pool + (cache["k_scale"], cache["v_scale"])
    scan_layers, expert_stacks = split_expert_stacks(params["layers"], cfg,
                                                     mesh)
    scan_xs = (scan_layers, jnp.arange(cfg.num_layers, dtype=jnp.int32))
    if layer_wnd is not None:
        scan_xs = scan_xs + (layer_wnd,)
    (x, pool), drops = jax.lax.scan(layer_step, (x, pool), scan_xs)
    aux = _sum_stats(drops)

    logits = lm_logits(x, params["final_norm"], lm_head(params, cfg), cfg)
    cache_out = dict(zip(cache_keys(kvq), pool))
    if with_aux:
        return logits, cache_out, aux
    return logits, cache_out
