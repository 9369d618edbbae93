"""The plain reference: a decoder's forward pass in straightforward
`jax.numpy`, float32, matmuls at `jax.default_matmul_precision("highest")`.
No cache, no batching, no paging, no kernels, no scan: one sequence in, the
logits at every position out. It is what the served path (models/llama.py
through the engine's mixed steps, KV pool and decode windows) is held to
(ROADMAP R0; tests/test_olmoe.py; benchmark/reference/olmoe.py is the
benchmark's own copy of these lines, and a tier-1 test keeps the two
identical).

Written from the published OLMoE model (`OlmoeForCausalLM`,
https://huggingface.co/allenai/OLMoE-1B-7B-0125-Instruct):

  attention   q = RMSNorm_q(x Wq), k = RMSNorm_k(x Wk), v = x Wv, each
              norm over the WHOLE projection (all heads together, its own
              weight vector), THEN the split into heads, rotate-half RoPE
              over the full head, causal softmax at scale head_dim**-0.5,
              Wo. Pre-norm residual block, plain RMSNorm (w * x_hat).
  experts     p = softmax(x W_router) in float32 over ALL experts; the k
              largest p are the weights as they are (`norm_topk_prob:
              false`: not renormalised); y = sum_i p_i W_down,i(
              silu(W_gate,i x) * W_up,i x). EVERY expert is evaluated on
              every token and masked by the top-k: the plainest form. No
              capacity, nothing dropped.

A dense-MLP or renormalised-router model (Mistral, Mixtral) is the same
function with other arguments (`qk_norm=False`, `num_experts=0`,
`norm_topk_prob=True`); OLMoE and Moonlight are held to it so far.

And from the published Moonlight-16B-A3B (`DeepseekV3ForCausalLM`,
https://huggingface.co/moonshotai/Moonlight-16B-A3B, `modeling_deepseek.py`;
benchmark/reference/moonlight.py is the benchmark's copy of these lines).
Pre-norm residual block, plain RMSNorm, eps 1e-5, final norm, untied head.

  attention   (every layer; `attention_mla`, the EXPANDED form) q = x Wq ->
              [T, H, dn + dr] = q_nope | q_pe (no query LoRA: `q_lora_rank`
              null). x Wkv_a -> [T, r + dr] = c | k_pe, k_pe shared by all
              heads. c_n = RMSNorm(c; kv_a_layernorm). c_n Wkv_b ->
              [T, H, dn + dv] = k_nope | v. RoPE (theta 50000, over the dr
              dims) on q_pe and k_pe only. k = k_nope | k_pe, q = q_nope |
              q_pe, causal softmax in float32 of q k^T (dn + dr)**-0.5 (no
              `mscale`: `rope_scaling` is null), out = P v -> [T, H dv] Wo.
              Moonlight: H 16, dn 128, dr 64, dv 128, r 512.
  the served form  (models/llama._mla_front / _mla_out, ABSORBED, the same
              function): with Wkv_b = W_UK | W_UV per head, q_lat = q_nope
              W_UK^T [H, r]; score = (q_lat . c_n + q_pe . k_pe) (dn +
              dr)**-0.5; o_lat = P c_n [H, r]; out_h = o_lat W_UV. The cache
              row of a token is c_n | rotated k_pe: r + dr = 576 values,
              one leaf, no heads.
  layer 0..   (`first_k_dense_replace` layers) a dense SwiGLU MLP of
              `intermediate_size` (11264).
  the others  s = sigmoid(x Wg) in float32 over all E experts; idx =
              top_k(s + b) with b = `e_score_correction_bias` (the one
              group of `n_group` 1 is kept whole); w = s[idx]: the bias
              picks, it does not weigh; w = w / (sum w + 1e-20) *
              `routed_scaling_factor` (2.446); y = sum_i w_i E_idx_i(x) +
              S(x), E a SwiGLU of `moe_intermediate_size` (1408), S ONE
              SwiGLU of n_shared_experts x that (2816) on every token.
              Every expert is evaluated on every token and masked.

Departures from the published model, each deliberate:
  * weights are taken in this repo's layout: projections stored
    [in, out] (the checkpoint's are [out, in]; models/loader.py
    transposes), stacked over layers on a leading axis, experts on the
    next. The arithmetic is the published one;
  * everything is float32, where the published model runs bfloat16 with
    float32 islands (norms, router softmax): the reference is the
    function, not one rounding of it;
  * `clip_qkv` is not modeled (published: null; the loader refuses
    anything else);
  * a tie between the k-th and (k+1)-th router probability goes to the
    lower expert id (`jax.lax.top_k`), as in the served path; the
    published `torch.topk` leaves the order of ties unspecified.
  * RoPE under latent attention: the published code keeps each rotary
    pair in adjacent columns and, before rotating halves, de-interleaves
    the activations (`view(.., d/2, 2).transpose`). `attention_mla` does
    exactly that when `rope_interleaved` is true, which is how a
    checkpoint's own weights are read (tests/test_moonlight.py). The
    engine's layout has those columns of Wq / Wkv_a de-interleaved once at
    load (models/loader.deinterleave_rope: (x W)[perm] == x W[:, perm]),
    so on the engine's arrays, which is what every comparison uses, the
    reference rotates halves directly (`rope_interleaved` false);
  * what the loader refuses is not modelled here either: a query LoRA,
    more than one expert group, `rope_scaling` (and its `mscale`),
    multi-token-prediction layers.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def rms_norm(x, w, eps):
    return w * (x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                                  + eps))


def rope(x, positions, theta):
    """Rotate-half RoPE over the full head. x: [T, H, hd]."""
    hd = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    angle = positions.astype(F32)[:, None] * inv_freq[None, :]  # [T, hd/2]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(x, lp, *, num_heads, num_kv_heads, head_dim, rope_theta,
              rms_norm_eps, qk_norm):
    t = x.shape[0]
    q, k, v = x @ lp["wq"], x @ lp["wk"], x @ lp["wv"]
    if "wq_b" in lp:
        q, k, v = q + lp["wq_b"], k + lp["wk_b"], v + lp["wv_b"]
    if qk_norm:                      # over the whole projection, pre-split
        q = rms_norm(q, lp["q_norm"], rms_norm_eps)
        k = rms_norm(k, lp["k_norm"], rms_norm_eps)
    positions = jnp.arange(t)
    q = rope(q.reshape(t, num_heads, head_dim), positions, rope_theta)
    k = rope(k.reshape(t, num_kv_heads, head_dim), positions, rope_theta)
    v = v.reshape(t, num_kv_heads, head_dim)
    group = num_heads // num_kv_heads          # grouped-query: share k, v
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) * head_dim ** -0.5
    causal = positions[None, :] <= positions[:, None]          # [q, k]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("hqk,khd->qhd", probs, v)
    return out.reshape(t, num_heads * head_dim) @ lp["wo"]


def deinterleave(x):
    """[.., d] with rotary pairs in adjacent columns -> evens | odds."""
    return jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)


def attention_mla(x, lp, *, num_heads, head_dim, kv_lora_rank,
                  qk_nope_head_dim, qk_rope_head_dim, rope_theta,
                  rms_norm_eps, rope_interleaved=False):
    """Multi-head latent attention, expanded: per-head keys and values
    are rebuilt from the latent. `head_dim` is the value head's."""
    t, h, r = x.shape[0], num_heads, kv_lora_rank
    dn, dr = qk_nope_head_dim, qk_rope_head_dim
    q = (x @ lp["wq"]).reshape(t, h, dn + dr)
    q_nope, q_pe = q[..., :dn], q[..., dn:]
    ckv = x @ lp["wkv_a"]                                   # [T, r + dr]
    k_pe = ckv[:, None, r:]                                 # [T, 1, dr]
    c = rms_norm(ckv[:, :r], lp["kv_a_norm"], rms_norm_eps)
    kv = (c @ lp["wkv_b"]).reshape(t, h, dn + head_dim)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    if rope_interleaved:
        q_pe, k_pe = deinterleave(q_pe), deinterleave(k_pe)
    positions = jnp.arange(t)
    q_pe = rope(q_pe, positions, rope_theta)
    k_pe = rope(k_pe, positions, rope_theta)
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (t, h, dr))], -1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) * (dn + dr) ** -0.5
    causal = positions[None, :] <= positions[:, None]          # [q, k]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("hqk,khd->qhd", probs, v)
    return out.reshape(t, h * head_dim) @ lp["wo"]


def dense_mlp(x, lp, names=("w_gate", "w_up", "w_down")):
    gate, up, down = (lp[name] for name in names)
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def router_weights(x, lp, *, num_experts_per_tok, norm_topk_prob,
                   moe_scoring="softmax", moe_routed_scale=1.0):
    """[T, E] float32: each token's weight on every expert, zero outside
    its top-k. A `router_bias` leaf picks and does not weigh."""
    logits = x @ lp["router"]                                  # [T, E]
    scores = (jax.nn.sigmoid(logits) if moe_scoring == "sigmoid"
              else jax.nn.softmax(logits, axis=-1))
    pick = scores + lp["router_bias"] if "router_bias" in lp else scores
    _, chosen = jax.lax.top_k(pick, num_experts_per_tok)       # [T, k]
    mask = jnp.sum(jax.nn.one_hot(chosen, scores.shape[-1], dtype=F32), 1)
    weights = scores * mask
    if norm_topk_prob:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                             + 1e-20)
    return weights * moe_routed_scale


def expert_mlp(x, lp, **router):
    """Every expert on every token, masked by the top-k; plus the shared
    expert (leaves `ws_*`) where the layer has one."""
    weights = router_weights(x, lp, **router)
    hidden = (jax.nn.silu(jnp.einsum("td,edf->etf", x, lp["w_gate"]))
              * jnp.einsum("td,edf->etf", x, lp["w_up"]))
    y = jnp.einsum("etf,efd->etd", hidden, lp["w_down"])       # [E, T, D]
    y = jnp.einsum("te,etd->td", weights, y)
    if "ws_gate" in lp:
        y = y + dense_mlp(x, lp, ("ws_gate", "ws_up", "ws_down"))
    return y


def layer(x, lp, *, num_heads, num_kv_heads, head_dim, rope_theta,
          rms_norm_eps, qk_norm=False, num_experts=0,
          num_experts_per_tok=0, norm_topk_prob=True, mla=None,
          moe_scoring="softmax", moe_routed_scale=1.0):
    """One pre-norm residual block. x: [T, D]; lp: this layer's weights,
    float32. `mla`: attention_mla's sizes (a dict) for latent attention.
    A layer without a `router` leaf has a dense MLP."""
    xn = rms_norm(x, lp["attn_norm"], rms_norm_eps)
    if mla:
        x = x + attention_mla(xn, lp, num_heads=num_heads,
                              head_dim=head_dim, rope_theta=rope_theta,
                              rms_norm_eps=rms_norm_eps, **mla)
    else:
        x = x + attention(
            xn, lp, num_heads=num_heads, num_kv_heads=num_kv_heads,
            head_dim=head_dim, rope_theta=rope_theta,
            rms_norm_eps=rms_norm_eps, qk_norm=qk_norm)
    xn = rms_norm(x, lp["mlp_norm"], rms_norm_eps)
    if num_experts and "router" in lp:
        return x + expert_mlp(xn, lp,
                              num_experts_per_tok=num_experts_per_tok,
                              norm_topk_prob=norm_topk_prob,
                              moe_scoring=moe_scoring,
                              moe_routed_scale=moe_routed_scale)
    return x + dense_mlp(xn, lp)


def arch_kwargs(cfg) -> dict:
    """`layer`'s keyword arguments from a ModelConfig."""
    mla = dict(kv_lora_rank=cfg.kv_lora_rank,
               qk_nope_head_dim=cfg.qk_nope_head_dim,
               qk_rope_head_dim=cfg.qk_rope_head_dim) if cfg.is_mla else None
    return dict(num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
                head_dim=cfg.head_dim, rope_theta=cfg.rope_theta,
                rms_norm_eps=cfg.rms_norm_eps, qk_norm=cfg.qk_norm,
                num_experts=cfg.num_experts,
                num_experts_per_tok=cfg.num_experts_per_tok,
                norm_topk_prob=cfg.norm_topk_prob, mla=mla,
                moe_scoring=cfg.moe_scoring,
                moe_routed_scale=cfg.moe_routed_scale)


LAYER_GROUPS = ("dense_layers", "layers")   # in the model's layer order


def forward(params, tokens, **arch):
    """tokens [T] -> logits [T, V] float32: one full forward pass over one
    sequence. `params` is the engine's tree (models/llama.init_params /
    models/loader.load_params_from_hf), in any dtype: upcast here."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda a: jnp.asarray(a, F32), params)
        # ids the engine served  # dynalint: disable-next-line=R1
        x = params["embed"][jnp.asarray(tokens)]
        for group in LAYER_GROUPS:
            stack = params.get(group, {"wq": ()})
            for i in range(len(stack["wq"])):
                lp = {name: leaf[i] for name, leaf in stack.items()}
                x = layer(x, lp, **arch)
        x = rms_norm(x, params["final_norm"], arch["rms_norm_eps"])
        head = params["lm_head"] if "lm_head" in params \
            else params["embed"].T
        return x @ head
