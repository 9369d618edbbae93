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
`norm_topk_prob=True`); only OLMoE is held to it so far.

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


def dense_mlp(x, lp):
    return (jax.nn.silu(x @ lp["w_gate"]) * (x @ lp["w_up"])) @ lp["w_down"]


def expert_mlp(x, lp, *, num_experts_per_tok, norm_topk_prob):
    """Every expert on every token, masked by the top-k."""
    probs = jax.nn.softmax(x @ lp["router"], axis=-1)          # [T, E]
    _, chosen = jax.lax.top_k(probs, num_experts_per_tok)      # [T, k]
    mask = jnp.sum(jax.nn.one_hot(chosen, probs.shape[-1], dtype=F32), 1)
    weights = probs * mask
    if norm_topk_prob:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    hidden = (jax.nn.silu(jnp.einsum("td,edf->etf", x, lp["w_gate"]))
              * jnp.einsum("td,edf->etf", x, lp["w_up"]))
    y = jnp.einsum("etf,efd->etd", hidden, lp["w_down"])       # [E, T, D]
    return jnp.einsum("te,etd->td", weights, y)


def layer(x, lp, *, num_heads, num_kv_heads, head_dim, rope_theta,
          rms_norm_eps, qk_norm=False, num_experts=0,
          num_experts_per_tok=0, norm_topk_prob=True):
    """One pre-norm residual block. x: [T, D]; lp: this layer's weights,
    float32."""
    x = x + attention(
        rms_norm(x, lp["attn_norm"], rms_norm_eps), lp,
        num_heads=num_heads, num_kv_heads=num_kv_heads, head_dim=head_dim,
        rope_theta=rope_theta, rms_norm_eps=rms_norm_eps, qk_norm=qk_norm)
    xn = rms_norm(x, lp["mlp_norm"], rms_norm_eps)
    if num_experts:
        return x + expert_mlp(xn, lp,
                              num_experts_per_tok=num_experts_per_tok,
                              norm_topk_prob=norm_topk_prob)
    return x + dense_mlp(xn, lp)


def arch_kwargs(cfg) -> dict:
    """`layer`'s keyword arguments from a ModelConfig."""
    return dict(num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
                head_dim=cfg.head_dim, rope_theta=cfg.rope_theta,
                rms_norm_eps=cfg.rms_norm_eps, qk_norm=cfg.qk_norm,
                num_experts=cfg.num_experts,
                num_experts_per_tok=cfg.num_experts_per_tok,
                norm_topk_prob=cfg.norm_topk_prob)


def forward(params, tokens, **arch):
    """tokens [T] -> logits [T, V] float32: one full forward pass over one
    sequence. `params` is the engine's tree (models/llama.init_params /
    models/loader.load_params_from_hf), in any dtype: upcast here."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda a: jnp.asarray(a, F32), params)
        # ids the engine served  # dynalint: disable-next-line=R1
        x = params["embed"][jnp.asarray(tokens)]
        num_layers = params["layers"]["wq"].shape[0]
        for i in range(num_layers):
            lp = {name: leaf[i] for name, leaf in params["layers"].items()}
            x = layer(x, lp, **arch)
        x = rms_norm(x, params["final_norm"], arch["rms_norm_eps"])
        head = params["lm_head"] if "lm_head" in params \
            else params["embed"].T
        return x @ head
