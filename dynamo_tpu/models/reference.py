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

And from the row `Ling-3.0-flash-VL` of the architecture catalog
(`bailing_hybrid`; the layer equations from the Kimi Linear report,
arXiv:2510.26692, for the linear layers, DeepSeek-V2/V3 for latent attention
and the grouped router, arXiv:2505.06708 for the head-wise output gate;
benchmark/reference/ling.py is the benchmark's copy of these lines). Layer
i is latent attention where (i + 1) % `layer_group_size` == 0 and Kimi
Delta Attention (KDA) otherwise; eps 1e-6.

  KDA         (`attention_kda`, the per-token recurrence) per head h of H,
              d = `head_dim`: q~, k~, v = SiLU(conv(x Wq)), SiLU(conv(x
              Wk)), SiLU(conv(x Wv)), conv a causal depthwise convolution
              of `short_conv_kernel_size` (4) over the sequence; q = q~ /
              |q~|_2 d**-0.5, k = k~ / |k~|_2; decay g = `kda_lower_bound`
              sigmoid(exp(A_log_h) (x Wf + dt_bias)) per CHANNEL, in
              (-5, 0); beta = sigmoid(x Wb) per head; S_t = (I - beta_t k_t
              k_t^T) diag(exp g_t) S_{t-1} + beta_t k_t v_t^T, o_t = S_t^T
              q_t; out = (RMSNorm_head(o_t) * sigmoid(x Wg)) Wo.
  the served form  (models/llama._kda_front / kda_chunk / kda_step, the same
              function): a chunk's tokens at once by the WY form, U = (I +
              diag(beta) A)^-1 diag(beta) (V - (K e^G) S_0) with A[t, i] =
              sum_c k_t k_i e^(G_t - G_i), i < t, G the running sum of g;
              the state lives in a per-sequence slot beside the page cache.
  latent attention  `attention_mla` with the row's two additions:
              `use_qk_norm`, an RMSNorm over each head's (nope | rope)
              query and over the shared rope key before RoPE (leaves
              `mla_q_norm`, `mla_k_norm`; the nope keys come from the
              latent, which `kv_a_layernorm` norms); and the head-wise
              gate, o_h * sigmoid(x Wgate)_h before Wo (`w_attn_gate`).
  experts     s = sigmoid(x Wr) over all E; the pick is on s + bias and
              group-limited: a group's score is the sum of its two
              largest, the `topk_group` best of `n_group` groups stay, the
              k best experts come from them; w = s_i / sum s_picked x
              `routed_scaling_factor`. A chip's SHARE (`expert_first`,
              `experts_held`): the router and the pick are over all E, the
              expert leaves hold the share's experts alone, and what the
              absent experts would add is left out (model-configs guide,
              section 4); the shared expert is computed here whole.

And from the row `Mellum2-12B-A2.5B-Instruct` of the architecture catalog
(`model_type` mellum; the class name is assumed, `MellumForCausalLM`;
benchmark/reference/mellum.py is the benchmark's copy of these lines).
Pre-norm residual block, plain RMSNorm (eps 1e-6), final norm, untied
head, no biases.

  attention   (every layer) `attention` above without a QK-norm (no key
              of the row's config declares one): q -> [T, 32, 128], k, v ->
              [T, 4, 128], rotate-half RoPE over the full head with THIS
              LAYER KIND's table, 8 query heads a KV head, causal softmax
              in float32 at 128 ** -0.5, Wo.
  sliding_attention layers (`layer_types[i]`, 3 of every 4): a query at
              p sees keys j with p - `sliding_window` < j <= p (`window`).
              RoPE: `rope_parameters.sliding_attention`, plain.
  full_attention layers: all keys j <= p. RoPE:
              `rope_parameters.full_attention`, YaRN as `transformers`
              computes it (`yarn_inv_freq`): f_i = theta ** (2i / d);
              extrapolated 1 / f_i, interpolated 1 / (factor f_i); c(r) =
              d ln(L0 / (2 pi r)) / (2 ln theta); low = floor(c(beta_fast)),
              high = ceil(c(beta_slow)), clipped to [0, d - 1]; ramp_i =
              clip((i - low) / (high - low), 0, 1); inv_freq_i =
              interpolated_i ramp_i + extrapolated_i (1 - ramp_i); cos and
              sin are multiplied by `attention_factor` at EVERY position,
              not only past the original context L0.
  experts     (every layer, `mlp_layer_types` all sparse) `expert_mlp`
              with the softmax router, the 8 largest of 64 renormalised
              (`norm_topk_prob` true), width `moe_intermediate_size`; no
              shared expert, no selection bias, no scale.
  left out    `described_as` mentions a multi-token-prediction head that
              no key of `config` describes; `max_window_layers: 0` beside
              an explicit `layer_types` (`layer_types` governs);
              `intermediate_size`, unused where every layer is sparse.

And from the row `Trinity-Mini` of the architecture catalog (`model_type`
afmoe; `transformers` 4.57.6 here has no such class and there is no
network, so what no key of the row's config states is written from the
family's published modelling code as the issue that asked for it gives it,
one line each below; benchmark/reference/trinity.py is the benchmark's copy
of these lines). RMSNorm eps 1e-5, final norm, untied head, no biases.

  embedding   h0 = E[ids] * sqrt(hidden_size) (`mup_enabled`;
              `embed_scale`).
  attention   (every layer) q -> [T, 32, 128], k, v -> [T, 4, 128];
              g = x Wg [T, 4096]. RMSNorm over EACH head's 128 values of
              q and of k, one weight vector for all heads (`qk_norm`
              "head": code-sourced), BEFORE RoPE. sliding_attention
              layers: rotate-half RoPE over the full head at `rope_theta`
              and keys j with p - `sliding_window` < j <= p.
              full_attention layers: NO positional embedding (`rope=None`:
              code-sourced) and all keys j <= p. 8 query heads a KV head,
              causal softmax in float32 at 128 ** -0.5; the output times
              sigmoid(g), element-wise, before Wo (`w_out_gate`:
              code-sourced).
  block       four norms (code-sourced): h = h + RMSNorm(attention(
              RMSNorm(h; attn_norm)); post_attn_norm); h = h +
              RMSNorm(mlp(RMSNorm(h; mlp_norm)); post_mlp_norm).
  layer 0..   (`num_dense_layers` layers, a stack of their own IN FRONT
              of the kind stacks: `lead0`, ...) a dense SwiGLU of
              `intermediate_size` (6144).
  the others  `expert_mlp` with Moonlight's router: s = sigmoid(x Wr) in
              float32 over all 128; idx = top8(s + b), b the selection
              bias, which picks and does not weigh (code-sourced); w =
              s[idx] / (sum + 1e-20) (`route_norm`) x `route_scale`
              2.826; plus ONE shared SwiGLU of `num_shared_experts` x
              `moe_intermediate_size` on every token. `n_group` 1: no
              group limit.

And from the row `Falcon-H1-34B-Instruct` of the architecture catalog
(`model_type` falcon_h1), read against `transformers`' own
`modeling_falcon_h1.py` (4.57.6, its non-kernel path; tests/
test_falcon_h1.py holds this file to it on a tiny configuration with every
multiplier active; benchmark/reference/falcon_h1.py is the benchmark's
copy of these lines). RMSNorm eps 1e-5, `final_layernorm`, untied head,
no bias on any projection. With h the residual stream:

  model       h0 = E[ids] * `embedding_multiplier` (`embed_scale`); the
              blocks; logits = (RMSNorm(h) W_head) * `lm_head_multiplier`.
  block       u = RMSNorm(h; attn_norm). TWO branches read the same u and
              are ADDED (`parallel_block`): h = h + `ssm_out_multiplier`
              SSM(u) + `attention_out_multiplier` Attn(
              `attention_in_multiplier` u); then h = h + MLP(RMSNorm(h;
              mlp_norm)).
  Attn        `attention` above, no QK-norm, with k = (u Wk) *
              `key_multiplier` BEFORE RoPE (plain, theta 1e11).
  SSM         (`mixer_ssm`, Mamba-2 as the per-token recurrence) p =
              ((`ssm_in_multiplier` u) W_in) * mup, W_in to z | x | B | C
              | dt (d_ssm | d_ssm | G N | G N | H), mup the constant
              vector that multiplies those five segments by
              `ssm_multipliers`[0..4]. x | B | C pass a depth-wise causal
              convolution of `mamba_d_conv` taps WITH bias, then SiLU. x
              -> [H, P] heads, B, C -> [G, N] groups (head h reads group
              h // (H / G)). dt_h = softplus(dt_h + dt_bias_h), A_h =
              -exp(A_log_h); per head S [P, N]: S_t = exp(dt_t A) S_{t-1}
              + dt_t x_t B_t^T, y_t = S_t C_t + D_h x_t. Then the gated
              grouped norm, the gate FIRST (`mamba_norm_before_gate`
              false): y = RMSNorm_groups(y * SiLU(z)), the variance over
              each of the G groups of d_ssm / G, one weight of d_ssm;
              W_out.
  the served form  (models/llama._ssm_front / ssm_mix_rows / ssm_decode,
              ops/state_space.py, the same function): a prompt chunk's
              tokens at once by the quadratic form inside blocks of 64
              with the cumulative-decay mask, the state passed between
              blocks and kept in a per-sequence slot beside the K / V
              pages the same layer holds; one token a row in place.
  MLP         down(up(x) * SiLU(gate(x) * `mlp_multipliers`[0])) *
              `mlp_multipliers`[1] (`dense_mlp`'s `multipliers`).

And from the row `LFM2-8B-A1B` of the architecture catalog (`model_type`
lfm2_moe), the non-expert parts read against `transformers`' own
`modeling_lfm2.py` (4.57.6, the dense family's class, its non-kernel path;
tests/test_lfm2.py holds this file to it on a tiny configuration;
benchmark/reference/lfm2.py is the benchmark's copy of these lines).
RMSNorm eps `norm_eps`, plain weight; the final norm is the leaf the
checkpoint calls `embedding_norm`; the head is the embedding table.

  block       h = h + mixer(RMSNorm(h; operator_norm)); h = h +
              ffn(RMSNorm(h; ffn_norm)). `layer_types[i]` says which
              mixer layer i has: "conv" | "full_attention".
  conv        (`short_conv`) B | C | u = x W_in [D, 3 D], in that order;
              g = B * u; c_t = sum_{j < K} w[j] * g_{t - (K - 1) + j}, a
              causal depth-wise convolution of K = `conv_L_cache` taps
              with zeros before the sequence and NO activation; out = (C *
              c) W_out. What a sequence carries from token to token is the
              last K - 1 rows of g (`tails`).
  attention   `attention` above with `qk_norm` "head": an RMSNorm over
              each head's values of q and of k (`q_layernorm`,
              `k_layernorm`, one weight of head_dim for all heads) BEFORE
              rotate-half RoPE at `rope_theta`; heads of hidden / H.
  ffn         the first `num_dense_layers` layers a SwiGLU of
              `intermediate_size`; the others (code-sourced: the installed
              `transformers` has no `lfm2_moe`) `expert_mlp` with s =
              sigmoid(x Wr) in float32 over all E; idx = top_k(s + b), b
              the `expert_bias` (`use_expert_bias`), which picks and does
              not weigh; w = s[idx] / (sum + 1e-6) (`norm_topk_prob`; the
              constant is `renorm_eps`) x `routed_scaling_factor`; no
              shared expert, no groups.

And from the row `Brumby-14B-Base` of the architecture catalog
(https://huggingface.co/manifestai/Brumby-14B-Base/blob/main/config.json,
`model_type` brumby: Qwen3-14B's block with every layer's attention
replaced by power retention; the mixer is the gated power retention of
"Scaling Context Requires Rethinking Attention", arXiv:2507.04239, at
degree 2. The config carries no key of the mixer: its degree, its gate and
what it keeps of Qwen3's front are benchmark/configs/brumby-14b/meta.json's
`assumed`; benchmark/reference/brumby.py is the benchmark's copy of these
lines). Pre-norm residual block, plain RMSNorm, eps 1e-6, dense SwiGLU,
final norm, untied head.

  mixer       (`attention_retention`, the masked QUADRATIC form: no state,
              no chunks, no features) q = xn Wq, k = xn Wk, v = xn Wv, no
              bias; RMSNorm over each head's values of q and of k, one
              weight of head_dim a projection; rotate-half RoPE at
              `rope_theta` on q and k; log g = log_sigmoid(xn Wg + bg), one
              scalar a key-value head and token. For query head h of
              key-value head c = h // (H / Hkv): w[t, i] = exp(sum_{l =
              i + 1 .. t} log g_l[c]) (q_t[h] . k_i[c])^2 for i <= t; o_t[h]
              = sum_i w[t, i] v_i[c] / sum_i w[t, i]: every weight >= 0, no
              softmax, no scale on q . k (it cancels), the plain sum below
              the line (the served path adds 1e-12 to it). Then Wo, no
              output gate.
  the served form  (ops/power_retention.py, the same function as a
              recurrence over phi, phi(x) . phi(y) = (x . y)^2):
              `retention_features` is phi in the layout the served state
              holds, written as plain index arithmetic, and
              `retention_state` the state S [Hkv, hd, F], z [Hkv, F] after
              a sequence by the per-token recurrence: what a served
              sequence's slot is compared with.

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

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def rms_norm(x, w, eps):
    return w * (x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                                  + eps))


def yarn_inv_freq(dim, theta, factor, original_max_position, beta_fast,
                  beta_slow):
    """YaRN's frequencies [dim / 2]: a ramp over the dimensions between
    the interpolated and the extrapolated ones (module docstring)."""
    f = theta ** (jnp.arange(0, dim, 2, dtype=F32) / dim)
    extrapolated, interpolated = 1.0 / f, 1.0 / (factor * f)

    def c(turns):
        return dim * math.log(original_max_position / (2 * math.pi * turns)) \
            / (2 * math.log(theta))
    low = max(math.floor(c(beta_fast)), 0)
    high = min(math.ceil(c(beta_slow)), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=F32) - low) / (high - low),
                    0.0, 1.0)
    return interpolated * ramp + extrapolated * (1.0 - ramp)


def rope(x, positions, theta, yarn=None):
    """Rotate-half RoPE over the full head. x: [T, H, hd]. `yarn`: a dict
    of `yarn_inv_freq`'s arguments after theta, and `attention_factor`
    (0: 0.1 ln(factor) + 1), which multiplies cos and sin. `theta` None:
    a layer kind without a positional embedding, x as it is."""
    if theta is None:
        return x
    hd = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    scale = 1.0
    if yarn:
        inv_freq = yarn_inv_freq(
            hd, theta, yarn["factor"], yarn["original_max_position"],
            yarn["beta_fast"], yarn["beta_slow"])
        scale = yarn.get("attention_factor") \
            or 0.1 * math.log(yarn["factor"]) + 1.0
    angle = positions.astype(F32)[:, None] * inv_freq[None, :]  # [T, hd/2]
    cos, sin = (scale * jnp.cos(angle)[:, None, :],
                scale * jnp.sin(angle)[:, None, :])
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(x, lp, *, num_heads, num_kv_heads, head_dim, rope_theta,
              rms_norm_eps, qk_norm, window=0, yarn=None,
              key_multiplier=1.0):
    """`window` > 0: a query at p sees keys j with p - window < j <= p.
    `qk_norm` True: over the whole projection; "head": over each head,
    one weight vector for all. `rope_theta` None: no rotation. A
    `w_out_gate` leaf: the output times sigmoid(x Wg) before Wo.
    `key_multiplier` scales k before RoPE."""
    t = x.shape[0]
    q, k, v = x @ lp["wq"], (x @ lp["wk"]) * key_multiplier, x @ lp["wv"]
    if "wq_b" in lp:
        q, k, v = q + lp["wq_b"], k + lp["wk_b"], v + lp["wv_b"]
    if qk_norm is True:              # over the whole projection, pre-split
        q = rms_norm(q, lp["q_norm"], rms_norm_eps)
        k = rms_norm(k, lp["k_norm"], rms_norm_eps)
    q = q.reshape(t, num_heads, head_dim)
    k = k.reshape(t, num_kv_heads, head_dim)
    if qk_norm == "head":            # over each head's values, post-split
        q = rms_norm(q, lp["q_norm"], rms_norm_eps)
        k = rms_norm(k, lp["k_norm"], rms_norm_eps)
    positions = jnp.arange(t)
    q = rope(q, positions, rope_theta, yarn)
    k = rope(k, positions, rope_theta, yarn)
    v = v.reshape(t, num_kv_heads, head_dim)
    group = num_heads // num_kv_heads          # grouped-query: share k, v
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) * head_dim ** -0.5
    causal = positions[None, :] <= positions[:, None]          # [q, k]
    if window:
        causal &= positions[:, None] - positions[None, :] < window
    scores = jnp.where(causal[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("hqk,khd->qhd", probs, v)
    out = out.reshape(t, num_heads * head_dim)
    if "w_out_gate" in lp:           # element-wise output gate
        out = out * jax.nn.sigmoid(x @ lp["w_out_gate"])
    return out @ lp["wo"]


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
    ckv = x @ lp["wkv_a"]                                   # [T, r + dr]
    k_pe = ckv[:, None, r:]                                 # [T, 1, dr]
    if "mla_q_norm" in lp:           # the hybrid's QK-norm, before RoPE
        q = rms_norm(q, lp["mla_q_norm"], rms_norm_eps)
        k_pe = rms_norm(k_pe, lp["mla_k_norm"], rms_norm_eps)
    q_nope, q_pe = q[..., :dn], q[..., dn:]
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
    if "w_attn_gate" in lp:          # head-wise output gate
        out = out * jax.nn.sigmoid(x @ lp["w_attn_gate"])[:, :, None]
    return out.reshape(t, h * head_dim) @ lp["wo"]


def causal_conv(x, w):
    """Causal depthwise convolution over the sequence. x [T, C], w [K, C]:
    y_t = sum_j w[j] x_{t - (K - 1) + j}, zeros before the sequence."""
    k = w.shape[0]
    xp = jnp.concatenate([jnp.zeros((k - 1, x.shape[1]), x.dtype), x])
    return sum(w[j] * xp[j:j + x.shape[0]] for j in range(k))


def short_conv(x, lp, tails=None):
    """The gated short convolution (module docstring). x [T, D], the
    normed input. `tails`: a list that takes this layer's state after
    the sequence, the last K - 1 rows of B * u [K - 1, D] (zeros where
    the sequence is shorter)."""
    d = x.shape[1]
    p = x @ lp["conv_in"]
    g = p[:, :d] * p[:, 2 * d:]
    if tails is not None:
        k = lp["conv_w"].shape[0]
        tails.append(jnp.concatenate(
            [jnp.zeros((k - 1, d), g.dtype), g])[-(k - 1):])
    return (p[:, d:2 * d] * causal_conv(g, lp["conv_w"])) @ lp["wo"]


def l2_normalize(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def round_to(x, dtype):
    """x rounded to `dtype`'s exponent and mantissa, still float32. Not a
    cast there and back: XLA drops such a pair (it may keep excess
    precision), and the rounding is the point."""
    info = jnp.finfo(dtype)
    if info.bits >= 32:
        return x
    return jax.lax.reduce_precision(x, info.nexp, info.nmant)


def kda_recurrence(s, xs, state_dtype=F32):
    """One token of the gated delta rule: (s [H, dk, dv], (q, k [H, dk],
    v [H, dv], g [H, dk], b [H])) -> (s', o [H, dv]): decay, the delta
    against what the decayed state holds at k, read at q."""
    q_t, k_t, v_t, g_t, b_t = xs
    s = jnp.exp(g_t)[:, :, None] * s
    s = s + jnp.einsum("hk,hv->hkv", k_t, b_t[:, None] * (
        v_t - jnp.einsum("hk,hkv->hv", k_t, s)))
    s = round_to(s, state_dtype)
    return s, jnp.einsum("hkv,hk->hv", s, q_t)


def attention_kda(x, lp, *, num_heads, head_dim, lower_bound, rms_norm_eps,
                  state_dtype=F32):
    """Kimi Delta Attention as the per-token recurrence. x [T, D], the
    normed input; `head_dim` is the linear layers' own. `state_dtype`:
    what S is rounded to after every token (float32: not at all)."""
    t, h, d = x.shape[0], num_heads, head_dim
    qkv = jax.nn.silu(causal_conv(x @ lp["kda_wqkv"], lp["kda_conv_w"]))
    q, k, v = (a.reshape(t, h, d) for a in jnp.split(qkv, 3, axis=-1))
    q, k = l2_normalize(q) * d ** -0.5, l2_normalize(k)
    g = lower_bound * jax.nn.sigmoid(
        jnp.exp(lp["kda_a_log"])[None, :, None]
        * (x @ lp["kda_wf"] + lp["kda_dt_bias"]).reshape(t, h, d))
    beta = jax.nn.sigmoid(x @ lp["kda_wb"])                     # [T, H]

    _, o = jax.lax.scan(
        functools.partial(kda_recurrence, state_dtype=state_dtype),
        jnp.zeros((h, d, d), F32), (q, k, v, g, beta))
    o = rms_norm(o, lp["kda_o_norm"], rms_norm_eps)
    o = o * jax.nn.sigmoid(x @ lp["kda_wg"]).reshape(t, h, d)
    return o.reshape(t, h * d) @ lp["wo"]


def retention_features(x):
    """phi(x) [..., F] of x [..., d], phi(x) . phi(y) = (x . y)^2, in the
    layout the served state holds: feature s d + a is c_s x[a] x[(a + s)
    mod d] for s = 0 .. d / 2, c = 1 for the squares (s = 0) and for s =
    d / 2 (each of those pairs stands twice), sqrt 2 between."""
    d = x.shape[-1]
    s = jnp.arange(d // 2 + 1)[:, None]
    a = jnp.arange(d)[None, :]
    coef = jnp.where((s == 0) | (s == d // 2), 1.0, math.sqrt(2.0))
    return (coef * x[..., None, :] * x[..., (a + s) % d]).reshape(
        x.shape[:-1] + (-1,))


def retention_state(k, v, log_g, state_dtype=F32):
    """The power-retention state after a sequence, by the per-token
    recurrence: k, v [T, Hkv, hd] (k normed and rotated), log_g [T, Hkv]
    -> (S [Hkv, hd, F], z [Hkv, F]): S_t = g_t S_{t-1} + v_t phi(k_t)^T,
    z_t = g_t z_{t-1} + phi(k_t). `state_dtype`: what both are rounded to
    after every token (float32: not at all)."""
    def step(carry, xs):
        s, z = carry
        k_t, v_t, g_t = xs
        pk = retention_features(k_t)
        g_t = jnp.exp(g_t)
        s = g_t[:, None, None] * s + v_t[:, :, None] * pk[:, None, :]
        z = g_t[:, None] * z + pk
        return (round_to(s, state_dtype), round_to(z, state_dtype)), None
    hkv, hd = k.shape[1:]
    f = hd * (hd // 2 + 1)
    return jax.lax.scan(step, (jnp.zeros((hkv, hd, f), F32),
                               jnp.zeros((hkv, f), F32)),
                        (k, v, log_g))[0]


def attention_retention(x, lp, *, num_heads, num_kv_heads, head_dim,
                        rope_theta, rms_norm_eps, softmax=False,
                        tails=None):
    """Gated power retention at degree 2 as the masked quadratic form
    (module docstring). x [T, D], the normed input. `tails`: a list that
    takes this layer's state after the sequence, (S, z) of
    `retention_state`. `softmax`: Qwen3's own mixer behind the same front
    (causal softmax at head_dim ** -0.5, no gate), which is how the front
    is held to `transformers` (tests/test_brumby.py)."""
    t, hkv = x.shape[0], num_kv_heads
    q = rms_norm((x @ lp["wq"]).reshape(t, num_heads, head_dim),
                 lp["q_norm"], rms_norm_eps)
    k = rms_norm((x @ lp["wk"]).reshape(t, hkv, head_dim), lp["k_norm"],
                 rms_norm_eps)
    v = (x @ lp["wv"]).reshape(t, hkv, head_dim)
    positions = jnp.arange(t)
    q, k = rope(q, positions, rope_theta), rope(k, positions, rope_theta)
    causal = positions[None, :] <= positions[:, None]          # [q, k]
    qk = jnp.einsum("qcgd,kcd->cgqk",
                    q.reshape(t, hkv, num_heads // hkv, head_dim), k)
    if softmax:
        w = jax.nn.softmax(jnp.where(causal, qk * head_dim ** -0.5,
                                     -jnp.inf), axis=-1)
    else:
        log_g = jax.nn.log_sigmoid(x @ lp["ret_wg"] + lp["ret_bg"])
        if tails is not None:
            tails.append(retention_state(k, v, log_g))
        gc = jnp.cumsum(log_g, axis=0).T                       # [Hkv, T]
        decay = jnp.exp(jnp.where(causal, gc[:, :, None] - gc[:, None, :],
                                  -jnp.inf))                   # [Hkv, q, k]
        w = decay[:, None] * qk * qk
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    out = jnp.einsum("cgqk,kcd->qcgd", w, v)
    return out.reshape(t, num_heads * head_dim) @ lp["wo"]


def ssm_recurrence(consts, s, xs, state_dtype=F32):
    """One token of the state-space scan: (consts (A, D [H]), s [H, P,
    N], (x [H, P], dt [H], b, c [H, N])) -> (s', y [H, P]): decay, the
    input weighed by dt along B, read along C, the skip."""
    a, d = consts
    x_t, dt_t, b_t, c_t = xs
    s = jnp.exp(dt_t * a)[:, None, None] * s \
        + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
    s = round_to(s, state_dtype)
    return s, jnp.einsum("hpn,hn->hp", s, c_t) + d[:, None] * x_t


def mixer_ssm(x, lp, *, n_heads, d_head, n_groups, d_state, rms_norm_eps,
              in_multiplier=1.0, multipliers=(1.0,) * 5, state_dtype=F32):
    """The Mamba-2 mixer as the per-token recurrence. x [T, D], the
    normed input. `state_dtype`: what S is rounded to after every token
    (float32: not at all)."""
    t, h, g, n = x.shape[0], n_heads, n_groups, d_state
    ds = h * d_head
    sizes = [ds, ds, g * n, g * n, h]
    mup = jnp.concatenate([jnp.full((size,), m, F32)
                           for size, m in zip(sizes, multipliers)])
    p = ((x * in_multiplier) @ lp["ssm_in"]) * mup
    z, xbc, dt = p[:, :ds], p[:, ds:-h], p[:, -h:]
    xbc = jax.nn.silu(causal_conv(xbc, lp["ssm_conv_w"]) + lp["ssm_conv_b"])
    xs = xbc[:, :ds].reshape(t, h, d_head)
    # a head reads its group's B and C
    b, c = (jnp.repeat(v.reshape(t, g, n), h // g, axis=1)
            for v in (xbc[:, ds:ds + g * n], xbc[:, ds + g * n:]))
    dt = jax.nn.softplus(dt + lp["ssm_dt_bias"])
    _, y = jax.lax.scan(
        functools.partial(ssm_recurrence,
                          (-jnp.exp(lp["ssm_a_log"]), lp["ssm_d"]),
                          state_dtype=state_dtype),
        jnp.zeros((h, d_head, n), F32), (xs, dt, b, c))
    y = y.reshape(t, ds) * jax.nn.silu(z)                  # the gate first
    y = y.reshape(t, g, ds // g)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                          + rms_norm_eps)
    return (y.reshape(t, ds) * lp["ssm_norm"]) @ lp["ssm_out"]


def parallel_block(x, lp, *, attn, ssm, attention_in_multiplier=1.0,
                   attention_out_multiplier=1.0, ssm_out_multiplier=1.0,
                   state_dtype=F32, without_ssm=False):
    """Both mixers of a parallel block on the one normed input x [T, D],
    their outputs added, each times its multiplier. `attn`, `ssm`: the
    keyword arguments of `attention` and `mixer_ssm`. `without_ssm`
    leaves the state-space branch out (a control of the comparison)."""
    out = attention_out_multiplier * attention(
        x * attention_in_multiplier, lp, **attn)
    if without_ssm:
        return out
    return out + ssm_out_multiplier * mixer_ssm(
        x, lp, state_dtype=state_dtype, **ssm)


def dense_mlp(x, lp, names=("w_gate", "w_up", "w_down"),
              multipliers=(1.0, 1.0)):
    gate, up, down = (lp[name] for name in names)
    return ((jax.nn.silu((x @ gate) * multipliers[0]) * (x @ up)) @ down) \
        * multipliers[1]


def group_limited(pick, n_group, topk_group):
    """DeepSeek-V3's group-limited pick: the experts lie in `n_group`
    equal groups in order; a group's score is the sum of its two largest
    `pick`; outside the `topk_group` best groups `pick` becomes -inf."""
    t, e = pick.shape
    grouped = pick.reshape(t, n_group, e // n_group)
    score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)     # [T, G]
    _, kept = jax.lax.top_k(score, topk_group)
    mask = jnp.sum(jax.nn.one_hot(kept, n_group, dtype=F32), 1) > 0
    return jnp.where(mask[:, :, None], grouped, -jnp.inf).reshape(t, e)


def router_weights(x, lp, *, num_experts_per_tok, norm_topk_prob,
                   moe_scoring="softmax", moe_routed_scale=1.0,
                   n_group=1, topk_group=1, renorm_eps=1e-20):
    """[T, E] float32: each token's weight on every expert, zero outside
    its top-k. A `router_bias` leaf picks and does not weigh.
    `renorm_eps`: what `norm_topk_prob` adds to the kept weights' sum."""
    logits = x @ lp["router"]                                  # [T, E]
    scores = (jax.nn.sigmoid(logits) if moe_scoring == "sigmoid"
              else jax.nn.softmax(logits, axis=-1))
    pick = scores + lp["router_bias"] if "router_bias" in lp else scores
    if n_group > 1:
        pick = group_limited(pick, n_group, topk_group)
    _, chosen = jax.lax.top_k(pick, num_experts_per_tok)       # [T, k]
    mask = jnp.sum(jax.nn.one_hot(chosen, scores.shape[-1], dtype=F32), 1)
    weights = scores * mask
    if norm_topk_prob:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                             + renorm_eps)
    return weights * moe_routed_scale


def expert_mlp(x, lp, expert_first=0, **router):
    """Every expert on every token, masked by the top-k; plus the shared
    expert (leaves `ws_*`) where the layer has one. Where the expert
    leaves hold a share of the router's experts (fewer than its columns),
    they are experts `expert_first` on, and the others add nothing."""
    weights = router_weights(x, lp, **router)
    held = lp["w_gate"].shape[0]
    weights = weights[:, expert_first:expert_first + held]
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
          moe_scoring="softmax", moe_routed_scale=1.0, kda=None,
          n_group=1, topk_group=1, expert_first=0, window=0, yarn=None,
          par=None, mlp_multipliers=(1.0, 1.0), renorm_eps=1e-20,
          tails=None):
    """One pre-norm residual block. x: [T, D]; lp: this layer's weights,
    float32. `mla`: attention_mla's sizes (a dict) for latent attention;
    `kda`: attention_kda's, for a layer that has its leaves. A layer
    without a `router` leaf has a dense MLP. `window`, `yarn`: THIS
    layer's sliding width and RoPE scaling (`layer_kind_kwargs`). A
    layer with `post_attn_norm` / `post_mlp_norm` leaves norms each
    half's output before the residual (four norms a block). `par`:
    `parallel_block`'s arguments after `attn`, for a layer with a
    state-space mixer beside its attention (`key_multiplier` among
    them, which is attention's). A layer with a `conv_in` leaf is a
    gated short convolution (`short_conv`, which `tails` goes to); one
    with a `ret_wg` leaf is power retention (`attention_retention`,
    likewise)."""
    def post(out, name):
        return rms_norm(out, lp[name], rms_norm_eps) if name in lp else out
    xn = rms_norm(x, lp["attn_norm"], rms_norm_eps)
    if "ssm_in" in lp:
        par = dict(par)
        out = parallel_block(
            xn, lp, attn=dict(
                num_heads=num_heads, num_kv_heads=num_kv_heads,
                head_dim=head_dim, rope_theta=rope_theta,
                rms_norm_eps=rms_norm_eps, qk_norm=qk_norm,
                key_multiplier=par.pop("key_multiplier")), **par)
    elif "conv_in" in lp:
        out = short_conv(xn, lp, tails)
    elif "ret_wg" in lp:
        out = attention_retention(
            xn, lp, num_heads=num_heads, num_kv_heads=num_kv_heads,
            head_dim=head_dim, rope_theta=rope_theta,
            rms_norm_eps=rms_norm_eps, tails=tails)
    elif "kda_wqkv" in lp:
        out = attention_kda(xn, lp, num_heads=num_heads,
                            rms_norm_eps=rms_norm_eps, **kda)
    elif mla:
        out = attention_mla(xn, lp, num_heads=num_heads,
                            head_dim=head_dim, rope_theta=rope_theta,
                            rms_norm_eps=rms_norm_eps, **mla)
    else:
        out = attention(
            xn, lp, num_heads=num_heads, num_kv_heads=num_kv_heads,
            head_dim=head_dim, rope_theta=rope_theta,
            rms_norm_eps=rms_norm_eps, qk_norm=qk_norm, window=window,
            yarn=yarn)
    x = x + post(out, "post_attn_norm")
    xn = rms_norm(x, lp["mlp_norm"], rms_norm_eps)
    if num_experts and "router" in lp:
        out = expert_mlp(xn, lp,
                         num_experts_per_tok=num_experts_per_tok,
                         norm_topk_prob=norm_topk_prob,
                         moe_scoring=moe_scoring,
                         moe_routed_scale=moe_routed_scale,
                         expert_first=expert_first,
                         **(dict(n_group=n_group, topk_group=topk_group)
                            if n_group > 1 else {}),
                         # only where a family has its own constant: the
                         # older families' callers (and the tests that
                         # stand a mutant router in) know no such argument
                         **(dict(renorm_eps=renorm_eps)
                            if renorm_eps != 1e-20 else {}))
    else:
        out = dense_mlp(xn, lp, multipliers=mlp_multipliers)
    return x + post(out, "post_mlp_norm")


def arch_kwargs(cfg) -> dict:
    """`layer`'s keyword arguments from a ModelConfig."""
    mla = dict(kv_lora_rank=cfg.kv_lora_rank,
               qk_nope_head_dim=cfg.qk_nope_head_dim,
               qk_rope_head_dim=cfg.qk_rope_head_dim) if cfg.is_mla else None
    by_kind = {}
    if cfg.window_pool:
        import dataclasses
        by_kind = dict(layer_types=tuple(cfg.layer_types),
                       sliding_window=cfg.sliding_window,
                       rope_full=dataclasses.asdict(cfg.rope_full),
                       rope_sliding=dataclasses.asdict(cfg.rope_sliding))
    if cfg.has_conv:
        by_kind = dict(layer_types=tuple(cfg.layer_types),
                       renorm_eps=cfg.moe_renorm_eps)
    if cfg.embed_scale:     # beside them: `forward` takes it out again
        by_kind["embed_scale"] = cfg.embed_scale
    if cfg.has_ssm:
        by_kind.update(
            lm_head_multiplier=cfg.lm_head_multiplier,
            mlp_multipliers=tuple(cfg.mlp_multipliers),
            par=dict(
                key_multiplier=cfg.key_multiplier,
                attention_in_multiplier=cfg.attention_in_multiplier,
                attention_out_multiplier=cfg.attention_out_multiplier,
                ssm_out_multiplier=cfg.ssm_out_multiplier,
                ssm=dict(n_heads=cfg.mamba_n_heads,
                         d_head=cfg.mamba_d_head,
                         n_groups=cfg.mamba_n_groups,
                         d_state=cfg.mamba_d_state,
                         rms_norm_eps=cfg.rms_norm_eps,
                         in_multiplier=cfg.ssm_in_multiplier,
                         multipliers=tuple(cfg.ssm_multipliers))))
    return dict(num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
                head_dim=cfg.head_dim, rope_theta=cfg.rope_theta,
                rms_norm_eps=cfg.rms_norm_eps, qk_norm=cfg.qk_norm,
                num_experts=cfg.num_experts,
                num_experts_per_tok=cfg.num_experts_per_tok,
                norm_topk_prob=cfg.norm_topk_prob, mla=mla,
                moe_scoring=cfg.moe_scoring,
                moe_routed_scale=cfg.moe_routed_scale,
                kda=dict(head_dim=cfg.linear_head_dim,
                         lower_bound=cfg.linear_gate_lower_bound)
                if cfg.linear_group_size else None,
                n_group=cfg.moe_n_group, topk_group=cfg.moe_topk_group,
                expert_first=cfg.expert_first, **by_kind)


def layer_kind_kwargs(index, layer_types=(), sliding_window=0,
                      rope_full=None, rope_sliding=None) -> dict:
    """`layer`'s arguments that go by layer KIND, for layer `index` of a
    model whose `layer_types` says which layers slide: its window (0 on a
    full layer) and its RoPE (`rope_theta`, and `yarn` where the kind's
    `rope_type` is yarn; `rope_theta` None where it is none: the kind
    has no positional embedding). {} for a model of one kind, and for
    one whose kinds share one RoPE (conv layers beside attention: the
    layer's own leaves say which it is)."""
    if not layer_types or rope_full is None:
        return {}
    sliding = layer_types[index] == "sliding_attention"
    p = rope_sliding if sliding else rope_full
    return dict(window=sliding_window if sliding else 0,
                rope_theta=None if p["rope_type"] == "none"
                else p["theta"],
                yarn=p if p["rope_type"] == "yarn" else None)


LAYER_GROUPS = ("dense_layers", "layers")   # in the model's layer order


def layer_stacks(params) -> list:
    """The model's layer stacks in layer order: the two named above, or a
    hybrid's runs of like layers, `run0`, `run1`, ... (models/llama.
    layer_groups)."""
    runs = sorted((k for k in params if k.startswith("run")),
                  key=lambda k: int(k[3:]))
    return [params[k] for k in runs or LAYER_GROUPS if k in params]


def layers_in_order(params, layer_types=()) -> list:
    """Every layer's weights, in the model's order. Where `layer_types`
    says which layers slide, the stacks are a stack a KIND, in the order
    the kinds first appear (models/llama.layer_runs), and the model's
    order interleaves them; a dense lead in front of them has stacks of
    its own (`lead0`, ...), whose layers come first."""
    def unstacked(stacks):
        return [{name: leaf[i] for name, leaf in stack.items()}
                for stack in stacks for i in range(len(stack["attn_norm"]))]
    stacks = layer_stacks(params)
    if not layer_types:
        return unstacked(stacks)
    out = unstacked(params[k] for k in sorted(
        (k for k in params if k.startswith("lead")),
        key=lambda k: int(k[4:])))
    layer_types = layer_types[len(out):]
    kinds = list(dict.fromkeys(layer_types))
    taken = [0] * len(kinds)
    for kind in layer_types:
        s = kinds.index(kind)
        out.append({name: leaf[taken[s]]
                    for name, leaf in stacks[s].items()})
        taken[s] += 1
    return out


def forward(params, tokens, **arch):
    """tokens [T] -> logits [T, V] float32: one full forward pass over one
    sequence. `params` is the engine's tree (models/llama.init_params /
    models/loader.load_params_from_hf), in any dtype: upcast here.
    `tails` (a list): takes every conv layer's state after the sequence,
    in layer order (`short_conv`)."""
    by_kind = {k: arch.pop(k) for k in (
        "layer_types", "sliding_window", "rope_full", "rope_sliding")
        if k in arch}
    embed_scale = arch.pop("embed_scale", 0.0)
    lm_head_multiplier = arch.pop("lm_head_multiplier", 1.0)
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda a: jnp.asarray(a, F32), params)
        # ids the engine served  # dynalint: disable-next-line=R1
        x = params["embed"][jnp.asarray(tokens)]
        if embed_scale:
            x = x * embed_scale
        for index, lp in enumerate(layers_in_order(
                params, by_kind.get("layer_types", ()))):
            x = layer(x, lp, **{**arch,
                                **layer_kind_kwargs(index, **by_kind)})
        x = rms_norm(x, params["final_norm"], arch["rms_norm_eps"])
        head = params["lm_head"] if "lm_head" in params \
            else params["embed"].T
        return (x @ head) * lm_head_multiplier
