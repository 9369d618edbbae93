"""The benchmark's own copy of the plain reference for the language model of
Ling-3.0-flash-VL (`bailing_hybrid`): the forward pass in straightforward
`jax.numpy`, float32, matmuls at `jax.default_matmul_precision("highest")`.
No cache, no state slots, no batching, no kernels: Kimi Delta Attention as
the PER-TOKEN recurrence (a `lax.scan` over the sequence), where the served
path runs the chunkwise form and a one-token form over state slots; latent
attention in the EXPANDED form, where the served path runs the absorbed
form over its one-leaf cache; every held expert evaluated on every token
and masked by the router, where the served path sorts and runs grouped
matmuls. It imports nothing from `dynamo_tpu`: what the served path is
compared with (checks/reference_logits_ling.py) is kept with the benchmark,
so no PR that changes the program changes the yardstick. The functions
down to `layer` are dynamo_tpu/models/reference.py's, line for line
(tests/test_ling.py and benchmark/tests/test_ling_cell.py hold the two to
identical logits); that file's docstring has the layer equations. What is
added here is `forward_blocked`, which does the same arithmetic at the
published widths on the chip beside the served model: weights stay in their
stored dtype and are upcast inside each jitted piece, the experts a block
at a time, and the head and the log-softmax only at the positions asked
for.

A chip's share: the router is over all the published experts, the expert
leaves hold `num_experts` of them from `expert_first` on, and what the
absent experts would add is left out, as in the served path.

Weights come in the engine's layout: projections [in, out], stacked over
layers on a leading axis, a stack a run of like layers (`run0`, `run1`,
... in layer order), experts on the next axis.
"""
from __future__ import annotations

import functools

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


def attention_mla(x, lp, *, num_heads, head_dim, kv_lora_rank,
                  qk_nope_head_dim, qk_rope_head_dim, rope_theta,
                  rms_norm_eps):
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

    def step(s, xs):                       # s [H, dk, dv]
        q_t, k_t, v_t, g_t, b_t = xs
        s = jnp.exp(g_t)[:, :, None] * s
        s = s + jnp.einsum("hk,hv->hkv", k_t, b_t[:, None] * (
            v_t - jnp.einsum("hk,hkv->hv", k_t, s)))
        s = round_to(s, state_dtype)
        return s, jnp.einsum("hkv,hk->hv", s, q_t)

    _, o = jax.lax.scan(step, jnp.zeros((h, d, d), F32),
                        (q, k, v, g, beta))
    o = rms_norm(o, lp["kda_o_norm"], rms_norm_eps)
    o = o * jax.nn.sigmoid(x @ lp["kda_wg"]).reshape(t, h, d)
    return o.reshape(t, h * d) @ lp["wo"]


def dense_mlp(x, lp, names=("w_gate", "w_up", "w_down")):
    gate, up, down = (lp[name] for name in names)
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


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
                   n_group=1, topk_group=1):
    """[T, E] float32: each token's weight on every expert, zero outside
    its top-k. A `router_bias` leaf picks and does not weigh."""
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
                             + 1e-20)
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


def layer(x, lp, *, num_heads, head_dim, rope_theta, rms_norm_eps, mla, kda,
          router, expert_first=0):
    """One pre-norm residual block. x: [T, D]; lp: this layer's weights,
    float32. A layer with `kda_wqkv` is a linear layer, any other has
    latent attention; a layer without a `router` leaf has a dense MLP."""
    xn = rms_norm(x, lp["attn_norm"], rms_norm_eps)
    if "kda_wqkv" in lp:
        x = x + attention_kda(xn, lp, num_heads=num_heads,
                              rms_norm_eps=rms_norm_eps, **kda)
    else:
        x = x + attention_mla(xn, lp, num_heads=num_heads,
                              head_dim=head_dim, rope_theta=rope_theta,
                              rms_norm_eps=rms_norm_eps, **mla)
    xn = rms_norm(x, lp["mlp_norm"], rms_norm_eps)
    if "router" in lp:
        return x + expert_mlp(xn, lp, expert_first=expert_first, **router)
    return x + dense_mlp(xn, lp)


def arch_from_hf(hf: dict) -> dict:
    """`layer`'s keyword arguments from the configuration's config.json."""
    return dict(
        num_heads=hf["num_attention_heads"],
        head_dim=int(hf["v_head_dim"]),
        rope_theta=float(hf.get("rope_theta", 10000.0)),
        rms_norm_eps=float(hf.get("rms_norm_eps", 1e-6)),
        mla=dict(kv_lora_rank=int(hf["kv_lora_rank"]),
                 qk_nope_head_dim=int(hf["qk_nope_head_dim"]),
                 qk_rope_head_dim=int(hf["qk_rope_head_dim"])),
        kda=dict(head_dim=int(hf["head_dim"]),
                 lower_bound=float(hf.get("kda_lower_bound", -5))),
        router=dict(num_experts_per_tok=int(hf["num_experts_per_tok"]),
                    norm_topk_prob=bool(hf.get("norm_topk_prob", True)),
                    moe_scoring=hf["score_function"],
                    moe_routed_scale=float(
                        hf.get("routed_scaling_factor", 1.0)),
                    n_group=int(hf.get("n_group") or 1),
                    topk_group=int(hf.get("topk_group") or 1)),
        expert_first=int(hf.get("expert_first", 0)))


def layer_stacks(params) -> list:
    """The model's runs of like layers, in layer order."""
    runs = sorted((k for k in params if k.startswith("run")),
                  key=lambda k: int(k[3:]))
    return [params[k] for k in runs]


def forward(params, tokens, hf: dict, **changes):
    """tokens [T] -> logits [T, V] float32: one full forward pass over one
    sequence, every weight upcast at once (a small model)."""
    arch = {**arch_from_hf(hf), **changes}
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda a: jnp.asarray(a, F32), params)
        # ids the engine served  # dynalint: disable-next-line=R1
        x = params["embed"][jnp.asarray(tokens)]
        for stack in layer_stacks(params):
            for i in range(len(stack["attn_norm"])):
                lp = {name: leaf[i] for name, leaf in stack.items()}
                x = layer(x, lp, **arch)
        x = rms_norm(x, params["final_norm"], arch["rms_norm_eps"])
        return x @ params["lm_head"]


EXPERT_LEAVES = ("w_gate", "w_up", "w_down")
SHARED_LEAVES = ("ws_gate", "ws_up", "ws_down")


def _freeze(d: dict) -> tuple:
    return tuple(sorted((k, _freeze(v) if isinstance(v, dict) else v)
                        for k, v in d.items()))


@functools.partial(jax.jit, static_argnames=("arch", "state_dtype"))
def _mixer(x, lp, arch, state_dtype):
    """x + the layer's attention (either kind) on the normed input."""
    arch = dict(arch)
    lp = jax.tree.map(lambda a: a.astype(F32), lp)
    xn = rms_norm(x, lp["attn_norm"], arch["rms_norm_eps"])
    if "kda_wqkv" in lp:
        return x + attention_kda(
            xn, lp, num_heads=arch["num_heads"],
            rms_norm_eps=arch["rms_norm_eps"], state_dtype=state_dtype,
            **dict(arch["kda"]))
    return x + attention_mla(
        xn, lp, num_heads=arch["num_heads"], head_dim=arch["head_dim"],
        rope_theta=arch["rope_theta"], rms_norm_eps=arch["rms_norm_eps"],
        **dict(arch["mla"]))


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(x, w, eps):
    return rms_norm(x, w.astype(F32), eps)


@functools.partial(jax.jit, static_argnames=("router",))
def _route(xn, lp, router):
    lp = jax.tree.map(lambda a: a.astype(F32), lp)
    return router_weights(xn, lp, **dict(router))


@jax.jit
def _expert_block(xn, weights, w_gate, w_up, w_down):
    """A block of experts on every token, weighted: [T, D]."""
    w_gate, w_up, w_down = (w.astype(F32) for w in (w_gate, w_up, w_down))
    hidden = (jax.nn.silu(jnp.einsum("td,edf->etf", xn, w_gate))
              * jnp.einsum("td,edf->etf", xn, w_up))
    y = jnp.einsum("etf,efd->etd", hidden, w_down)
    return jnp.einsum("te,etd->td", weights, y)


@jax.jit
def _dense(xn, w_gate, w_up, w_down):
    return dense_mlp(xn, {"w_gate": w_gate.astype(F32),
                          "w_up": w_up.astype(F32),
                          "w_down": w_down.astype(F32)})


@jax.jit
def _logits_block(x, head):
    return x @ head.astype(F32)


def forward_blocked(params, tokens, hf: dict, positions=None,
                    expert_block: int = 16, vocab_block: int = 16384,
                    cast=None, state_dtype=F32):
    """`forward`'s arithmetic at the published widths: tokens [T] ->
    log-softmax over the vocabulary, float32, at `positions` (a list of
    row indices; None: every row) -> [len(positions), V]. `cast`, if
    given, is applied to every weight leaf first, and `state_dtype` is
    what the linear layers' state is rounded to after every token
    (checks/reference_logits_ling.py uses the two to show what the
    reference reads in the next lower precision)."""
    arch = arch_from_hf(hf)
    frozen = _freeze({k: v for k, v in arch.items()
                      if k not in ("router", "expert_first")})
    router = _freeze(arch["router"])
    first = arch["expert_first"]
    cast = cast or (lambda a: a)
    eps = arch["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        # ids the engine served  # dynalint: disable-next-line=R1
        x = cast(params["embed"])[jnp.asarray(tokens)].astype(F32)
        for stack in layer_stacks(params):
            mixer = [name for name in stack
                     if name not in EXPERT_LEAVES + SHARED_LEAVES
                     + ("router", "router_bias", "mlp_norm")]
            for i in range(len(stack["attn_norm"])):
                x = _mixer(x, {name: cast(stack[name][i])
                               for name in mixer}, frozen, state_dtype)
                xn = _norm(x, cast(stack["mlp_norm"][i]), eps)
                if "router" not in stack:
                    x = x + _dense(xn, *(cast(stack[name][i])
                                         for name in EXPERT_LEAVES))
                    continue
                rl = {name: cast(stack[name][i])
                      for name in ("router", "router_bias") if name in stack}
                held = stack["w_gate"].shape[1]
                weights = _route(xn, rl, router)[:, first:first + held]
                for lo in range(0, held, expert_block):
                    hi = min(held, lo + expert_block)
                    x = x + _expert_block(
                        xn, weights[:, lo:hi],
                        *(cast(stack[name][i, lo:hi])
                          for name in EXPERT_LEAVES))
                if "ws_gate" in stack:
                    x = x + _dense(xn, *(cast(stack[name][i])
                                         for name in SHARED_LEAVES))
        if positions is not None:
            x = x[jnp.asarray(positions)]
        x = _norm(x, cast(params["final_norm"]), eps)
        head = params["lm_head"]
        logits = jnp.concatenate(
            [_logits_block(x, cast(head[:, lo:lo + vocab_block]))
             for lo in range(0, head.shape[1], vocab_block)], axis=1)
        return jax.nn.log_softmax(logits, axis=-1)
