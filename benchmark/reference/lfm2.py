"""The benchmark's own copy of the plain reference for the `lfm2_moe` family
(LFM2-8B-A1B): the lines of dynamo_tpu/models/reference.py that this model
reads, with NO import from `dynamo_tpu` (benchmark/tests/test_lfm2_cell.py
and tests/test_lfm2.py hold the two to identical logits), and
`forward_blocked`, the same arithmetic a block of heads, of experts and of
the vocabulary at a time, for the published widths on a chip that also
holds the served model, with the controls that
checks/reference_logits_lfm2.py reads.

Plain float32 `jax.numpy` at `highest` matmul precision: no cache, no
chunks, no paging, no kernels, one sequence in, logits at every position
out. Written from the row `LFM2-8B-A1B` of the architecture catalog
(`model_type` lfm2_moe); the non-expert parts read against `transformers`'
own `modeling_lfm2.py` (4.57.6, the dense family's class), the expert block
from the published class's code ("code-sourced": the installed
`transformers` has no `lfm2_moe`). Plain RMSNorm (w * x_hat, eps `norm_eps`);
the final norm is what the checkpoint calls `embedding_norm`; the head is
the embedding table (the family's default tie).

  block       h = h + mixer(RMSNorm(h; operator_norm)); h = h +
              ffn(RMSNorm(h; ffn_norm)). `layer_types[i]`: "conv" |
              "full_attention".
  conv        B | C | u = x W_in [D, 3 D], in that order; g = B * u; c_t =
              sum_{j < K} w[j] * g_{t - (K - 1) + j}: a causal depth-wise
              convolution of K = `conv_L_cache` taps, zeros before the
              sequence, NO activation; out = (C * c) W_out. What a sequence
              carries from token to token is the last K - 1 rows of g.
  attention   q = x Wq -> [T, H, hd], k = x Wk, v = x Wv -> [T, Hkv, hd],
              hd = hidden / H; RMSNorm over EACH head's hd values of q and
              of k, one weight vector for all heads, before rotate-half
              RoPE at `rope_theta`; H / Hkv query heads a KV head; causal
              softmax in float32 at hd ** -0.5; Wo.
  layer 0..   (`num_dense_layers` layers) a dense SwiGLU of
              `intermediate_size`.
  the others  (code-sourced) s = sigmoid(x Wr) in float32 over all E; idx =
              top_k(s + b), b the `expert_bias` (`use_expert_bias`), which
              picks and does not weigh; w = s[idx] / (sum s[idx] + 1e-6)
              (`norm_topk_prob`) x `routed_scaling_factor`; y = sum_i w_i
              E_idx_i(x), E a SwiGLU of `moe_intermediate_size`. Every
              expert on every token, masked: nothing dropped. No shared
              expert, no groups.

Weights are read in the engine's layout: projections [in, out]; the lead's
layers in stacks of their own (`lead0`, ...), then a stack a layer KIND
(`run0`, `run1`: all the attention layers behind the lead, all the conv
ones, in the order the kinds first appear there), experts on the next axis.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
RENORM_EPS = 1e-6


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
              rms_norm_eps):
    t = x.shape[0]
    q = (x @ lp["wq"]).reshape(t, num_heads, head_dim)
    k = (x @ lp["wk"]).reshape(t, num_kv_heads, head_dim)
    v = (x @ lp["wv"]).reshape(t, num_kv_heads, head_dim)
    q = rms_norm(q, lp["q_norm"], rms_norm_eps)     # over each head's values
    k = rms_norm(k, lp["k_norm"], rms_norm_eps)
    positions = jnp.arange(t)
    q, k = rope(q, positions, rope_theta), rope(k, positions, rope_theta)
    group = num_heads // num_kv_heads          # grouped-query: share k, v
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) * head_dim ** -0.5
    causal = positions[None, :] <= positions[:, None]          # [q, k]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("hqk,khd->qhd", probs, v)
    return out.reshape(t, num_heads * head_dim) @ lp["wo"]


def causal_conv(g, w, reset_every=0):
    """Causal depthwise convolution over the sequence. g [T, C], w [K, C]:
    y_t = sum_j w[j] g_{t - (K - 1) + j}, zeros before the sequence.
    `reset_every` n > 0 (a control): a tap that reaches back over a
    position that is a multiple of n reads zero, as if the tail were lost
    at every n-token edge."""
    k, t = w.shape[0], g.shape[0]
    gp = jnp.concatenate([jnp.zeros((k - 1, g.shape[1]), g.dtype), g])
    at = jnp.arange(t)
    y = 0.0
    for j in range(k):
        tap = gp[j:j + t]
        if reset_every:
            seen = at - (k - 1) + j >= (at // reset_every) * reset_every
            tap = jnp.where(seen[:, None], tap, 0.0)
        y = y + w[j] * tap
    return y


def conv_gate(x, lp):
    """(g = B * u, C) of the normed input x [T, D]: `conv_in` is B | C | u."""
    d = x.shape[1]
    p = x @ lp["conv_in"]
    return p[:, :d] * p[:, 2 * d:], p[:, d:2 * d]


def tail_of(g, k, tokens=None, reset_every=0):
    """The state after `tokens` tokens (None: all): the last K - 1 rows of
    g[:tokens], zeros where the sequence is shorter; under `reset_every`
    the rows before the last edge are zero."""
    tokens = g.shape[0] if tokens is None else tokens
    gp = jnp.concatenate([jnp.zeros((k - 1, g.shape[1]), g.dtype), g])
    tail = gp[tokens:tokens + k - 1]             # rows tokens - (K - 1) ..
    if reset_every:
        at = tokens - (k - 1) + jnp.arange(k - 1)
        # the row a step at `tokens` would read: lost if before its edge
        tail = jnp.where(
            (at >= (tokens // reset_every) * reset_every)[:, None], tail,
            0.0)
    return tail


def short_conv(x, lp, tails=None):
    g, c = conv_gate(x, lp)
    if tails is not None:
        tails.append(tail_of(g, lp["conv_w"].shape[0]))
    return (c * causal_conv(g, lp["conv_w"])) @ lp["wo"]


def dense_mlp(x, lp):
    return (jax.nn.silu(x @ lp["w_gate"]) * (x @ lp["w_up"])) @ lp["w_down"]


def router_weights(x, lp, *, num_experts_per_tok, norm_topk_prob,
                   moe_routed_scale, bias_in_weights=False):
    """[T, E] float32: each token's weight on every expert, zero outside
    its top-k. The `router_bias` leaf (where the model has one) picks and
    does not weigh; `bias_in_weights` (a control) weighs with it too."""
    scores = jax.nn.sigmoid(x @ lp["router"])                  # [T, E]
    pick = scores + lp["router_bias"] if "router_bias" in lp else scores
    _, chosen = jax.lax.top_k(pick, num_experts_per_tok)       # [T, k]
    mask = jnp.sum(jax.nn.one_hot(chosen, scores.shape[-1], dtype=F32), 1)
    weights = (pick if bias_in_weights else scores) * mask
    if norm_topk_prob:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                             + RENORM_EPS)
    return weights * moe_routed_scale


def expert_mlp(x, lp, **router):
    """Every expert on every token, masked by the top-k."""
    weights = router_weights(x, lp, **router)
    hidden = (jax.nn.silu(jnp.einsum("td,edf->etf", x, lp["w_gate"]))
              * jnp.einsum("td,edf->etf", x, lp["w_up"]))
    y = jnp.einsum("etf,efd->etd", hidden, lp["w_down"])       # [E, T, D]
    return jnp.einsum("te,etd->td", weights, y)


ROUTER = ("num_experts_per_tok", "norm_topk_prob", "moe_routed_scale")
ATTN = ("num_heads", "num_kv_heads", "head_dim", "rope_theta",
        "rms_norm_eps")


def layer(x, lp, arch, tails=None):
    """One pre-norm residual block. x: [T, D]; lp: this layer's weights,
    float32. A layer with a `conv_in` leaf is a gated short convolution,
    one without a `router` leaf has a dense MLP (the lead)."""
    eps = arch["rms_norm_eps"]
    xn = rms_norm(x, lp["attn_norm"], eps)
    if "conv_in" in lp:
        out = short_conv(xn, lp, tails)
    else:
        out = attention(xn, lp, **{k: arch[k] for k in ATTN})
    x = x + out
    xn = rms_norm(x, lp["mlp_norm"], eps)
    if "router" in lp:
        return x + expert_mlp(xn, lp, **{k: arch[k] for k in ROUTER})
    return x + dense_mlp(xn, lp)


def arch_from_hf(hf: dict) -> dict:
    """`layer`'s arguments from the config.json."""
    if hf.get("conv_bias"):
        raise ValueError("conv_bias: true is not modelled")
    for key in ("n_group", "topk_group"):
        if hf.get(key) not in (None, 1):
            raise ValueError(f"{key}={hf[key]!r}: one expert group is what "
                             f"is modelled")
    if hf.get("rope_scaling"):
        raise ValueError("rope_scaling is not modelled")
    heads = int(hf["num_attention_heads"])
    return dict(
        num_heads=heads, num_kv_heads=int(hf["num_key_value_heads"]),
        head_dim=int(hf.get("head_dim") or hf["hidden_size"] // heads),
        rope_theta=float(hf.get("rope_theta", 1e6)),
        rms_norm_eps=float(hf.get("norm_eps", 1e-5)),
        num_experts_per_tok=int(hf.get("num_experts_per_tok", 0)),
        norm_topk_prob=bool(hf.get("norm_topk_prob", True)),
        moe_routed_scale=float(hf.get("routed_scaling_factor", 1.0)),
        layer_types=tuple(hf["layer_types"]),
        conv_taps=int(hf.get("conv_L_cache", 3)))


def _numbered(params, prefix) -> list:
    return [params[k] for k in sorted(
        (k for k in params if k.startswith(prefix)),
        key=lambda k: int(k[len(prefix):]))]


def layer_index(params, layer_types) -> list:
    """(stack, row) of every layer, in the model's order: the lead's
    stacks (`lead0`, ...) layer by layer, then, behind the lead, a stack a
    KIND, `run0` and `run1` in the order the kinds first appear there, which
    the model's order interleaves."""
    out = [(stack, i) for stack in _numbered(params, "lead")
           for i in range(len(stack["attn_norm"]))]
    runs = _numbered(params, "run")
    rest = layer_types[len(out):]
    kinds = list(dict.fromkeys(rest))
    assert len(runs) == len(kinds), (len(runs), kinds)
    taken = [0] * len(kinds)
    for kind in rest:
        s = kinds.index(kind)
        out.append((runs[s], taken[s]))
        taken[s] += 1
    return out


def forward(params, tokens, hf: dict, tails: list = None):
    """tokens [T] -> logits [T, V] float32: one full forward pass over one
    sequence, every weight upcast at once (a small model). `tails` (a
    list) takes every conv layer's state after the sequence, in layer
    order."""
    arch = arch_from_hf(hf)
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda a: jnp.asarray(a, F32), params)
        # ids the engine served  # dynalint: disable-next-line=R1
        x = params["embed"][jnp.asarray(tokens)]
        for stack, i in layer_index(params, arch["layer_types"]):
            x = layer(x, {name: leaf[i] for name, leaf in stack.items()},
                      arch, tails)
        x = rms_norm(x, params["final_norm"], arch["rms_norm_eps"])
        head = params["lm_head"] if "lm_head" in params \
            else params["embed"].T
        return x @ head


# -- the same arithmetic in blocks, at the published widths -------------------

EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


def round_to(x, dtype):
    """x rounded to `dtype`'s exponent and mantissa, still float32 (not a
    cast there and back, which XLA may drop)."""
    if dtype is None or jnp.finfo(dtype).bits >= 32:
        return x
    info = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, info.nexp, info.nmant)


def _freeze(d: dict) -> tuple:
    return tuple(sorted(d.items()))


def _cut_group(lp, h, hkv, hd, group):
    """KV head `group`'s columns of Wq, Wk, Wv and its rows of Wo, with
    the query heads that share it (a head's norm has one weight vector for
    all heads, so it is not cut). Outside the jit below, so that one
    compiled program serves every group."""
    g = h // hkv
    d = lp["wq"].shape[0]

    def heads(w, n):        # [D, n x hd] -> this group's columns
        per = g if n == h else 1
        return w.reshape(d, n, hd)[:, group * per:(group + 1) * per] \
            .reshape(d, per * hd)
    return {"wq": heads(lp["wq"], h), "wk": heads(lp["wk"], hkv),
            "wv": heads(lp["wv"], hkv),
            "wo": lp["wo"].reshape(h, hd, -1)[group * g:(group + 1) * g]
            .reshape(g * hd, -1),
            "q_norm": lp["q_norm"], "k_norm": lp["k_norm"]}


@functools.partial(jax.jit, static_argnames=("attn",))
def _attention_group(xn, cut, attn):
    """One KV head of `attention` on the normed input, with the query
    heads that share it: [T, D], summed over the groups by the caller."""
    attn = dict(attn)
    cut = jax.tree.map(lambda a: a.astype(F32), cut)
    g = attn.pop("num_heads") // attn.pop("num_kv_heads")
    return attention(xn, cut, num_heads=g, num_kv_heads=1, **attn)


@functools.partial(jax.jit, static_argnames=("reset_every", "tokens"))
def _short_conv(xn, conv_in, conv_w, wo, reset_every, tokens):
    """`short_conv`, and the tails after `tokens` tokens and after one more
    [2, K - 1, D] (None where `tokens` is)."""
    lp = {"conv_in": conv_in.astype(F32), "conv_w": conv_w.astype(F32)}
    g, c = conv_gate(xn, lp)
    k = conv_w.shape[0]
    tails = None if tokens is None else jnp.stack(
        [tail_of(g, k, n, reset_every) for n in (tokens, tokens + 1)])
    return (c * causal_conv(g, lp["conv_w"], reset_every)) \
        @ wo.astype(F32), tails


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(x, w, eps):
    return rms_norm(x, w.astype(F32), eps)


@functools.partial(jax.jit, static_argnames=("router", "bias_in_weights"))
def _route(xn, w_router, bias, router, bias_in_weights):
    lp = {"router": w_router.astype(F32)}
    if bias is not None:
        lp["router_bias"] = bias.astype(F32)
    return router_weights(xn, lp, bias_in_weights=bias_in_weights,
                          **dict(router))


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
                    expert_block: int = 8, vocab_block: int = 16384,
                    cast=None, state_tokens=None, reset_every: int = 0,
                    bias_in_weights: bool = False, act_dtype=None):
    """`forward`'s arithmetic at the published widths: tokens [T] ->
    log-softmax over the vocabulary, float32, at `positions` (a list of
    row indices; None: every row) -> [len(positions), V]. Attention a KV
    head and its query heads at a time, the experts `expert_block` at a
    time, the head `vocab_block` columns at a time. With `state_tokens`
    (a count of tokens) the result is (that, tails [2, conv layers, K - 1,
    D]): every conv layer's state after that many tokens and after one
    more. The controls, each changing this reference alone
    (checks/reference_logits_lfm2.py CONTROLS): `cast` is applied to every
    weight leaf first (the next lower precision); `reset_every` n loses
    every conv layer's tail at each n-token edge; `bias_in_weights` weighs
    the picked experts with the selection bias too; `act_dtype` rounds the
    activations at the block's joints (the normed inputs, each half's
    output, each residual sum)."""
    arch = arch_from_hf(hf)
    router = _freeze({k: arch[k] for k in ROUTER})
    attn = _freeze({k: arch[k] for k in ATTN})
    cast = cast or (lambda a: a)
    eps, hkv = arch["rms_norm_eps"], arch["num_kv_heads"]

    def rnd(a):
        return round_to(a, act_dtype)
    tails = []
    with jax.default_matmul_precision("highest"):
        # ids the engine served  # dynalint: disable-next-line=R1
        x = cast(params["embed"])[jnp.asarray(tokens)].astype(F32)
        for stack, i in layer_index(params, arch["layer_types"]):
            def leaf(name):
                return cast(stack[name][i])
            xn = rnd(_norm(x, leaf("attn_norm"), eps))
            if "conv_in" in stack:
                out, tail = _short_conv(
                    xn, leaf("conv_in"), leaf("conv_w"), leaf("wo"),
                    reset_every, state_tokens)
                tails.append(tail)
            else:
                lp = {name: leaf(name) for name in (
                    "wq", "wk", "wv", "wo", "q_norm", "k_norm")}
                out = sum(_attention_group(
                    xn, _cut_group(lp, arch["num_heads"], hkv,
                                   arch["head_dim"], group), attn)
                    for group in range(hkv))
            x = rnd(x + rnd(out))
            xn = rnd(_norm(x, leaf("mlp_norm"), eps))
            if "router" not in stack:
                out = _dense(xn, *(leaf(name) for name in EXPERT_LEAVES))
            else:
                weights = _route(
                    xn, leaf("router"), leaf("router_bias")
                    if "router_bias" in stack else None, router,
                    bias_in_weights)
                e = stack["w_gate"].shape[1]
                out = 0.0
                for lo in range(0, e, expert_block):
                    hi = min(e, lo + expert_block)
                    out = out + _expert_block(
                        xn, weights[:, lo:hi],
                        *(cast(stack[name][i, lo:hi])
                          for name in EXPERT_LEAVES))
            x = rnd(x + rnd(out))
        if positions is not None:
            x = x[jnp.asarray(positions)]
        x = rnd(_norm(x, cast(params["final_norm"]), eps))
        if "lm_head" in params:
            width = params["lm_head"].shape[1]

            def head(lo):
                return params["lm_head"][:, lo:lo + vocab_block]
        else:       # tied: a block of the table's rows, transposed
            width = params["embed"].shape[0]

            def head(lo):
                return params["embed"][lo:lo + vocab_block].T
        logits = jnp.concatenate(
            [_logits_block(x, cast(head(lo)))
             for lo in range(0, width, vocab_block)], axis=1)
        logp = jax.nn.log_softmax(logits, axis=-1)
    if state_tokens is None:
        return logp
    return logp, jnp.stack(tails, axis=1)
