"""The benchmark's own copy of the plain reference for the `afmoe` family
(Trinity-Mini): the lines of dynamo_tpu/models/reference.py that this model
reads, with NO import from `dynamo_tpu` (a tier-1 test,
benchmark/tests/test_trinity_cell.py, holds the two to identical logits),
and `forward_blocked`, the same arithmetic a block of heads, of experts and
of the vocabulary at a time, for the published widths on a chip that also
holds the served model.

Plain float32 `jax.numpy` at `highest` matmul precision: no cache, no
paging, no batching, no kernels, one sequence in, logits at every position
out. Written from the row `Trinity-Mini` of the architecture catalog
(`model_type` afmoe) and, where no key of the row's config speaks, from the
family's published modelling code as ISSUE 40 gives it (marked
"code-sourced"; `transformers` 4.57.6 here has no afmoe class and there is
no network). Plain RMSNorm (w * x_hat, eps 1e-5), final norm, untied head,
no biases.

  embedding   h0 = E[ids] * sqrt(hidden_size) (`mup_enabled`).
  attention   (every layer) q = x Wq -> [T, 32, 128], k = x Wk, v = x Wv ->
              [T, 4, 128], g = x Wg [T, 4096]. RMSNorm over EACH head's 128
              values of q and of k, one weight vector for all heads
              (code-sourced), before RoPE. 8 query heads a KV head; causal
              softmax in float32 at 128 ** -0.5; the output times
              sigmoid(g), element-wise, before Wo (code-sourced).
  sliding_attention layers (`layer_types[i]`): rotate-half RoPE over the
              full head at `rope_theta` on q and k; a query at p sees keys
              j with p - sliding_window < j <= p.
  full_attention layers: NO positional embedding (code-sourced); all keys
              j <= p.
  block       four norms (code-sourced): h = h + RMSNorm(attention(
              RMSNorm(h; attn_norm)); post_attn_norm); h = h +
              RMSNorm(mlp(RMSNorm(h; mlp_norm)); post_mlp_norm).
  layer 0..   (`num_dense_layers` layers) a dense SwiGLU of
              `intermediate_size`.
  the others  s = sigmoid(x Wr) in float32 over all experts; idx =
              top_k(s + b), b the selection bias, which picks and does not
              weigh (code-sourced); w = s[idx] / (sum s[idx] + 1e-20)
              (`route_norm`) x `route_scale`; y = sum_i w_i E_idx_i(x) +
              S(x), E a SwiGLU of `moe_intermediate_size`, S ONE SwiGLU of
              `num_shared_experts` x that on every token. Every expert on
              every token, masked: nothing dropped. `n_group` 1: no group
              limit.

Weights are read in the engine's layout: projections [in, out]; the lead's
layers in stacks of their own (`lead0`, ...: a run of like kinds each, in
layer order), then a stack a layer KIND (`run0`, `run1`: all the sliding
layers behind the lead, all the full ones, in the order the kinds first
appear there), experts on the next axis.
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
    """Rotate-half RoPE over the full head. x: [T, H, hd]. `theta` None: a
    layer kind without a positional embedding, x as it is."""
    if theta is None:
        return x
    hd = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    angle = positions.astype(F32)[:, None] * inv_freq[None, :]  # [T, hd/2]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(x, lp, *, num_heads, num_kv_heads, head_dim, rope_theta,
              rms_norm_eps, window=0):
    """`window` > 0: a query at p sees keys j with p - window < j <= p.
    `rope_theta` None: no rotation."""
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
    if window:
        causal &= positions[:, None] - positions[None, :] < window
    scores = jnp.where(causal[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("hqk,khd->qhd", probs, v)
    out = out.reshape(t, num_heads * head_dim)
    out = out * jax.nn.sigmoid(x @ lp["w_out_gate"])      # the output gate
    return out @ lp["wo"]


def dense_mlp(x, lp, names=("w_gate", "w_up", "w_down")):
    gate, up, down = (lp[name] for name in names)
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def router_weights(x, lp, *, num_experts_per_tok, norm_topk_prob,
                   moe_scoring, moe_routed_scale):
    """[T, E] float32: each token's weight on every expert, zero outside
    its top-k. The `router_bias` leaf picks and does not weigh."""
    logits = x @ lp["router"]                                  # [T, E]
    scores = (jax.nn.sigmoid(logits) if moe_scoring == "sigmoid"
              else jax.nn.softmax(logits, axis=-1))
    _, chosen = jax.lax.top_k(scores + lp["router_bias"],
                              num_experts_per_tok)             # [T, k]
    mask = jnp.sum(jax.nn.one_hot(chosen, scores.shape[-1], dtype=F32), 1)
    weights = scores * mask
    if norm_topk_prob:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                             + 1e-20)
    return weights * moe_routed_scale


def expert_mlp(x, lp, **router):
    """Every expert on every token, masked by the top-k; plus the shared
    expert."""
    weights = router_weights(x, lp, **router)
    hidden = (jax.nn.silu(jnp.einsum("td,edf->etf", x, lp["w_gate"]))
              * jnp.einsum("td,edf->etf", x, lp["w_up"]))
    y = jnp.einsum("etf,efd->etd", hidden, lp["w_down"])       # [E, T, D]
    y = jnp.einsum("te,etd->td", weights, y)
    return y + dense_mlp(x, lp, ("ws_gate", "ws_up", "ws_down"))


ROUTER = ("num_experts_per_tok", "norm_topk_prob", "moe_scoring",
          "moe_routed_scale")


def layer(x, lp, *, num_heads, num_kv_heads, head_dim, rms_norm_eps,
          rope_theta, window, **router):
    """One residual block of four norms. x: [T, D]; lp: this layer's
    weights, float32; `window`, `rope_theta`: THIS layer's, by its kind. A
    layer without a `router` leaf has a dense MLP (the lead)."""
    xn = rms_norm(x, lp["attn_norm"], rms_norm_eps)
    out = attention(xn, lp, num_heads=num_heads, num_kv_heads=num_kv_heads,
                    head_dim=head_dim, rope_theta=rope_theta,
                    rms_norm_eps=rms_norm_eps, window=window)
    x = x + rms_norm(out, lp["post_attn_norm"], rms_norm_eps)
    xn = rms_norm(x, lp["mlp_norm"], rms_norm_eps)
    out = expert_mlp(xn, lp, **router) if "router" in lp \
        else dense_mlp(xn, lp)
    return x + rms_norm(out, lp["post_mlp_norm"], rms_norm_eps)


def arch_from_hf(hf: dict) -> dict:
    """`layer`'s keyword arguments from the config.json, and what goes by
    layer kind or stands outside the layers (`OUTSIDE`)."""
    for key in ("n_group", "num_expert_groups", "topk_group",
                "num_limited_groups"):
        if hf.get(key) not in (None, 1):
            raise ValueError(f"{key}={hf[key]!r}: one expert group is what "
                             f"is modelled")
    if hf.get("rope_scaling"):
        raise ValueError("rope_scaling is not modelled")
    return dict(
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf["num_key_value_heads"],
        head_dim=int(hf["head_dim"]),
        rms_norm_eps=float(hf.get("rms_norm_eps", 1e-5)),
        num_experts_per_tok=int(hf["num_experts_per_tok"]),
        norm_topk_prob=bool(hf.get("route_norm", True)),
        moe_scoring=hf["score_func"],
        moe_routed_scale=float(hf.get("route_scale", 1.0)),
        layer_types=tuple(hf["layer_types"]),
        sliding_window=int(hf["sliding_window"]),
        rope_theta=float(hf.get("rope_theta", 10000.0)),
        embed_scale=float(hf["hidden_size"]) ** 0.5
        if hf.get("mup_enabled") else 0.0)


OUTSIDE = ("layer_types", "sliding_window", "rope_theta", "embed_scale")


def layer_kind_kwargs(index, layer_types, sliding_window, rope_theta,
                      **_) -> dict:
    """`layer`'s arguments that go by layer KIND, for layer `index`: its
    window (0 on a full layer) and its RoPE (None on a full layer)."""
    sliding = layer_types[index] == "sliding_attention"
    return dict(window=sliding_window if sliding else 0,
                rope_theta=rope_theta if sliding else None)


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


def forward(params, tokens, hf: dict):
    """tokens [T] -> logits [T, V] float32: one full forward pass over one
    sequence, every weight upcast at once (a small model)."""
    arch = arch_from_hf(hf)
    outside = {k: arch.pop(k) for k in OUTSIDE}
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda a: jnp.asarray(a, F32), params)
        # ids the engine served  # dynalint: disable-next-line=R1
        x = params["embed"][jnp.asarray(tokens)]
        if outside["embed_scale"]:
            x = x * outside["embed_scale"]
        for index, (stack, i) in enumerate(layer_index(
                params, outside["layer_types"])):
            lp = {name: leaf[i] for name, leaf in stack.items()}
            x = layer(x, lp, **arch, **layer_kind_kwargs(index, **outside))
        x = rms_norm(x, params["final_norm"], arch["rms_norm_eps"])
        return x @ params["lm_head"]


EXPERT_LEAVES = ("w_gate", "w_up", "w_down")
SHARED_LEAVES = ("ws_gate", "ws_up", "ws_down")
ATTN_LEAVES = ("attn_norm", "wq", "wk", "wv", "wo", "q_norm", "k_norm",
               "w_out_gate")


def _freeze(d: dict) -> tuple:
    return tuple(sorted(d.items()))


def _cut_group(lp, h, hkv, hd, group):
    """KV head `group`'s columns of Wq, Wk, Wv and the gate and its rows of
    Wo, with the query heads that share it (a head's norm has one weight
    vector for all heads, so it is not cut). Outside the jit below, so
    that one compiled program serves every group."""
    g = h // hkv
    d = lp["wq"].shape[0]

    def heads(w, n):        # [D, n x hd] -> this group's columns
        per = g if n == h else 1
        return w.reshape(d, n, hd)[:, group * per:(group + 1) * per] \
            .reshape(d, per * hd)
    return {"wq": heads(lp["wq"], h), "wk": heads(lp["wk"], hkv),
            "wv": heads(lp["wv"], hkv),
            "w_out_gate": heads(lp["w_out_gate"], h),
            "wo": lp["wo"].reshape(h, hd, -1)[group * g:(group + 1) * g]
            .reshape(g * hd, -1),
            "q_norm": lp["q_norm"], "k_norm": lp["k_norm"]}


@functools.partial(jax.jit, static_argnames=("attn",))
def _attention_group(xn, cut, attn):
    """One KV head of `attention` on the normed input, with the query
    heads that share it (`_cut_group`): [T, D], summed over the groups by
    the caller. The function's own lines with the head axes cut."""
    attn = dict(attn)
    cut = jax.tree.map(lambda a: a.astype(F32), cut)
    g = attn.pop("num_heads") // attn.pop("num_kv_heads")
    return attention(xn, cut, num_heads=g, num_kv_heads=1, **attn)


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(x, w, eps):
    return rms_norm(x, w.astype(F32), eps)


@functools.partial(jax.jit, static_argnames=("router",))
def _route(xn, w_router, bias, router):
    return router_weights(xn, {"router": w_router.astype(F32),
                               "router_bias": bias.astype(F32)},
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
                    cast=None):
    """`forward`'s arithmetic at the published widths: tokens [T] ->
    log-softmax over the vocabulary, float32, at `positions` (a list of
    row indices; None: every row) -> [len(positions), V]. Attention a KV
    head and its query heads at a time, the experts `expert_block` at a
    time, the head `vocab_block` columns at a time. `cast`, if given, is
    applied to every weight leaf first (checks/reference_logits_trinity.py
    uses it to show what the reference reads in the next lower
    precision)."""
    arch = arch_from_hf(hf)
    outside = {k: arch.pop(k) for k in OUTSIDE}
    router = _freeze({k: arch[k] for k in ROUTER})
    cast = cast or (lambda a: a)
    eps, hkv = arch["rms_norm_eps"], arch["num_kv_heads"]
    with jax.default_matmul_precision("highest"):
        # ids the engine served  # dynalint: disable-next-line=R1
        x = cast(params["embed"])[jnp.asarray(tokens)].astype(F32)
        if outside["embed_scale"]:
            x = x * outside["embed_scale"]
        for index, (stack, i) in enumerate(layer_index(
                params, outside["layer_types"])):
            attn = _freeze({
                **{k: arch[k] for k in ("num_heads", "num_kv_heads",
                                        "head_dim", "rms_norm_eps")},
                **layer_kind_kwargs(index, **outside)})

            def leaf(name):
                return cast(stack[name][i])
            lp = {name: leaf(name) for name in ATTN_LEAVES}
            xn = _norm(x, lp.pop("attn_norm"), eps)
            out = sum(_attention_group(
                xn, _cut_group(lp, arch["num_heads"], hkv,
                               arch["head_dim"], group), attn)
                for group in range(hkv))
            x = x + _norm(out, leaf("post_attn_norm"), eps)
            xn = _norm(x, leaf("mlp_norm"), eps)
            if "router" not in stack:
                out = _dense(xn, *(leaf(name) for name in EXPERT_LEAVES))
            else:
                weights = _route(xn, leaf("router"), leaf("router_bias"),
                                 router)
                out = _dense(xn, *(leaf(name) for name in SHARED_LEAVES))
                e = stack["w_gate"].shape[1]
                for lo in range(0, e, expert_block):
                    hi = min(e, lo + expert_block)
                    out = out + _expert_block(
                        xn, weights[:, lo:hi],
                        *(cast(stack[name][i, lo:hi])
                          for name in EXPERT_LEAVES))
            x = x + _norm(out, leaf("post_mlp_norm"), eps)
        if positions is not None:
            x = x[jnp.asarray(positions)]
        x = _norm(x, cast(params["final_norm"]), eps)
        head = params["lm_head"]
        logits = jnp.concatenate(
            [_logits_block(x, cast(head[:, lo:lo + vocab_block]))
             for lo in range(0, head.shape[1], vocab_block)], axis=1)
        return jax.nn.log_softmax(logits, axis=-1)
