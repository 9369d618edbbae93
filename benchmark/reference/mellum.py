"""The benchmark's own copy of the plain reference for the `mellum` family
(Mellum2-12B-A2.5B): the lines of dynamo_tpu/models/reference.py that this
model reads, with NO import from `dynamo_tpu` (a tier-1 test,
benchmark/tests/test_mellum_cell.py, holds the two to identical logits),
and `forward_blocked`, the same arithmetic a block of heads, of experts and
of the vocabulary at a time, for the published widths on a chip that also
holds the served model.

Plain float32 `jax.numpy` at `highest` matmul precision: no cache, no
paging, no batching, no kernels, one sequence in, logits at every position
out. Written from the row `Mellum2-12B-A2.5B-Instruct` of the architecture
catalog (`model_type` mellum). Pre-norm residual block, plain RMSNorm
(eps 1e-6), final norm, untied head, no biases.

  attention   (every layer) q = x Wq -> [T, 32, 128], k = x Wk, v = x Wv ->
              [T, 4, 128]; rotate-half RoPE over the full head on q and k
              with THIS LAYER KIND's table; 8 query heads a KV head; causal
              softmax in float32 at 128 ** -0.5; Wo.
  sliding_attention layers (`layer_types[i]`): a query at p sees keys j
              with p - sliding_window < j <= p. RoPE:
              `rope_parameters.sliding_attention`, plain.
  full_attention layers: all keys j <= p. RoPE:
              `rope_parameters.full_attention`, YaRN as `transformers`
              computes it (`yarn_inv_freq`); cos and sin are multiplied by
              `attention_factor` at every position.
  experts     (every layer) p = softmax(x W_router) in float32 over all
              experts; the `num_experts_per_tok` largest, renormalised to
              sum to one; y = sum_i p_i W_down,i(silu(W_gate,i x) * W_up,i
              x). Every expert on every token, masked: nothing dropped.

Assumed, and said in the configuration's meta.json: the class name
(`MellumForCausalLM`; the catalog gives none); no QK-norm (no key declares
one); no multi-token-prediction head (`described_as` mentions one that no
key of `config` describes); `max_window_layers: 0` beside an explicit
`layer_types`: `layer_types` governs; `intermediate_size` is unused where
every layer is sparse. Weights are read in the engine's layout:
projections [in, out], a stack a layer KIND (`run0`, `run1`: all the
sliding layers, all the full ones, in the order the kinds first appear),
experts on the next axis.
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
    """YaRN's frequencies [dim / 2]: f_i = theta ** (2i / dim); the
    extrapolated 1 / f_i and the interpolated 1 / (factor f_i) blend by a
    linear ramp over i between low = floor(c(beta_fast)) and high =
    ceil(c(beta_slow)), c(r) = dim ln(L0 / (2 pi r)) / (2 ln theta),
    clipped to [0, dim - 1]."""
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
    (0: 0.1 ln(factor) + 1), which multiplies cos and sin."""
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
              window=0, yarn=None):
    """`window` > 0: a query at p sees keys j with p - window < j <= p."""
    t = x.shape[0]
    q, k, v = x @ lp["wq"], x @ lp["wk"], x @ lp["wv"]
    positions = jnp.arange(t)
    q = rope(q.reshape(t, num_heads, head_dim), positions, rope_theta, yarn)
    k = rope(k.reshape(t, num_kv_heads, head_dim), positions, rope_theta,
             yarn)
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
    return out.reshape(t, num_heads * head_dim) @ lp["wo"]


def router_weights(x, lp, *, num_experts_per_tok, norm_topk_prob=True):
    """[T, E] float32: each token's weight on every expert, zero outside
    its top-k."""
    scores = jax.nn.softmax(x @ lp["router"], axis=-1)         # [T, E]
    _, chosen = jax.lax.top_k(scores, num_experts_per_tok)     # [T, k]
    mask = jnp.sum(jax.nn.one_hot(chosen, scores.shape[-1], dtype=F32), 1)
    weights = scores * mask
    if norm_topk_prob:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                             + 1e-20)
    return weights


def expert_mlp(x, lp, **router):
    """Every expert on every token, masked by the top-k."""
    weights = router_weights(x, lp, **router)
    hidden = (jax.nn.silu(jnp.einsum("td,edf->etf", x, lp["w_gate"]))
              * jnp.einsum("td,edf->etf", x, lp["w_up"]))
    y = jnp.einsum("etf,efd->etd", hidden, lp["w_down"])       # [E, T, D]
    return jnp.einsum("te,etd->td", weights, y)


def layer(x, lp, *, num_heads, num_kv_heads, head_dim, rope_theta,
          rms_norm_eps, num_experts_per_tok, norm_topk_prob=True, window=0,
          yarn=None):
    """One pre-norm residual block. x: [T, D]; lp: this layer's weights,
    float32; `window`, `yarn`, `rope_theta`: THIS layer's, by its kind."""
    xn = rms_norm(x, lp["attn_norm"], rms_norm_eps)
    x = x + attention(xn, lp, num_heads=num_heads, num_kv_heads=num_kv_heads,
                      head_dim=head_dim, rope_theta=rope_theta,
                      window=window, yarn=yarn)
    xn = rms_norm(x, lp["mlp_norm"], rms_norm_eps)
    return x + expert_mlp(xn, lp, num_experts_per_tok=num_experts_per_tok,
                          norm_topk_prob=norm_topk_prob)


def rope_entry(entry: dict, hf: dict) -> dict:
    """One entry of the file's `rope_parameters`, under this file's
    names."""
    kind = entry.get("rope_type", "default")
    out = {"rope_type": kind, "theta": float(entry["rope_theta"])}
    if kind == "yarn":
        out.update(
            factor=float(entry["factor"]),
            original_max_position=int(
                entry.get("original_max_position_embeddings")
                or hf["max_position_embeddings"]),
            beta_fast=float(entry.get("beta_fast") or 32.0),
            beta_slow=float(entry.get("beta_slow") or 1.0),
            attention_factor=float(entry.get("attention_factor") or 0.0))
    elif kind != "default":
        raise ValueError(f"rope_type {kind!r} is not modelled")
    return out


def arch_from_hf(hf: dict) -> dict:
    """`layer`'s keyword arguments from the config.json, and what goes by
    layer kind (`layer_kind_kwargs`)."""
    ropes = hf["rope_parameters"]
    return dict(
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf["num_key_value_heads"],
        head_dim=int(hf["head_dim"]),
        rms_norm_eps=float(hf.get("rms_norm_eps", 1e-6)),
        num_experts_per_tok=int(hf["num_experts_per_tok"]),
        norm_topk_prob=bool(hf.get("norm_topk_prob", True)),
        layer_types=tuple(hf["layer_types"]),
        sliding_window=int(hf["sliding_window"]),
        rope_full=rope_entry(ropes["full_attention"], hf),
        rope_sliding=rope_entry(ropes["sliding_attention"], hf))


BY_KIND = ("layer_types", "sliding_window", "rope_full", "rope_sliding")


def layer_kind_kwargs(index, layer_types, sliding_window, rope_full,
                      rope_sliding) -> dict:
    """`layer`'s arguments that go by layer KIND, for layer `index`: its
    window (0 on a full layer) and its RoPE."""
    sliding = layer_types[index] == "sliding_attention"
    p = rope_sliding if sliding else rope_full
    return dict(window=sliding_window if sliding else 0,
                rope_theta=p["theta"],
                yarn=p if p["rope_type"] == "yarn" else None)


def layer_index(params, layer_types) -> list:
    """(stack, row) of every layer, in the model's order. The engine keeps
    a stack a KIND, `run0` and `run1` in the order the kinds first appear
    in `layer_types`, and the model's order interleaves them."""
    runs = sorted((k for k in params if k.startswith("run")),
                  key=lambda k: int(k[3:]))
    kinds = list(dict.fromkeys(layer_types))
    assert len(runs) == len(kinds), (runs, kinds)
    taken = [0] * len(kinds)
    out = []
    for kind in layer_types:
        s = kinds.index(kind)
        out.append((params[runs[s]], taken[s]))
        taken[s] += 1
    return out


def forward(params, tokens, hf: dict):
    """tokens [T] -> logits [T, V] float32: one full forward pass over one
    sequence, every weight upcast at once (a small model)."""
    arch = arch_from_hf(hf)
    by_kind = {k: arch.pop(k) for k in BY_KIND}
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda a: jnp.asarray(a, F32), params)
        # ids the engine served  # dynalint: disable-next-line=R1
        x = params["embed"][jnp.asarray(tokens)]
        for index, (stack, i) in enumerate(layer_index(
                params, by_kind["layer_types"])):
            lp = {name: leaf[i] for name, leaf in stack.items()}
            x = layer(x, lp, **arch, **layer_kind_kwargs(index, **by_kind))
        x = rms_norm(x, params["final_norm"], arch["rms_norm_eps"])
        return x @ params["lm_head"]


EXPERT_LEAVES = ("w_gate", "w_up", "w_down")
ATTN_LEAVES = ("attn_norm", "wq", "wk", "wv", "wo")


def _freeze(d: dict) -> tuple:
    return tuple(sorted((k, _freeze(v) if isinstance(v, dict) else v)
                        for k, v in d.items()))


def _thaw(t: tuple) -> dict:
    return {k: _thaw(v) if isinstance(v, tuple) and v
            and isinstance(v[0], tuple) else v for k, v in t}


@functools.partial(jax.jit, static_argnames=("attn", "group"))
def _attention_group(xn, lp, attn, group):
    """KV head `group` of `attention` on the normed input, with the query
    heads that share it, through their rows of Wo: [T, D], summed over the
    groups by the caller. The function's own lines with the head axes
    cut."""
    attn = _thaw(attn)
    lp = jax.tree.map(lambda a: a.astype(F32), lp)
    h, hkv, hd = attn.pop("num_heads"), attn.pop("num_kv_heads"), \
        attn["head_dim"]
    g = h // hkv
    d = lp["wq"].shape[0]
    cut = {"wq": lp["wq"].reshape(d, h, hd)[:, group * g:(group + 1) * g]
           .reshape(d, g * hd),
           "wk": lp["wk"].reshape(d, hkv, hd)[:, group],
           "wv": lp["wv"].reshape(d, hkv, hd)[:, group],
           "wo": lp["wo"].reshape(h, hd, -1)[group * g:(group + 1) * g]
           .reshape(g * hd, -1)}
    return attention(xn, cut, num_heads=g, num_kv_heads=1, **attn)


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(x, w, eps):
    return rms_norm(x, w.astype(F32), eps)


@functools.partial(jax.jit, static_argnames=("router",))
def _route(xn, w_router, router):
    return router_weights(xn, {"router": w_router.astype(F32)},
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
    applied to every weight leaf first (checks/reference_logits_mellum.py
    uses it to show what the reference reads in the next lower
    precision)."""
    arch = arch_from_hf(hf)
    by_kind = {k: arch.pop(k) for k in BY_KIND}
    router = _freeze({k: arch[k] for k in ("num_experts_per_tok",
                                           "norm_topk_prob")})
    cast = cast or (lambda a: a)
    eps, hkv = arch["rms_norm_eps"], arch["num_kv_heads"]
    with jax.default_matmul_precision("highest"):
        # ids the engine served  # dynalint: disable-next-line=R1
        x = cast(params["embed"])[jnp.asarray(tokens)].astype(F32)
        for index, (stack, i) in enumerate(layer_index(
                params, by_kind["layer_types"])):
            kind = layer_kind_kwargs(index, **by_kind)
            attn = _freeze({
                **{k: arch[k] for k in ("num_heads", "num_kv_heads",
                                        "head_dim")}, **kind})
            lp = {name: cast(stack[name][i]) for name in ATTN_LEAVES}
            xn = _norm(x, lp.pop("attn_norm"), eps)
            for group in range(hkv):
                x = x + _attention_group(xn, lp, attn, group)
            xn = _norm(x, cast(stack["mlp_norm"][i]), eps)
            weights = _route(xn, cast(stack["router"][i]), router)
            e = stack["w_gate"].shape[1]
            for lo in range(0, e, expert_block):
                hi = min(e, lo + expert_block)
                x = x + _expert_block(
                    xn, weights[:, lo:hi],
                    *(cast(stack[name][i, lo:hi])
                      for name in EXPERT_LEAVES))
        if positions is not None:
            x = x[jnp.asarray(positions)]
        x = _norm(x, cast(params["final_norm"]), eps)
        head = params["lm_head"]
        logits = jnp.concatenate(
            [_logits_block(x, cast(head[:, lo:lo + vocab_block]))
             for lo in range(0, head.shape[1], vocab_block)], axis=1)
        return jax.nn.log_softmax(logits, axis=-1)
