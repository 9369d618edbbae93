"""The benchmark's own copy of the plain reference for OLMoE
(`OlmoeForCausalLM`): the forward pass in straightforward `jax.numpy`,
float32, matmuls at `jax.default_matmul_precision("highest")`. No cache,
no batching, no kernels. It imports nothing from `dynamo_tpu`: what the
served path is compared with (checks/reference_logits.py) is kept with the
benchmark, so no PR that changes the program changes the yardstick. The
functions down to `layer` are dynamo_tpu/models/reference.py's, line for
line (tests/test_olmoe.py and benchmark/tests/test_olmoe_cell.py hold the
two to identical logits); that file's docstring has the layer equations
and the departures from the published model. What is added here is
`forward_blocked`, which does the same arithmetic at the published widths
on the chip without holding a float32 copy of a whole layer's experts:
the experts are upcast a block at a time.

Weights come in the engine's layout: projections [in, out], stacked over
layers on a leading axis, experts on the next.
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


def arch_from_hf(hf: dict) -> dict:
    """`layer`'s keyword arguments from a published config.json."""
    heads = hf["num_attention_heads"]
    olmoe = (hf.get("architectures") or [""])[0] == "OlmoeForCausalLM"
    return dict(num_heads=heads,
                num_kv_heads=hf.get("num_key_value_heads", heads),
                head_dim=hf.get("head_dim") or hf["hidden_size"] // heads,
                rope_theta=float(hf.get("rope_theta", 10000.0)),
                rms_norm_eps=float(hf.get("rms_norm_eps", 1e-5)),
                qk_norm=olmoe,
                num_experts=int(hf.get("num_experts", 0)),
                num_experts_per_tok=int(hf.get("num_experts_per_tok", 0)),
                norm_topk_prob=bool(hf.get("norm_topk_prob", False)))


def forward(params, tokens, hf: dict):
    """tokens [T] -> logits [T, V] float32: one full forward pass over one
    sequence, every weight upcast at once (a small model)."""
    arch = arch_from_hf(hf)
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


EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


@functools.partial(jax.jit, static_argnames=("arch",))
def _attention_and_route(x, lp, arch):
    """The block's attention half and the router: (x after attention,
    the normed input of the experts, the [T, E] weights)."""
    arch = dict(arch)
    lp = jax.tree.map(lambda a: a.astype(F32), lp)
    x = x + attention(
        rms_norm(x, lp["attn_norm"], arch["rms_norm_eps"]), lp,
        num_heads=arch["num_heads"], num_kv_heads=arch["num_kv_heads"],
        head_dim=arch["head_dim"], rope_theta=arch["rope_theta"],
        rms_norm_eps=arch["rms_norm_eps"], qk_norm=arch["qk_norm"])
    xn = rms_norm(x, lp["mlp_norm"], arch["rms_norm_eps"])
    probs = jax.nn.softmax(xn @ lp["router"], axis=-1)
    _, chosen = jax.lax.top_k(probs, arch["num_experts_per_tok"])
    mask = jnp.sum(jax.nn.one_hot(chosen, probs.shape[-1], dtype=F32), 1)
    weights = probs * mask
    if arch["norm_topk_prob"]:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return x, xn, weights


@jax.jit
def _expert_block(xn, weights, w_gate, w_up, w_down):
    """A block of experts on every token, weighted: [T, D]."""
    w_gate, w_up, w_down = (w.astype(F32) for w in (w_gate, w_up, w_down))
    hidden = (jax.nn.silu(jnp.einsum("td,edf->etf", xn, w_gate))
              * jnp.einsum("td,edf->etf", xn, w_up))
    y = jnp.einsum("etf,efd->etd", hidden, w_down)
    return jnp.einsum("te,etd->td", weights, y)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, head, eps):
    return jax.nn.log_softmax(
        rms_norm(x, final_norm.astype(F32), eps) @ head.astype(F32), -1)


def forward_blocked(params, tokens, hf: dict, expert_block: int = 16,
                    cast=None):
    """`forward`'s arithmetic for an expert model at the published widths:
    tokens [T] -> log-softmax over the vocabulary [T, V] float32. Weights
    stay in their stored dtype on the device and are upcast inside each
    jitted piece, the experts `expert_block` at a time (the float32 copy
    of one OLMoE layer's experts alone is 1.6 GB). `cast`, if given, is
    applied to every weight leaf first (checks/reference_logits.py uses it
    to show what the reference reads in the next lower precision)."""
    arch = arch_from_hf(hf)
    frozen = tuple(sorted(arch.items()))
    cast = cast or (lambda a: a)
    layers = params["layers"]
    e = arch["num_experts"]
    with jax.default_matmul_precision("highest"):
        # ids the engine served  # dynalint: disable-next-line=R1
        x = cast(params["embed"])[jnp.asarray(tokens)].astype(F32)
        for i in range(layers["wq"].shape[0]):
            lp = {name: cast(leaf[i]) for name, leaf in layers.items()
                  if name not in EXPERT_LEAVES}
            x, xn, weights = _attention_and_route(x, lp, frozen)
            for lo in range(0, e, expert_block):
                hi = min(e, lo + expert_block)
                x = x + _expert_block(
                    xn, weights[:, lo:hi],
                    *(cast(layers[name][i, lo:hi])
                      for name in EXPERT_LEAVES))
        head = params["lm_head"] if "lm_head" in params \
            else params["embed"].T
        return _head(x, cast(params["final_norm"]), cast(head),
                     arch["rms_norm_eps"])
