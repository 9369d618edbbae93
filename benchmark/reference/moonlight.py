"""The benchmark's own copy of the plain reference for Moonlight-16B-A3B
(`DeepseekV3ForCausalLM`): the forward pass in straightforward `jax.numpy`,
float32, matmuls at `jax.default_matmul_precision("highest")`. No cache, no
batching, no kernels; attention in the EXPANDED form (per-head keys and
values rebuilt from the latent), where the served path runs the absorbed
form over its one-leaf cache. It imports nothing from `dynamo_tpu`: what
the served path is compared with (checks/reference_logits_moonlight.py) is
kept with the benchmark, so no PR that changes the program changes the
yardstick. The functions down to `layer` are dynamo_tpu/models/
reference.py's, line for line (tests/test_moonlight.py and
benchmark/tests/test_moonlight_cell.py hold the two to identical logits);
that file's docstring has the layer equations and the departures from the
published model. What is added here is `forward_blocked`, which does the
same arithmetic at the published widths on the chip beside the served
model: weights stay in their stored dtype and are upcast inside each
jitted piece, the experts a block at a time, attention a block of heads at
a time, and the head and the log-softmax only at the positions asked for
(`[3320, 163840]` float32 would be 2.2 GB).

Weights come in the engine's layout: projections [in, out], stacked over
layers on a leading axis (`dense_layers` then `layers`), experts on the
next; the rope columns of Wq / Wkv_a de-interleaved (models/loader.py).
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


def arch_from_hf(hf: dict) -> dict:
    """`layer`'s keyword arguments from a published config.json."""
    return dict(
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf["num_attention_heads"],
        head_dim=int(hf["v_head_dim"]),
        rope_theta=float(hf.get("rope_theta", 10000.0)),
        rms_norm_eps=float(hf.get("rms_norm_eps", 1e-6)),
        num_experts=int(hf["n_routed_experts"]),
        num_experts_per_tok=int(hf["num_experts_per_tok"]),
        norm_topk_prob=bool(hf.get("norm_topk_prob", False)),
        mla=dict(kv_lora_rank=int(hf["kv_lora_rank"]),
                 qk_nope_head_dim=int(hf["qk_nope_head_dim"]),
                 qk_rope_head_dim=int(hf["qk_rope_head_dim"])),
        moe_scoring=hf["scoring_func"],
        moe_routed_scale=float(hf.get("routed_scaling_factor", 1.0)))


LAYER_GROUPS = ("dense_layers", "layers")   # in the model's layer order


def forward(params, tokens, hf: dict):
    """tokens [T] -> logits [T, V] float32: one full forward pass over one
    sequence, every weight upcast at once (a small model)."""
    arch = arch_from_hf(hf)
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
        return x @ params["lm_head"]


EXPERT_LEAVES = ("w_gate", "w_up", "w_down")
SHARED_LEAVES = ("ws_gate", "ws_up", "ws_down")
ATTN_LEAVES = ("attn_norm", "wq", "wkv_a", "kv_a_norm", "wkv_b")


@functools.partial(jax.jit, static_argnames=("arch", "lo", "hi"))
def _attention_heads(xn, lp, arch, lo, hi):
    """Heads lo..hi of `attention_mla` on the normed input, through their
    rows of Wo: [T, D], summed over head blocks by the caller. The
    function's own lines with the head axis cut; the latent and the
    shared rotated key are recomputed a block (small)."""
    arch = dict(arch)
    mla = dict(arch["mla"])
    lp = jax.tree.map(lambda a: a.astype(F32), lp)
    h, dv = arch["num_heads"], arch["head_dim"]
    r, dn, dr = (mla["kv_lora_rank"], mla["qk_nope_head_dim"],
                 mla["qk_rope_head_dim"])
    cut = dict(lp)
    cut["wq"] = lp["wq"].reshape(-1, h, dn + dr)[:, lo:hi].reshape(
        lp["wq"].shape[0], -1)
    cut["wkv_b"] = lp["wkv_b"].reshape(r, h, dn + dv)[:, lo:hi].reshape(
        r, -1)
    cut["wo"] = lp["wo"].reshape(h, dv, -1)[lo:hi].reshape(
        (hi - lo) * dv, -1)
    return attention_mla(xn, cut, num_heads=hi - lo, head_dim=dv,
                         rope_theta=arch["rope_theta"],
                         rms_norm_eps=arch["rms_norm_eps"], **mla)


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


def _freeze(d: dict) -> tuple:
    return tuple(sorted((k, _freeze(v) if isinstance(v, dict) else v)
                        for k, v in d.items()))


def forward_blocked(params, tokens, hf: dict, positions=None,
                    expert_block: int = 16, head_block: int = 4,
                    vocab_block: int = 16384, cast=None):
    """`forward`'s arithmetic at the published widths: tokens [T] ->
    log-softmax over the vocabulary, float32, at `positions` (a list of
    row indices; None: every row) -> [len(positions), V]. `cast`, if
    given, is applied to every weight leaf first
    (checks/reference_logits_moonlight.py uses it to show what the
    reference reads in the next lower precision)."""
    arch = arch_from_hf(hf)
    frozen = _freeze(arch)
    router = _freeze({k: arch[k] for k in (
        "num_experts_per_tok", "norm_topk_prob", "moe_scoring",
        "moe_routed_scale")})
    cast = cast or (lambda a: a)
    eps, heads, e = arch["rms_norm_eps"], arch["num_heads"], arch["num_experts"]
    with jax.default_matmul_precision("highest"):
        # ids the engine served  # dynalint: disable-next-line=R1
        x = cast(params["embed"])[jnp.asarray(tokens)].astype(F32)
        for group in LAYER_GROUPS:
            stack = params.get(group, {"wq": ()})
            for i in range(len(stack["wq"])):
                lp = {name: cast(stack[name][i])
                      for name in ATTN_LEAVES + ("wo",)}
                xn = _norm(x, lp["attn_norm"], eps)
                for lo in range(0, heads, head_block):
                    x = x + _attention_heads(
                        xn, lp, frozen, lo, min(heads, lo + head_block))
                xn = _norm(x, cast(stack["mlp_norm"][i]), eps)
                if "router" not in stack:
                    x = x + _dense(xn, *(cast(stack[name][i])
                                         for name in EXPERT_LEAVES))
                    continue
                rl = {name: cast(stack[name][i])
                      for name in ("router", "router_bias") if name in stack}
                weights = _route(xn, rl, router)
                for lo in range(0, e, expert_block):
                    hi = min(e, lo + expert_block)
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
