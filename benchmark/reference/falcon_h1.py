"""The benchmark's own copy of the plain reference for Falcon-H1
(`falcon_h1`): the forward pass in straightforward `jax.numpy`, float32,
matmuls at `jax.default_matmul_precision("highest")`. No cache, no state
slots, no batching, no kernels: the Mamba-2 mixer as the PER-TOKEN
recurrence (a `lax.scan` over the sequence), where the served path runs the
quadratic form over blocks of a prompt chunk and a one-token form in place
over state slots; attention over the whole sequence at once, where the
served path reads pages. It imports nothing from `dynamo_tpu`: what the
served path is compared with (checks/reference_logits_falcon_h1.py) is kept
with the benchmark, so no PR that changes the program changes the
yardstick. The functions down to `dense_mlp` are the arithmetic of
dynamo_tpu/models/reference.py's `falcon_h1` path and nothing that path
never takes (plain RoPE, grouped-query attention, the mixer, the MLP, the
head; tests/test_falcon_h1.py and benchmark/tests/test_falcon_h1_cell.py
hold the two to identical logits); that file's docstring has the layer
equations, and tests/test_falcon_h1.py holds them to `transformers`' own
`falcon_h1`. What is added here is `forward_blocked`, which does the same
arithmetic at the published widths on the chip beside the served model:
weights stay in their stored dtype and are upcast inside each jitted piece,
the 21504-wide MLP a block of columns at a time, and the 261 120-column head
in column blocks at the compared rows only; and `mixer_state`, the state a
sequence's slot must hold after so many tokens.

Weights come in the engine's layout: projections [in, out], stacked over
layers on a leading axis, ONE stack (`layers`): every block is alike.
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
    """Rotate-half RoPE over the full head, no scaling. x: [T, H, hd]."""
    hd = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    angle = positions.astype(F32)[:, None] * inv_freq[None, :]  # [T, hd/2]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(x, lp, *, num_heads, num_kv_heads, head_dim, rope_theta,
              key_multiplier=1.0):
    """Causal grouped-query attention over the whole sequence, no bias;
    `key_multiplier` scales k before RoPE."""
    t = x.shape[0]
    q, k, v = x @ lp["wq"], (x @ lp["wk"]) * key_multiplier, x @ lp["wv"]
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


def causal_conv(x, w):
    """Causal depthwise convolution over the sequence. x [T, C], w [K, C]:
    y_t = sum_j w[j] x_{t - (K - 1) + j}, zeros before the sequence."""
    k = w.shape[0]
    xp = jnp.concatenate([jnp.zeros((k - 1, x.shape[1]), x.dtype), x])
    return sum(w[j] * xp[j:j + x.shape[0]] for j in range(k))


def round_to(x, dtype):
    """x rounded to `dtype`'s exponent and mantissa, still float32. Not a
    cast there and back: XLA drops such a pair (it may keep excess
    precision), and the rounding is the point."""
    info = jnp.finfo(dtype)
    if info.bits >= 32:
        return x
    return jax.lax.reduce_precision(x, info.nexp, info.nmant)


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


def ssm_inputs(x, lp, *, n_heads, d_head, n_groups, d_state,
               in_multiplier=1.0, multipliers=(1.0,) * 5):
    """What the recurrence reads of the normed input x [T, D]: the gate z
    [T, H P], and a token's (x [H, P], dt [H] after its softplus, B, C [H,
    N]) stacked over the sequence."""
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
    return z, (xs, jax.nn.softplus(dt + lp["ssm_dt_bias"]), b, c)


def ssm_scan(lp, tokens, state_dtype=F32):
    """The per-token recurrence over `tokens` (`ssm_inputs`' second
    value) from a state of zeros -> (the state after the last of them [H,
    P, N], y [T, H, P])."""
    xs = tokens[0]
    return jax.lax.scan(
        functools.partial(ssm_recurrence,
                          (-jnp.exp(lp["ssm_a_log"]), lp["ssm_d"]),
                          state_dtype=state_dtype),
        jnp.zeros(xs.shape[1:] + (tokens[2].shape[-1],), F32), tokens)


def mixer_ssm(x, lp, *, n_heads, d_head, n_groups, d_state, rms_norm_eps,
              in_multiplier=1.0, multipliers=(1.0,) * 5, state_dtype=F32):
    """The Mamba-2 mixer as the per-token recurrence. x [T, D], the
    normed input. `state_dtype`: what S is rounded to after every token
    (float32: not at all)."""
    t, g, ds = x.shape[0], n_groups, n_heads * d_head
    z, tokens = ssm_inputs(
        x, lp, n_heads=n_heads, d_head=d_head, n_groups=n_groups,
        d_state=d_state, in_multiplier=in_multiplier,
        multipliers=multipliers)
    _, y = ssm_scan(lp, tokens, state_dtype)
    y = y.reshape(t, ds) * jax.nn.silu(z)                  # the gate first
    y = y.reshape(t, g, ds // g)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                          + rms_norm_eps)
    return (y.reshape(t, ds) * lp["ssm_norm"]) @ lp["ssm_out"]


def mixer_state(x, lp, tokens: int, *, rms_norm_eps=None, state_dtype=F32,
                **sizes):
    """The states S [2, H, P, N] that the recurrence holds after the first
    `tokens` rows of the normed input x [T, D] and after one row more:
    what a served sequence's slot must hold once it has been fed that
    many tokens."""
    _, per_token = ssm_inputs(x, lp, **sizes)
    s, _ = ssm_scan(lp, tuple(v[:tokens] for v in per_token), state_dtype)
    after, _ = ssm_recurrence(
        (-jnp.exp(lp["ssm_a_log"]), lp["ssm_d"]), s,
        tuple(v[tokens] for v in per_token), state_dtype)
    return jnp.stack([s, after])


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


def arch_from_hf(hf: dict) -> dict:
    """The functions' keyword arguments from the configuration's
    config.json: `attn` (attention's), `ssm` (mixer_ssm's), `block`
    (parallel_block's multipliers), and the model's own."""
    eps = float(hf.get("rms_norm_eps", 1e-5))
    heads = int(hf["num_attention_heads"])
    return dict(
        rms_norm_eps=eps,
        embedding_multiplier=float(hf.get("embedding_multiplier", 1.0)),
        lm_head_multiplier=float(hf.get("lm_head_multiplier", 1.0)),
        mlp_multipliers=tuple(float(m) for m in hf["mlp_multipliers"]),
        attn=dict(num_heads=heads,
                  num_kv_heads=int(hf.get("num_key_value_heads", heads)),
                  head_dim=int(hf.get("head_dim")
                               or hf["hidden_size"] // heads),
                  rope_theta=float(hf["rope_theta"]),
                  key_multiplier=float(hf.get("key_multiplier", 1.0))),
        ssm=dict(n_heads=int(hf["mamba_n_heads"]),
                 d_head=int(hf["mamba_d_head"]),
                 n_groups=int(hf["mamba_n_groups"]),
                 d_state=int(hf["mamba_d_state"]), rms_norm_eps=eps,
                 in_multiplier=float(hf.get("ssm_in_multiplier", 1.0)),
                 multipliers=tuple(float(m)
                                   for m in hf["ssm_multipliers"])),
        block=dict(
            attention_in_multiplier=float(
                hf.get("attention_in_multiplier", 1.0)),
            attention_out_multiplier=float(
                hf.get("attention_out_multiplier", 1.0)),
            ssm_out_multiplier=float(hf.get("ssm_out_multiplier", 1.0))))


def layer(x, lp, arch: dict, state_dtype=F32, without_ssm=False):
    """One block: both mixers on the one normed input, added; then the
    MLP. x [T, D]; lp: this layer's weights, float32."""
    eps = arch["rms_norm_eps"]
    x = x + parallel_block(
        rms_norm(x, lp["attn_norm"], eps), lp, attn=arch["attn"],
        ssm=arch["ssm"], state_dtype=state_dtype, without_ssm=without_ssm,
        **arch["block"])
    return x + dense_mlp(rms_norm(x, lp["mlp_norm"], eps), lp,
                         multipliers=arch["mlp_multipliers"])


def forward(params, tokens, hf: dict, **changes):
    """tokens [T] -> logits [T, V] float32: one full forward pass over one
    sequence, every weight upcast at once (a small model). `changes`:
    `layer`'s `state_dtype` / `without_ssm`."""
    arch = arch_from_hf(hf)
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda a: jnp.asarray(a, F32), params)
        # ids the engine served  # dynalint: disable-next-line=R1
        x = params["embed"][jnp.asarray(tokens)] \
            * arch["embedding_multiplier"]
        stack = params["layers"]
        for i in range(len(stack["attn_norm"])):
            x = layer(x, {name: leaf[i] for name, leaf in stack.items()},
                      arch, **changes)
        x = rms_norm(x, params["final_norm"], arch["rms_norm_eps"])
        return (x @ params["lm_head"]) * arch["lm_head_multiplier"]


MLP_LEAVES = ("w_gate", "w_up", "w_down")


def _freeze(d: dict) -> tuple:
    return tuple(sorted((k, _freeze(v) if isinstance(v, dict) else v)
                        for k, v in d.items()))


def _thaw(t: tuple) -> dict:
    return {k: _thaw(v) if isinstance(v, tuple) and v
            and isinstance(v[0], tuple) else v for k, v in t}


@functools.partial(jax.jit, static_argnames=(
    "arch", "state_dtype", "without_ssm", "act_dtype"))
def _mixers(x, lp, arch, state_dtype, without_ssm, act_dtype):
    """x + both mixers on the normed input (`parallel_block`)."""
    arch = _thaw(arch)
    lp = jax.tree.map(lambda a: a.astype(F32), lp)
    xn = round_to(rms_norm(x, lp["attn_norm"], arch["rms_norm_eps"]),
                  act_dtype)
    return round_to(x + round_to(parallel_block(
        xn, lp, attn=arch["attn"], ssm=arch["ssm"],
        state_dtype=state_dtype, without_ssm=without_ssm,
        **arch["block"]), act_dtype), act_dtype)


@functools.partial(jax.jit, static_argnames=(
    "arch", "tokens", "state_dtype", "act_dtype"))
def _state(x, lp, arch, tokens, state_dtype, act_dtype):
    """A block's states after `tokens` tokens of the stream x and after
    one more (`mixer_state` on `_mixers`' own normed input)."""
    arch = _thaw(arch)
    lp = jax.tree.map(lambda a: a.astype(F32), lp)
    xn = round_to(rms_norm(x, lp["attn_norm"], arch["rms_norm_eps"]),
                  act_dtype)
    return mixer_state(xn, lp, tokens, state_dtype=state_dtype,
                       **arch["ssm"])


@functools.partial(jax.jit, static_argnames=("eps", "act_dtype"))
def _norm(x, w, eps, act_dtype):
    return round_to(rms_norm(x, w.astype(F32), eps), act_dtype)


@functools.partial(jax.jit, static_argnames=("gate_multiplier",))
def _mlp_block(xn, w_gate, w_up, w_down, gate_multiplier):
    """A block of the MLP's columns: their part of the down projection's
    sum, before its multiplier."""
    return dense_mlp(xn, {"w_gate": w_gate.astype(F32),
                          "w_up": w_up.astype(F32),
                          "w_down": w_down.astype(F32)},
                     multipliers=(gate_multiplier, 1.0))


@jax.jit
def _logits_block(x, head):
    return x @ head.astype(F32)


def forward_blocked(params, tokens, hf: dict, positions=None,
                    mlp_block: int = 5376, vocab_block: int = 16384,
                    cast=None, state_dtype=F32, without_ssm=False,
                    act_dtype=F32, state_tokens=None):
    """`forward`'s arithmetic at the published widths: tokens [T] ->
    log-softmax over the vocabulary, float32, at `positions` (a list of
    row indices; None: every row) -> [len(positions), V]. The controls of
    checks/reference_logits_falcon_h1.py, each a change of the REFERENCE
    alone: `cast` is applied to every weight leaf first; `state_dtype` is
    what the mixer's state is rounded to after every token; `without_ssm`
    leaves the state-space branch out of every block; `act_dtype` is what
    the activations are rounded to at the block's joints (each norm's
    output, the mixers' summed output, the MLP's output, the residual
    stream after each of its two additions, the logits). `state_tokens`
    (a count, below T): also every block's state after that many tokens of
    the sequence and after one more, [2, L, H, P, N] float32, as the second
    of a pair."""
    arch = arch_from_hf(hf)
    frozen = _freeze({k: arch[k] for k in (
        "rms_norm_eps", "attn", "ssm", "block")})
    cast = cast or (lambda a: a)
    eps = arch["rms_norm_eps"]
    m_gate, m_down = arch["mlp_multipliers"]
    with jax.default_matmul_precision("highest"):
        # the rows first, then the cast: a cast of the 2.67 GB table
        # would not fit beside the served model
        # ids the engine served  # dynalint: disable-next-line=R1
        x = round_to(cast(params["embed"][jnp.asarray(tokens)]).astype(F32)
                     * arch["embedding_multiplier"], act_dtype)
        stack = params["layers"]
        mixer = [name for name in stack
                 if name not in MLP_LEAVES + ("mlp_norm",)]
        width = stack["w_gate"].shape[-1]
        states = []
        for i in range(len(stack["attn_norm"])):
            lp = {name: cast(stack[name][i]) for name in mixer}
            if state_tokens is not None:
                states.append(_state(x, lp, frozen, int(state_tokens),
                                     state_dtype, act_dtype))
            x = _mixers(x, lp, frozen, state_dtype, without_ssm, act_dtype)
            xn = _norm(x, cast(stack["mlp_norm"][i]), eps, act_dtype)
            out = 0.0
            for lo in range(0, width, mlp_block):
                hi = min(width, lo + mlp_block)
                out = out + _mlp_block(
                    xn, cast(stack["w_gate"][i, :, lo:hi]),
                    cast(stack["w_up"][i, :, lo:hi]),
                    cast(stack["w_down"][i, lo:hi]), m_gate)
            x = round_to(x + round_to(out * m_down, act_dtype), act_dtype)
        if positions is not None:
            x = x[jnp.asarray(positions)]
        x = _norm(x, cast(params["final_norm"]), eps, act_dtype)
        head = params["lm_head"]
        logits = jnp.concatenate(
            [_logits_block(x, cast(head[:, lo:lo + vocab_block]))
             for lo in range(0, head.shape[1], vocab_block)], axis=1)
        logits = round_to(logits * arch["lm_head_multiplier"], act_dtype)
        logp = jax.nn.log_softmax(logits, axis=-1)
        if state_tokens is None:
            return logp
        return logp, jnp.stack(states, axis=1)
