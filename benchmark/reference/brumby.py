"""The benchmark's plain reference for the `brumby-14b` cells: Brumby-14B-
Base's forward pass (Qwen3-14B's block, every layer's attention replaced by
gated power retention at degree 2) in straightforward `jax.numpy`, float32,
matmuls at `highest` precision, from the engine's own weight arrays. No
cache, no slots, no chunks, no kernels, no scan over layers.

The benchmark's own copy of the lines of `dynamo_tpu/models/reference.py`
that this model takes (`rms_norm`, `rope`, `round_to`,
`retention_features`, `attention_retention`'s quadratic form, `dense_mlp`),
so that the comparison that decides `correct` does not move when the
program's reference does: benchmark/tests/test_brumby_cell.py holds the two
to the same logits on the rehearsal configuration. What is added here:
`forward_blocked`, the same arithmetic at the published widths in blocks
that fit beside the served model (one layer's mixer leaves, a block of the
MLP's columns and a block of the head's at a time, the head at the compared
rows only); the first layer's STATE after a token count by the per-token
recurrence, for the slot comparison; and the controls of
checks/reference_logits_brumby.py, each a change of the REFERENCE alone.

The layer (benchmark/configs/brumby-14b/meta.json's `assumed` has the
source of each line): xn = RMSNorm(x); q = xn Wq, k = xn Wk, v = xn Wv, no
bias; RMSNorm over each head's values of q and of k (one weight of
head_dim a projection); rotate-half RoPE at `rope_theta`; log g =
log_sigmoid(xn Wg + bg), one scalar a key-value head and token. For query
head h of key-value head c = h // (H / Hkv), i <= t:

    w[t, i] = exp(sum_{l = i + 1 .. t} log g_l[c]) (q_t[h] . k_i[c])^2
    o_t[h]  = sum_i w[t, i] v_i[c] / sum_i w[t, i]

then Wo; h = x + that; y = h + SwiGLU(RMSNorm(h)). As a recurrence over
phi, phi(x) . phi(y) = (x . y)^2 (`retention_features`: feature s d + a is
c_s x[a] x[(a + s) mod d], s = 0 .. d / 2): S_t = g_t S_{t-1} + v_t
phi(k_t)^T [Hkv, hd, F], z_t = g_t z_{t-1} + phi(k_t) [Hkv, F], o_t[h] =
S_t phi(q_t[h]) / z_t . phi(q_t[h]).
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


def rope(x, positions, theta):
    """Rotate-half RoPE over the full head. x: [T, H, hd]."""
    hd = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    angle = positions.astype(F32)[:, None] * inv_freq[None, :]  # [T, hd/2]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def round_to(x, dtype):
    """x rounded to `dtype`'s exponent and mantissa, still float32. Not a
    cast there and back: XLA drops such a pair (it may keep excess
    precision), and the rounding is the point."""
    info = jnp.finfo(dtype)
    if info.bits >= 32:
        return x
    return jax.lax.reduce_precision(x, info.nexp, info.nmant)


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


def retention_inputs(x, lp, *, num_heads, num_kv_heads, head_dim,
                     rope_theta, rms_norm_eps, gate_one=False):
    """What the mixer reads of the normed input x [T, D]: q [T, H, hd], k,
    v [T, Hkv, hd] (q and k head-normed and rotated), log_g [T, Hkv].
    `gate_one`: the gate set to 1 (a control)."""
    t = x.shape[0]
    q = rms_norm((x @ lp["wq"]).reshape(t, num_heads, head_dim),
                 lp["q_norm"], rms_norm_eps)
    k = rms_norm((x @ lp["wk"]).reshape(t, num_kv_heads, head_dim),
                 lp["k_norm"], rms_norm_eps)
    v = (x @ lp["wv"]).reshape(t, num_kv_heads, head_dim)
    positions = jnp.arange(t)
    log_g = jax.nn.log_sigmoid(x @ lp["ret_wg"] + lp["ret_bg"])
    if gate_one:
        log_g = jnp.zeros_like(log_g)
    return rope(q, positions, rope_theta), rope(k, positions, rope_theta), \
        v, log_g


def retention_step(carry, xs, state_dtype=F32, reset_every=0):
    """One token of the recurrence: ((S [Hkv, hd, F], z [Hkv, F]), (k, v
    [Hkv, hd], log_g [Hkv], the token's index)) -> (S', z'), rounded to
    `state_dtype`; zeroed first where the token opens a span of
    `reset_every` (a control: the state lost at every such edge)."""
    s, z = carry
    k_t, v_t, g_t, index = xs
    if reset_every:
        keep = (index % reset_every != 0).astype(F32)
        s, z = s * keep, z * keep
    pk = retention_features(k_t)
    g_t = jnp.exp(g_t)
    s = g_t[:, None, None] * s + v_t[:, :, None] * pk[:, None, :]
    z = g_t[:, None] * z + pk
    return round_to(s, state_dtype), round_to(z, state_dtype)


def retention_recurrent(q, k, v, log_g, state_dtype=F32, reset_every=0):
    """The mixer as the per-token recurrence from a state of zeros ->
    ((S, z) after the last token, o [T, H, hd]): what the controls on the
    STATE are read through, and what gives the state itself."""
    hkv, hd = k.shape[1:]
    f = hd * (hd // 2 + 1)

    def step(carry, xs):
        q_t = xs[0].reshape(hkv, -1, hd)
        s, z = retention_step(carry, xs[1:], state_dtype, reset_every)
        pq = retention_features(q_t)                         # [Hkv, G, F]
        num = jnp.einsum("cgf,cvf->cgv", pq, s)
        den = jnp.einsum("cgf,cf->cg", pq, z)
        return (s, z), (num / den[..., None]).reshape(-1, hd)

    zeros = (jnp.zeros((hkv, hd, f), F32), jnp.zeros((hkv, f), F32))
    return jax.lax.scan(step, zeros,
                        (q, k, v, log_g, jnp.arange(k.shape[0])))


def retention_quadratic(q, k, v, log_g, degree=2, reset_every=0):
    """The mixer as the masked quadratic form (module docstring): no
    state, no chunks, no features -> o [T, H, hd]. `degree` 1: |q . k| in
    place of its square; `reset_every`: a query sees the keys of its own
    span alone (both controls)."""
    t, hkv, hd = k.shape
    positions = jnp.arange(t)
    causal = positions[None, :] <= positions[:, None]          # [q, k]
    if reset_every:
        causal &= positions[None, :] // reset_every \
            == positions[:, None] // reset_every
    qk = jnp.einsum("qcgd,kcd->cgqk", q.reshape(t, hkv, -1, hd), k)
    gc = jnp.cumsum(log_g, axis=0).T                           # [Hkv, T]
    decay = jnp.exp(jnp.where(causal, gc[:, :, None] - gc[:, None, :],
                              -jnp.inf))                       # [Hkv, q, k]
    w = decay[:, None] * qk * qk if degree == 2 \
        else decay[:, None] * jnp.abs(qk)
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    return jnp.einsum("cgqk,kcd->qcgd", w, v).reshape(t, -1, hd)


def attention_retention(x, lp, *, state_dtype=F32, degree=2, gate_one=False,
                        reset_every=0, **sizes):
    """x [T, D], the normed input -> the mixer's output [T, D]. The
    quadratic form, but where the state is rounded after every token
    (`state_dtype` below float32), which only the recurrence can show."""
    q, k, v, log_g = retention_inputs(x, lp, gate_one=gate_one, **sizes)
    if jnp.finfo(state_dtype).bits < 32:
        _, o = retention_recurrent(q, k, v, log_g, state_dtype, reset_every)
    else:
        o = retention_quadratic(q, k, v, log_g, degree, reset_every)
    return o.reshape(x.shape[0], -1) @ lp["wo"]


def mixer_state(x, lp, tokens: int, *, state_dtype=F32, gate_one=False,
                reset_every=0, **sizes):
    """The states ((S [2, Hkv, hd, F], z [2, Hkv, F])) that the recurrence
    holds after the first `tokens` rows of the normed input x [T, D] and
    after one row more: what a served sequence's slot must hold once it
    has been fed that many tokens."""
    q, k, v, log_g = retention_inputs(x, lp, gate_one=gate_one, **sizes)
    first = tuple(a[:tokens] for a in (q, k, v, log_g))
    carry, _ = retention_recurrent(*first, state_dtype, reset_every)
    after = retention_step(
        carry, (k[tokens], v[tokens], log_g[tokens], jnp.int32(tokens)),
        state_dtype, reset_every)
    return tuple(jnp.stack(pair) for pair in zip(carry, after))


def dense_mlp(x, lp):
    return (jax.nn.silu(x @ lp["w_gate"]) * (x @ lp["w_up"])) @ lp["w_down"]


def arch_from_hf(hf: dict) -> dict:
    """The mixer's keyword arguments and the norm's epsilon from the
    configuration's config.json."""
    heads = int(hf["num_attention_heads"])
    return dict(
        num_heads=heads,
        num_kv_heads=int(hf.get("num_key_value_heads", heads)),
        head_dim=int(hf.get("head_dim") or hf["hidden_size"] // heads),
        rope_theta=float(hf["rope_theta"]),
        rms_norm_eps=float(hf.get("rms_norm_eps", 1e-6)))


def forward(params, tokens, hf: dict, **changes):
    """tokens [T] -> logits [T, V] float32: one full forward pass over one
    sequence, every weight upcast at once (a small model). `changes`:
    `attention_retention`'s controls."""
    arch = arch_from_hf(hf)
    eps = arch["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda a: jnp.asarray(a, F32), params)
        # ids the engine served  # dynalint: disable-next-line=R1
        x = params["embed"][jnp.asarray(tokens)]
        stack = params["layers"]
        for i in range(len(stack["attn_norm"])):
            lp = {name: leaf[i] for name, leaf in stack.items()}
            x = x + attention_retention(
                rms_norm(x, lp["attn_norm"], eps), lp, **arch, **changes)
            x = x + dense_mlp(rms_norm(x, lp["mlp_norm"], eps), lp)
        return rms_norm(x, params["final_norm"], eps) @ params["lm_head"]


MLP_LEAVES = ("w_gate", "w_up", "w_down")
_STATIC = ("arch", "state_dtype", "degree", "gate_one", "reset_every",
           "act_dtype")


@functools.partial(jax.jit, static_argnames=_STATIC)
def _mixer(x, lp, arch, state_dtype, degree, gate_one, reset_every,
           act_dtype):
    """x + the mixer on the normed input."""
    arch = dict(arch)
    lp = jax.tree.map(lambda a: a.astype(F32), lp)
    xn = round_to(rms_norm(x, lp["attn_norm"], arch["rms_norm_eps"]),
                  act_dtype)
    return round_to(x + round_to(attention_retention(
        xn, lp, state_dtype=state_dtype, degree=degree, gate_one=gate_one,
        reset_every=reset_every, **arch), act_dtype), act_dtype)


@functools.partial(jax.jit, static_argnames=_STATIC[:1] + (
    "tokens", "state_dtype", "gate_one", "reset_every", "act_dtype"))
def _state(x, lp, arch, tokens, state_dtype, gate_one, reset_every,
           act_dtype):
    """A layer's states after `tokens` tokens of the stream x and after
    one more (`mixer_state` on `_mixer`'s own normed input)."""
    arch = dict(arch)
    lp = jax.tree.map(lambda a: a.astype(F32), lp)
    xn = round_to(rms_norm(x, lp["attn_norm"], arch["rms_norm_eps"]),
                  act_dtype)
    return mixer_state(xn, lp, tokens, state_dtype=state_dtype,
                       gate_one=gate_one, reset_every=reset_every, **arch)


@functools.partial(jax.jit, static_argnames=("eps", "act_dtype"))
def _norm(x, w, eps, act_dtype):
    return round_to(rms_norm(x, w.astype(F32), eps), act_dtype)


@jax.jit
def _mlp_block(xn, w_gate, w_up, w_down):
    """A block of the MLP's columns: their part of the down projection's
    sum."""
    return dense_mlp(xn, {"w_gate": w_gate.astype(F32),
                          "w_up": w_up.astype(F32),
                          "w_down": w_down.astype(F32)})


@jax.jit
def _logits_block(x, head):
    return x @ head.astype(F32)


def forward_blocked(params, tokens, hf: dict, positions=None,
                    mlp_block: int = 4352, vocab_block: int = 16384,
                    cast=None, state_dtype=F32, degree=2, gate_one=False,
                    reset_every=0, act_dtype=F32, state_tokens=None):
    """`forward`'s arithmetic at the published widths: tokens [T] ->
    log-softmax over the vocabulary, float32, at `positions` (a list of
    row indices; None: every row) -> [len(positions), V]. The controls of
    checks/reference_logits_brumby.py, each a change of the REFERENCE
    alone: `cast` is applied to every weight leaf first; `gate_one` sets
    the gate to 1; `degree` 1 weighs by |q . k|; `reset_every` loses the
    state at every edge of that many tokens; `state_dtype` is what the
    state is rounded to after every token; `act_dtype` is what the
    activations are rounded to at the block's joints (each norm's output,
    the mixer's output, the MLP's output, the residual stream after each
    of its two additions, the logits). `state_tokens` (a count, below T):
    also the FIRST layer's state after that many tokens of the sequence
    and after one more, (S [2, Hkv, hd, F], z [2, Hkv, F]) float32, as
    the second of a pair (the later layers' inputs carry the served
    path's own activation rounding)."""
    arch = tuple(sorted(arch_from_hf(hf).items()))
    eps = dict(arch)["rms_norm_eps"]
    cast = cast or (lambda a: a)
    with jax.default_matmul_precision("highest"):
        # the rows first, then the cast: a cast of the 1.56 GB table
        # would not fit beside the served model
        # ids the engine served  # dynalint: disable-next-line=R1
        x = round_to(cast(params["embed"][jnp.asarray(tokens)]).astype(F32),
                     act_dtype)
        stack = params["layers"]
        mixer = [name for name in stack
                 if name not in MLP_LEAVES + ("mlp_norm",)]
        width = stack["w_gate"].shape[-1]
        states = None
        for i in range(len(stack["attn_norm"])):
            lp = {name: cast(stack[name][i]) for name in mixer}
            if state_tokens is not None and i == 0:
                states = _state(x, lp, arch, int(state_tokens), state_dtype,
                                gate_one, reset_every, act_dtype)
            x = _mixer(x, lp, arch, state_dtype, degree, gate_one,
                       reset_every, act_dtype)
            xn = _norm(x, cast(stack["mlp_norm"][i]), eps, act_dtype)
            out = 0.0
            for lo in range(0, width, mlp_block):
                hi = min(width, lo + mlp_block)
                out = out + _mlp_block(
                    xn, cast(stack["w_gate"][i, :, lo:hi]),
                    cast(stack["w_up"][i, :, lo:hi]),
                    cast(stack["w_down"][i, lo:hi]))
            x = round_to(x + round_to(out, act_dtype), act_dtype)
        if positions is not None:
            x = x[jnp.asarray(positions)]
        x = _norm(x, cast(params["final_norm"]), eps, act_dtype)
        head = params["lm_head"]
        logits = round_to(jnp.concatenate(
            [_logits_block(x, cast(head[:, lo:lo + vocab_block]))
             for lo in range(0, head.shape[1], vocab_block)], axis=1),
            act_dtype)
        logp = jax.nn.log_softmax(logits, axis=-1)
        if state_tokens is None:
            return logp
        return logp, states
