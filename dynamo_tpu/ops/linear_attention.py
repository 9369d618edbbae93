"""Kimi Delta Attention's state update (a gated delta rule with a
per-channel decay), in the two forms the served path needs.

The function (dynamo_tpu/models/reference.attention_kda has it as the
per-token recurrence): per head, S [dk, dv] float32,

    S_t = (I - b_t k_t k_t^T) diag(exp g_t) S_{t-1} + b_t k_t v_t^T
    o_t = S_t^T q_t

with g_t in (lower bound, 0) per channel and b_t in (0, 1).

`kda_step`: one token a row (a decode step). The state is read twice and
written once: W = S^T [a*q, a*k] (a = exp g) from the old state, d =
b (v - W_k), S' = a * S + k d^T, o = W_q + (k . q) d.

`kda_chunk`: a chunk of T tokens a row at once, in blocks of at most
`BLOCK` tokens (the WY form of Gated DeltaNet / Kimi Linear). With G the
running sum of g inside a block, a block's pseudo-values solve the
unit-lower-triangular system

    (I + diag(b) A) U = diag(b) (V - (K e^G) S_0),
    A[t, i] = sum_c k_t[c] k_i[c] exp(G_t[c] - G_i[c])   (i < t)

and then o_t = (q_t e^{G_t})^T S_0 + sum_{i<=t} B[t, i] u_i with B as A
with q_t for k_t and the diagonal kept, S_C = e^{G_C} S_0 + (K e^{G_C -
G})^T U. Every decay that is formed is a PAIRWISE one, exp(G_t - G_i) with
i <= t, which never exceeds 1: the factored form (k e^G)(k e^-G)^T
overflows once a block's summed decay passes e^88, which 18 tokens at the
-5 bound do. A block reads and writes the state once. A token that is
padding (`valid` false) has b = 0, g = 0 and zero q, k, v: an identity
update, so padding cells and padding rows change no state.

All arithmetic is float32 at `Precision.HIGHEST`: a TPU's default rounds
float32 matmul operands to bfloat16, and the state is an accumulator over
the whole sequence.
"""
# dynalint: hot-path — every op here runs inside jitted decode/prefill programs
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

BLOCK = 16
F32 = jnp.float32
_einsum = functools.partial(jnp.einsum, precision=jax.lax.Precision.HIGHEST)


def l2_normalize(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def conv_with_tail(pre, tail, w, n_valid):
    """The causal depthwise convolution of a chunk that continues a
    sequence. pre [B, T, C]: the chunk's inputs; tail [B, K - 1, C]: the
    K - 1 inputs before it (zeros at a sequence's start); w [K, C];
    n_valid [B]: the row's real tokens, a prefix of the T. Returns (y
    [B, T, C] float32, the next tail [B, K - 1, C]: the last K - 1 of
    tail | pre[:n_valid], so a row with no real token keeps its own)."""
    k = w.shape[0]
    t = pre.shape[1]
    xp = jnp.concatenate([tail.astype(pre.dtype), pre], axis=1)
    wf = w.astype(F32)
    y = sum(wf[j] * xp[:, j:j + t].astype(F32) for j in range(k))
    at = n_valid[:, None] + jnp.arange(k - 1, dtype=n_valid.dtype)[None, :]
    return y, jnp.take_along_axis(xp, at[:, :, None], axis=1)


def kda_step(q, k, v, g, beta, s):
    """One token a row. q, k [B, H, dk], v [B, H, dv], g [B, H, dk],
    beta [B, H], s [B, H, dk, dv], all float32 -> (o [B, H, dv], s')."""
    a = jnp.exp(g)
    w = _einsum("bhck,bhkv->bhcv", jnp.stack([a * q, a * k], axis=2), s)
    d = beta[..., None] * (v - w[:, :, 1])
    s = a[..., None] * s + k[..., None] * d[:, :, None, :]
    o = w[:, :, 0] + jnp.sum(k * q, axis=-1, keepdims=True) * d
    return o, s


def _unit_lower_inverse(n):
    """(I + N)^-1 for N strictly lower triangular [..., C, C]: N is
    nilpotent (N^C = 0), so the inverse is the finite product (I - N)(I +
    N^2)(I + N^4)...: log2(C) squarings, no substitution loop."""
    c = n.shape[-1]
    eye = jnp.eye(c, dtype=n.dtype)
    inv, power, span = eye - n, n, 2
    while span < c:
        power = _einsum("...ij,...jk->...ik", power, power)
        inv = _einsum("...ij,...jk->...ik", inv, eye + power)
        span *= 2
    return inv


def _kda_block(s, xs):
    """One block of C <= BLOCK tokens: (s [B, H, dk, dv], (q, k [B, C, H,
    dk], v [B, C, H, dv], g [B, C, H, dk], beta [B, C, H])) -> (s', o)."""
    q, k, v, g, beta = xs
    c = q.shape[1]
    gc = jnp.cumsum(g, axis=1)                           # G_t, <= 0
    # pairwise decays exp(G_t - G_i), i <= t (<= 1); zero above the
    # diagonal, where the difference is positive and unbounded
    lower = jnp.tril(jnp.ones((c, c), bool))
    diff = gc[:, :, None] - gc[:, None, :]               # [B, t, i, H, dk]
    decay = jnp.where(lower[None, :, :, None, None],
                      jnp.exp(jnp.minimum(diff, 0.0)), 0.0)
    kk = jnp.einsum("bthc,bihc,btihc->bhti", k, k, decay,
                    precision=jax.lax.Precision.HIGHEST)
    qk = jnp.einsum("bthc,bihc,btihc->bhti", q, k, decay,
                    precision=jax.lax.Precision.HIGHEST)
    bt = beta.transpose(0, 2, 1)                         # [B, H, C]
    strict = jnp.tril(jnp.ones((c, c), F32), -1)
    inv = _unit_lower_inverse(bt[..., None] * kk * strict)
    eg = jnp.exp(gc)
    rhs = bt[..., None] * (
        v.transpose(0, 2, 1, 3)
        - _einsum("bthc,bhcv->bhtv", k * eg, s))         # [B, H, C, dv]
    u = _einsum("bhti,bhiv->bhtv", inv, rhs)
    o = _einsum("bthc,bhcv->bthv", q * eg, s) \
        + _einsum("bhti,bhiv->bthv", qk, u)
    last = gc[:, -1]                                     # [B, H, dk]
    s = jnp.exp(last)[..., None] * s + _einsum(
        "bthc,bhtv->bhcv", k * jnp.exp(last[:, None] - gc), u)
    return s, o


def kda_chunk(q, k, v, g, beta, s, block: int = BLOCK):
    """A chunk of T tokens a row. q, k [B, T, H, dk], v [B, T, H, dv],
    g [B, T, H, dk], beta [B, T, H], s [B, H, dk, dv], float32, padding
    tokens already neutral (module docstring) -> (o [B, T, H, dv], s').
    T is a multiple of `block` or smaller than it."""
    t = q.shape[1]
    if t <= block:
        s, o = _kda_block(s, (q, k, v, g, beta))
        return o, s
    n = t // block

    def blocks(a):      # [B, T, ...] -> [n, B, block, ...]
        return jnp.moveaxis(
            a.reshape((a.shape[0], n, block) + a.shape[2:]), 1, 0)

    s, o = jax.lax.scan(_kda_block, s, tuple(
        blocks(a) for a in (q, k, v, g, beta)))
    o = jnp.moveaxis(o, 0, 1)                            # [B, n, block, ..]
    return o.reshape((o.shape[0], t) + o.shape[3:]), s
