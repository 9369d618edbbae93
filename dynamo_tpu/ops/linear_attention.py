"""Kimi Delta Attention's state update (a gated delta rule with a
per-channel decay), in the two forms the served path needs.

The function (dynamo_tpu/models/reference.attention_kda has it as the
per-token recurrence): per head, S [dk, dv] float32,

    S_t = (I - b_t k_t k_t^T) diag(exp g_t) S_{t-1} + b_t k_t v_t^T
    o_t = S_t^T q_t

with g_t in (lower bound, 0) per channel and b_t in (0, 1).

`kda_step`: one token a row, the DEFINITION of the one-token form: W =
S^T [a*q, a*k] (a = exp g) from the old state, d = b (v - W_k), S' =
a * S + k d^T, o = W_q + (k . q) d. Written the plain way it reads the
state twice and writes it once, on a copy of the rows' states that the
caller gathered and scatters back: about six passes over them. The tests
hold the served form to it, and a backend without the kernel runs it.

`kda_step_slots`: the served form of the same update, addressed by slot
in the whole leaf [Lk, slots, H, dk, dv], in place. On a TPU one Pallas
kernel holds a row's heads in VMEM for the whole update (both products
float32 reductions on the vector unit, no operand rounded), so each live
slot's state crosses HBM once each way a layer and step, no other slot is
read or written but the scratch slot that dead rows name, and no [B, H,
dk, dv] copy exists outside VMEM. Elsewhere (`kda_step_slots_impl`) it is
`kda_step` on gathered rows, with the same dead rows and scratch slot.

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
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK = 16
F32 = jnp.float32
_einsum = functools.partial(jnp.einsum, precision=jax.lax.Precision.HIGHEST)


def l2_normalize(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def conv_with_tail(pre, tail, w, n_valid, bias=None):
    """The causal depthwise convolution of a chunk that continues a
    sequence. pre [B, T, C]: the chunk's inputs; tail [B, K - 1, C]: the
    K - 1 inputs before it (zeros at a sequence's start); w [K, C];
    n_valid [B]: the row's real tokens, a prefix of the T; bias [C], for
    a convolution that has one (the state-space mixer's). Returns (y
    [B, T, C] float32, the next tail [B, K - 1, C]: the last K - 1 of
    tail | pre[:n_valid], so a row with no real token keeps its own)."""
    k = w.shape[0]
    t = pre.shape[1]
    xp = jnp.concatenate([tail.astype(pre.dtype), pre], axis=1)
    wf = w.astype(F32)
    y = sum(wf[j] * xp[:, j:j + t].astype(F32) for j in range(k))
    if bias is not None:
        y = y + bias.astype(F32)
    at = n_valid[:, None] + jnp.arange(k - 1, dtype=n_valid.dtype)[None, :]
    return y, jnp.take_along_axis(xp, at[:, :, None], axis=1)


def conv_one_token(pre, tail, w, bias=None):
    """`conv_with_tail` for ONE token a row, the one definition every
    one-token form shares (a decode window's step, a mixed step's decode
    rows): pre [B, C], tail [B, K - 1, C], w [K, C], bias [C] or None ->
    (y [B, C] float32, the next tail [B, K - 1, C] in the tail's dtype).
    One tap window a row: K * C values, whatever the step's grid."""
    xp = jnp.concatenate([tail, pre[:, None].astype(tail.dtype)], axis=1)
    y = jnp.sum(w.astype(F32)[None] * xp.astype(F32), axis=1)
    if bias is not None:
        y = y + bias.astype(F32)
    return y, xp[:, 1:]


def kda_step(q, k, v, g, beta, s):
    """One token a row. q, k [B, H, dk], v [B, H, dv], g [B, H, dk],
    beta [B, H], s [B, H, dk, dv], all float32 -> (o [B, H, dv], s')."""
    a = jnp.exp(g)
    w = _einsum("bhck,bhkv->bhcv", jnp.stack([a * q, a * k], axis=2), s)
    d = beta[..., None] * (v - w[:, :, 1])
    s = a[..., None] * s + k[..., None] * d[:, :, None, :]
    o = w[:, :, 0] + jnp.sum(k * q, axis=-1, keepdims=True) * d
    return o, s


# heads of a row that one grid step of the slot-addressed kernel holds in
# VMEM (a head's state is dk x dv float32: 64 KB at 128 x 128). Blocks in
# and out are double-buffered, so hb heads cost 4 x hb x 64 KB of VMEM.
# PERF.md section 6, PR 34 has the sweep on the chip that chose it.
STEP_SLOTS_HEADS = 16


def kda_step_slots_impl() -> str:
    """"pallas": the slot-addressed kernel, compiled, on a TPU. "plain":
    `kda_step` on the rows' states gathered by slot and scattered back,
    which every backend lowers, elsewhere. ("interpret" runs the kernel's
    body in the Pallas interpreter: what a CPU test asks for.)"""
    return "pallas" if jax.default_backend() == "tpu" else "plain"


def _step_slots_kernel(hb, lk_ref, slot_ref, fresh_ref, cols_ref, rows_ref,
                       s_ref, o_ref, s_out_ref):
    """One row's `hb` heads. cols_ref [1, 1, dk, 3 hb]: a | q | k with dk
    on the sublanes, a head a lane (what scales a state's ROWS has to be
    a column; the caller transposes the small operands, nothing here
    does); rows_ref [1, 3, hb, dv]: v | beta | k . q, the two scalars
    spread over dv; s_ref, s_out_ref [1, 1, hb, dk, dv]: the same block
    of the aliased leaf. Each head's state is loaded once, both
    products are float32 reductions over dk on the vector unit, and the
    new state is stored once."""
    del lk_ref, slot_ref
    fresh = fresh_ref[pl.program_id(0)] != 0
    cols = cols_ref[0, 0]
    for i in range(hb):
        a, q, k = (cols[:, n * hb + i:n * hb + i + 1] for n in range(3))
        s = s_ref[0, 0, i]
        sa = a * jnp.where(fresh, 0.0, s)               # diag(a) S
        kb = jnp.broadcast_to(k, sa.shape)
        w_q = jnp.sum(q * sa, axis=0, keepdims=True)    # [1, dv]
        w_k = jnp.sum(kb * sa, axis=0, keepdims=True)
        v, beta, kq = (rows_ref[0, n, i:i + 1, :] for n in range(3))
        d = beta * (v - w_k)
        s_out_ref[0, 0, i] = sa + kb * d
        o_ref[0, i:i + 1, :] = w_q + kq * d


def kda_step_slots(kda_s, lk, slots, q, k, v, g, beta, fresh=None,
                   impl=None, heads_per_block: int = STEP_SLOTS_HEADS):
    """`kda_step` where the state rests. kda_s [Lk, S, H, dk, dv] float32:
    the whole leaf; lk: this layer's index in it (traced); slots [B]
    int32: each row's slot, -1 for a row that must change nothing (a
    DEAD row: padding, finished, or one whose tokens another form
    takes); q, k, g [B, H, dk], v [B, H, dv], beta [B, H] float32 in ROW
    order; fresh [B] bool: the row starts from zeros whatever its slot
    holds. -> (o [B, H, dv], kda_s'), the leaf aliased in to out.

    Each live row's slot is read once and written once a call; no other
    slot of the leaf is touched but the SCRATCH slot, the leaf's last
    (`models/llama.init_state` makes it; the scheduler never hands it
    out). An aliased output writes every grid step's block back, and a
    block's read is pipelined ahead of the step before's write, so a
    dead row must not name a slot that a live row of the same call
    updates: every dead row names the scratch slot, with b = 0, g = 0
    and k = q = v = 0 (an identity update, o = 0), so the scratch slot
    keeps what it held and what it holds reaches no live row. Two live
    rows of one call never share a slot (a slot is one sequence's).
    tests/test_linattn_kernel.py holds all of it."""
    impl = impl or kda_step_slots_impl()
    _, n_s, h, dk, dv = kda_s.shape
    b = slots.shape[0]
    live = slots >= 0
    at = jnp.where(live, slots, n_s - 1).astype(jnp.int32)
    fresh = jnp.zeros((b,), bool) if fresh is None else fresh
    q, k, v, g = (jnp.where(live[:, None, None], x, 0.0)
                  for x in (q, k, v, g))
    beta = jnp.where(live[:, None], beta, 0.0)
    if impl == "plain":
        s0 = jnp.where(fresh[:, None, None, None], 0.0, kda_s[lk, at])
        o, s1 = kda_step(q, k, v, g, beta, s0)
        # dead rows all name the scratch slot: theirs is dropped
        return o, kda_s.at[lk, jnp.where(live, slots, n_s)].set(
            s1, mode="drop")
    hb = min(heads_per_block, h)
    assert h % hb == 0, (h, hb)
    # the operands that scale a state's rows, as columns: [B, H/hb, dk,
    # a | q | k of hb heads]; and those that scale its columns, as rows
    cols = jnp.stack([jnp.exp(g), q, k], axis=1).reshape(
        b, 3, h // hb, hb, dk).transpose(0, 2, 4, 1, 3).reshape(
        b, h // hb, dk, 3 * hb)
    kq = jnp.sum(k * q, axis=-1)
    rows = jnp.stack([v, jnp.broadcast_to(beta[..., None], v.shape),
                      jnp.broadcast_to(kq[..., None], v.shape)], axis=1)

    def state_block(i, j, lk_ref, slot_ref, fresh_ref):
        return lk_ref[0], slot_ref[i], j, 0, 0

    state_spec = pl.BlockSpec((1, 1, hb, dk, dv), state_block)
    o, kda_s = pl.pallas_call(
        functools.partial(_step_slots_kernel, hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, h // hb),
            in_specs=[
                pl.BlockSpec((1, 1, dk, 3 * hb),
                             lambda i, j, *_: (i, j, 0, 0)),
                pl.BlockSpec((1, 3, hb, dv), lambda i, j, *_: (i, 0, j, 0)),
                state_spec],
            out_specs=[
                pl.BlockSpec((1, hb, dv), lambda i, j, *_: (i, j, 0)),
                state_spec]),
        out_shape=[jax.ShapeDtypeStruct((b, h, dv), F32),
                   jax.ShapeDtypeStruct(kda_s.shape, kda_s.dtype)],
        # operands count the three prefetched scalars: the leaf is the 6th
        input_output_aliases={5: 1},
        interpret=impl == "interpret",
    )(jnp.reshape(lk, (1,)).astype(jnp.int32), at, fresh.astype(jnp.int32),
      cols, rows, kda_s)
    return o, kda_s


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
