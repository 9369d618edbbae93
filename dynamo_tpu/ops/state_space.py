"""Mamba-2's state-space scan (SSD: a scalar decay a head, no delta rule),
in the forms the served path needs.

The function (dynamo_tpu/models/reference.ssm_recurrence has it as the
per-token recurrence): per head h of H, with P = the head's width and N the
state's, S [P, N] float32,

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T
    y_t = S_t C_t + D x_t

with dt_t > 0 a head (already past its softplus), A < 0 a head, x_t [P],
B_t and C_t [N] shared by the heads of a group (head h reads group
h // (H / G)). A token with dt = 0 changes nothing: exp(0) = 1 and the
input is weighed by dt. That is what padding is given, so padding cells
and padding rows are exact no-ops on the state.

`ssd_step`: one token a row, the DEFINITION of the one-token form, on a
copy of the rows' states that the caller gathered and scatters back. The
tests hold the other two forms to it, and a backend without the kernel
runs it.

`ssd_step_slots`: the served one-token form, addressed by slot in the whole
leaf [L, slots, H, P, N], in place. On a TPU one Pallas kernel holds a
block of a row's heads in VMEM for the whole update, so each live slot's
state crosses HBM once each way a layer and step, no other slot is read or
written but the scratch slot that dead rows name, and no [B, H, P, N] copy
exists outside VMEM (ops/linear_attention.kda_step_slots is the same
kernel for the delta rule; its docstring has the aliasing argument).
Elsewhere (`ssd_step_slots_impl`) it is `ssd_step` on gathered rows.

`ssd_chunk`: T tokens a row at once, in blocks of at most `BLOCK` tokens.
With a_t = dt_t A and G_t its running sum inside a block,

    y_t = exp(G_t) S_0 C_t + sum_{i <= t} exp(G_t - G_i) (C_t . B_i) dt_i x_i
    S_L = exp(G_L) S_0 + sum_i exp(G_L - G_i) dt_i x_i B_i^T

the quadratic form inside a block with the cumulative-decay mask, the state
passed from block to block. Every decay that is formed is exp of a
difference G_t - G_i with i <= t, which never exceeds 1. A block reads and
writes the state once.

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

# tokens a block of `ssd_chunk`: the mixed step's chunk beside a full batch
# (64 tokens) is one block. The published kernel's is 128
# (`mamba_chunk_size`); the result does not depend on it
BLOCK = 64
F32 = jnp.float32
_einsum = functools.partial(jnp.einsum, precision=jax.lax.Precision.HIGHEST)


def ssd_step(x, dt, a, b, c, d, s):
    """One token a row. x [B, H, P], dt [B, H], a, d [H], b, c [B, G, N],
    s [B, H, P, N], all float32 -> (y [B, H, P], s')."""
    h, g = x.shape[1], b.shape[1]
    bh, ch = (jnp.repeat(v, h // g, axis=1) for v in (b, c))     # [B, H, N]
    s = jnp.exp(dt * a)[..., None, None] * s \
        + (dt[..., None] * x)[..., None] * bh[:, :, None, :]
    y = _einsum("bhpn,bhn->bhp", s, ch) + d[None, :, None] * x
    return y, s


# heads of a row that one grid step of the slot-addressed kernel holds in
# VMEM (a head's state is P x N float32: 128 KB at 128 x 256). Blocks in
# and out are double-buffered, so hb heads cost 4 x hb x 128 KB of VMEM
STEP_SLOTS_HEADS = 8


def ssd_step_slots_impl() -> str:
    """"pallas": the slot-addressed kernel, compiled, on a TPU. "plain":
    `ssd_step` on the rows' states gathered by slot and scattered back,
    which every backend lowers, elsewhere. ("interpret" runs the kernel's
    body in the Pallas interpreter: what a CPU test asks for.)"""
    return "pallas" if jax.default_backend() == "tpu" else "plain"


def _ssd_slots_kernel(hb, l_ref, slot_ref, fresh_ref, cols_ref, rows_ref,
                       s_ref, y_ref, s_out_ref):
    """One row's `hb` heads. cols_ref [1, 1, P, 2 hb]: exp(dt A) | dt x
    with P on the sublanes, a head a lane (what scales a state's ROWS has
    to be a column; the caller transposes the small operands); rows_ref
    [1, 2, hb, N]: B | C of each head's group; s_ref, s_out_ref [1, 1, hb,
    P, N]: the same block of the aliased leaf; y_ref [1, 1, P, hb]: S' C
    a head, a column each. Each head's state is loaded once, the product
    with C is a float32 reduction over the lanes on the vector unit, and
    the new state is stored once."""
    del l_ref, slot_ref
    fresh = fresh_ref[pl.program_id(0)] != 0
    cols = cols_ref[0, 0]
    lane = jax.lax.broadcasted_iota(jnp.int32, y_ref.shape[2:], 1)
    y = jnp.zeros(y_ref.shape[2:], F32)
    for i in range(hb):
        decay, xdt = cols[:, i:i + 1], cols[:, hb + i:hb + i + 1]  # [P, 1]
        b, c = rows_ref[0, 0, i:i + 1, :], rows_ref[0, 1, i:i + 1, :]
        s = decay * jnp.where(fresh, 0.0, s_ref[0, 0, i]) + xdt * b
        s_out_ref[0, 0, i] = s
        y = jnp.where(lane == i, jnp.sum(s * c, axis=1, keepdims=True), y)
    y_ref[0, 0] = y


def ssd_step_slots(ssm_s, layer, slots, x, dt, a, b, c, d, fresh=None,
                   impl=None, heads_per_block: int = STEP_SLOTS_HEADS):
    """`ssd_step` where the state rests. ssm_s [L, S, H, P, N] float32: the
    whole leaf; layer: this layer's index in it (traced); slots [B] int32:
    each row's slot, -1 for a row that must change nothing (a DEAD row:
    padding, finished, or one whose tokens another form takes); x [B, H,
    P], dt [B, H], b, c [B, G, N] float32 in ROW order, a, d [H]; fresh
    [B] bool: the row starts from zeros whatever its slot holds. -> (y [B,
    H, P], ssm_s'), the leaf aliased in to out.

    Each live row's slot is read once and written once a call; no other
    slot of the leaf is touched but the SCRATCH slot, the leaf's last
    (`models/llama.init_state` makes it; the scheduler never hands it
    out). Every dead row names it with dt = 0 and x = 0 (an identity
    update, y = S C of whatever it holds, which the caller drops), so it
    keeps what it held and what it holds reaches no live row. Two live
    rows of one call never share a slot (a slot is one sequence's)."""
    impl = impl or ssd_step_slots_impl()
    _, n_s, h, p, n = ssm_s.shape
    rows, g = slots.shape[0], b.shape[1]
    live = slots >= 0
    at = jnp.where(live, slots, n_s - 1).astype(jnp.int32)
    fresh = jnp.zeros((rows,), bool) if fresh is None else fresh
    x = jnp.where(live[:, None, None], x, 0.0)
    dt = jnp.where(live[:, None], dt, 0.0)
    if impl == "plain":
        s0 = jnp.where(fresh[:, None, None, None], 0.0, ssm_s[layer, at])
        y, s1 = ssd_step(x, dt, a, b, c, d, s0)
        # dead rows all name the scratch slot: theirs is dropped
        return y, ssm_s.at[layer, jnp.where(live, slots, n_s)].set(
            s1, mode="drop")
    hb = min(heads_per_block, h)
    assert h % hb == 0, (h, hb)
    # the operands that scale a state's rows, as columns: [B, H/hb, P,
    # decay | dt x of hb heads]; B and C a head, as rows over the lanes
    decay = jnp.broadcast_to(jnp.exp(dt * a)[..., None], x.shape)
    cols = jnp.stack([decay, dt[..., None] * x], axis=1).reshape(
        rows, 2, h // hb, hb, p).transpose(0, 2, 4, 1, 3).reshape(
        rows, h // hb, p, 2 * hb)
    bc = jnp.repeat(jnp.stack([b, c], axis=1), h // g, axis=2)  # [B,2,H,N]

    def state_block(i, j, l_ref, slot_ref, fresh_ref):
        return l_ref[0], slot_ref[i], j, 0, 0

    state_spec = pl.BlockSpec((1, 1, hb, p, n), state_block)
    y, ssm_s = pl.pallas_call(
        functools.partial(_ssd_slots_kernel, hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(rows, h // hb),
            in_specs=[
                pl.BlockSpec((1, 1, p, 2 * hb),
                             lambda i, j, *_: (i, j, 0, 0)),
                pl.BlockSpec((1, 2, hb, n), lambda i, j, *_: (i, 0, j, 0)),
                state_spec],
            out_specs=[
                pl.BlockSpec((1, 1, p, hb), lambda i, j, *_: (i, j, 0, 0)),
                state_spec]),
        out_shape=[jax.ShapeDtypeStruct((rows, h // hb, p, hb), F32),
                   jax.ShapeDtypeStruct(ssm_s.shape, ssm_s.dtype)],
        # operands count the three prefetched scalars: the leaf is the 6th
        input_output_aliases={5: 1},
        # the op's name in a device trace (its share of the busy time reads it)
        name="ssd_step_slots",
        interpret=impl == "interpret",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), at,
      fresh.astype(jnp.int32), cols, bc, ssm_s)
    y = y.transpose(0, 1, 3, 2).reshape(rows, h, p)
    return y + d[None, :, None] * x, ssm_s


def _ssd_block(a, d, s, xs):
    """One block of L <= BLOCK tokens: (s [B, H, P, N], (x [B, L, H, P],
    dt [B, L, H], b, c [B, L, G, N])) -> (s', y [B, L, H, P])."""
    x, dt, b, c = xs
    rows, l, h, p = x.shape
    g = b.shape[2]
    gc = jnp.cumsum(dt * a, axis=1)                      # G_t [B, L, H], <= 0
    # pairwise decays exp(G_t - G_i), i <= t (<= 1); zero above the
    # diagonal, where the difference is positive and unbounded
    lower = jnp.tril(jnp.ones((l, l), bool))
    diff = gc[:, :, None] - gc[:, None, :]               # [B, t, i, H]
    decay = jnp.where(lower[None, :, :, None],
                      jnp.exp(jnp.minimum(diff, 0.0)), 0.0)
    cb = _einsum("btgn,bign->btig", c, b)                # C_t . B_i a group
    m = (decay * dt[:, None]).reshape(rows, l, l, g, h // g) \
        * cb[..., None]                                  # [B, t, i, G, H/G]
    y = _einsum("btih,bihp->bthp", m.reshape(rows, l, l, h), x)
    xg = x.reshape(rows, l, g, h // g, p)
    sg = s.reshape(rows, g, h // g, p, s.shape[-1])
    y = y + jnp.exp(gc)[..., None] * _einsum(
        "btgn,bgkpn->btgkp", c, sg).reshape(rows, l, h, p)
    last = gc[:, -1]                                     # G_L [B, H]
    w = (jnp.exp(last[:, None] - gc) * dt)[..., None] * x    # [B, L, H, P]
    s = jnp.exp(last)[..., None, None] * s + _einsum(
        "bigkp,bign->bgkpn", w.reshape(xg.shape), b).reshape(s.shape)
    return s, y + d[None, None, :, None] * x


def ssd_chunk(x, dt, a, b, c, d, s, block: int = BLOCK):
    """A chunk of T tokens a row. x [B, T, H, P], dt [B, T, H] (0 at a
    token that is padding: an exact no-op on the state), b, c [B, T, G,
    N], a, d [H], s [B, H, P, N], float32 -> (y [B, T, H, P], s'). T is a
    multiple of `block` or smaller than it."""
    t = x.shape[1]
    step = functools.partial(_ssd_block, a, d)
    if t <= block:
        s, y = step(s, (x, dt, b, c))
        return y, s
    n = t // block

    def blocks(v):      # [B, T, ...] -> [n, B, block, ...]
        return jnp.moveaxis(
            v.reshape((v.shape[0], n, block) + v.shape[2:]), 1, 0)

    s, y = jax.lax.scan(step, s, tuple(blocks(v) for v in (x, dt, b, c)))
    y = jnp.moveaxis(y, 0, 1)                            # [B, n, block, ..]
    return y.reshape((y.shape[0], t) + y.shape[3:]), s
