"""Gated power retention at degree 2 (Manifest AI, "Scaling Context
Requires Rethinking Attention", arXiv:2507.04239), in the forms the served
path needs.

The function (dynamo_tpu/models/reference.attention_retention has it as
the masked quadratic form, with no state and no features): per query head
h of key-value head c = h // (H / Hkv), causal, with g_t in (0, 1) a
key-value head and token,

    w[t, i] = (g_{i+1} ... g_t) (q_t[h] . k_i[c])^2        (i <= t)
    o_t[h]  = sum_i w[t, i] v_i[c] / sum_i w[t, i]

Every weight is >= 0: no softmax, and a scale on q . k cancels. As a
recurrence, which is what is served, with phi the symmetric degree-2
embedding, phi(x) . phi(y) = (x . y)^2:

    S_t = g_t S_{t-1} + v_t phi(k_t)^T      [d, F]  a key-value head
    z_t = g_t z_{t-1} + phi(k_t)            [F]
    o_t[h] = S_t phi(q_t[h]) / (z_t . phi(q_t[h]) + EPS)

`phi`: the d (d + 1) / 2 distinct products of a d-vector, laid out by the
DIAGONALS of x x^T taken around the corner: block s of d features is c_s
x[a] x[(a + s) mod d], s = 0 .. d / 2. Block 0 is the squares (c = 1);
blocks 1 .. d / 2 - 1 hold every pair {a, a + s} once (c = sqrt 2); block
d / 2 holds each of its d / 2 pairs twice (c = 1, and 1 + 1 = 2). F = d
(d / 2 + 1) features: 8320 = 65 x 128 at d = 128, whole lane tiles with
no zero among them (the 8256 distinct products and 64 repeats; the plain
outer product would be 16 384). A block is one roll and one multiply.

The state is stored values-major, [Hkv, d, F] (`ret_s`) beside [Hkv, F]
(`ret_z`): the features lie on the lanes, so phi(k) and phi(q) meet it as
ROWS, which broadcast over its sublanes for nothing.

`retention_step`: one token a row, the DEFINITION of the one-token form,
on a copy of the rows' states that the caller gathered and scatters back.
The tests hold the other forms to it, and a backend without the kernel
runs it.

`retention_step_slots`: the served one-token form, addressed by slot in the
whole leaf [L, slots, Hkv, d, F], in place. A head's state is 4.2 MB, so
one grid step of the Pallas kernel holds a BLOCK of a row's heads and
features in VMEM; each block crosses HBM once each way, the head's five
query heads read it while it is there, and no other slot is read or
written but the scratch slot that dead rows name
(ops/linear_attention.kda_step_slots has the aliasing argument). Here it
is no optimisation: the leaf is 4.9 GB at the served size and a program
that holds two of it does not fit the chip. The normaliser `ret_z` (0.8 %
of the state) is moved by a plain gather and scatter of its rows.
Elsewhere (`retention_step_slots_impl`) it is `retention_step` on gathered
rows.

`retention_chunk`: T tokens a row at once, in blocks of at most `BLOCK`
tokens: inside a block the masked [L, L] weights w, with no feature
formed for them; across its edge phi(q_t)^T (G_t S_0) and S_L = G_L S_0 +
sum_i (G_L / G_i) v_i phi(k_i)^T. Every decay that is formed is pairwise,
exp of a difference of running sums of log g with i <= t, which never
exceeds 1. A token that is padding has log g = 0, k = 0 and v = 0: an
identity update. A block reads and writes the state once.

All arithmetic is float32 at `Precision.HIGHEST`: a TPU's default rounds
float32 matmul operands to bfloat16, and the state is an accumulator over
the whole sequence.
"""
# dynalint: hot-path — every op here runs inside jitted decode/prefill programs
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# tokens a block of `retention_chunk`: phi(q) of a block is [L, H, F]
# float32 (85 MB at 64 x 40 x 8320), the largest temporary of a mixed step
BLOCK = 64
# what the served forms add to the normaliser before they divide; the
# reference divides by the plain sum. A live token's own weight (q . k)^2
# is of the order of d, so the quotient moves by 1e-14 of itself; a row of
# padding divides 0 by this and hands back 0
EPS = 1e-12
F32 = jnp.float32
_einsum = functools.partial(jnp.einsum, precision=jax.lax.Precision.HIGHEST)


def features(d: int) -> int:
    """F, the features `phi` gives a d-vector (d even)."""
    if d % 2:
        raise ValueError(f"power retention's features are laid out for an "
                         f"even head size, not {d}")
    return d * (d // 2 + 1)


@jax.named_scope("retention.phi")
def phi(x):
    """[..., d] -> [..., F]: phi(x) . phi(y) = (x . y)^2 (module
    docstring), in x's dtype. Block s is x times x rolled by s."""
    d = x.shape[-1]
    half = features(d) // d - 1
    xx = jnp.concatenate([x, x], axis=-1)
    coef = [1.0] + [math.sqrt(2.0)] * (half - 1) + [1.0]
    return jnp.concatenate(
        [c * x * xx[..., s:s + d] for s, c in enumerate(coef)], axis=-1)


def _by_group(q, hkv):
    """q [..., H, x] -> [..., Hkv, H / Hkv, x]: head h reads key-value
    head h // (H / Hkv)."""
    return q.reshape(q.shape[:-2] + (hkv, -1, q.shape[-1]))


def retention_step(q, k, v, log_g, s, z):
    """One token a row. q [B, H, d], k, v [B, Hkv, d], log_g [B, Hkv], s
    [B, Hkv, d, F], z [B, Hkv, F], all float32 -> (o [B, H, d], s', z')."""
    g = jnp.exp(log_g)
    pk = phi(k)
    s = g[..., None, None] * s + v[..., :, None] * pk[..., None, :]
    z = g[..., None] * z + pk
    pq = _by_group(phi(q), k.shape[1])                    # [B, Hkv, G, F]
    num = _einsum("bcgf,bcvf->bcgv", pq, s)
    den = _einsum("bcgf,bcf->bcg", pq, z)
    return (num / (den[..., None] + EPS)).reshape(q.shape), s, z


# what one grid step of the slot-addressed kernel holds in VMEM of a row's
# state: key-value heads and features (a multiple of 128 that divides F).
# A block is heads x d x features float32 (1.7 MB at 2 x 128 x 1664), in
# and out double-buffered. PERF.md section 6, PR 55 has the sweep on the
# chip that chose them.
STEP_SLOTS_HEADS = 2
STEP_SLOTS_FEATURES = 1664


def retention_step_slots_impl() -> str:
    """"pallas": the slot-addressed kernel, compiled, on a TPU. "plain":
    `retention_step` on the rows' states gathered by slot and scattered
    back, which every backend lowers, elsewhere. ("interpret" runs the
    kernel's body in the Pallas interpreter: what a CPU test asks for.)"""
    return "pallas" if jax.default_backend() == "tpu" else "plain"


def _step_slots_kernel(hb, grp, l_ref, slot_ref, fresh_ref, cols_ref,
                       pk_ref, pq_ref, s_ref, num_ref, s_out_ref):
    """One row's block of `hb` key-value heads and fb features. cols_ref
    [1, 1, d, 2 hb]: v | g with d on the sublanes, a head a lane (what
    scales a state's ROWS has to be a column; g is the same down its
    column); pk_ref [1, 1, hb, fb]: phi(k) a head, a row over the lanes;
    pq_ref [1, 1, hb grp, fb]: phi(q) of their query heads; s_ref,
    s_out_ref [1, 1, hb, d, fb]: the same block of the aliased leaf;
    num_ref [1, 1, d, hb grp]: S' phi(q) a query head, a column each,
    summed over the row's feature blocks (the block stays in VMEM while
    the feature axis, the grid's last, runs). Each block of the state is
    loaded once, every product with phi(q) is a float32 reduction over
    the lanes on the vector unit, and the new block is stored once."""
    del l_ref, slot_ref
    fresh = fresh_ref[pl.program_id(0)] != 0

    @pl.when(pl.program_id(2) == 0)
    def _():
        num_ref[...] = jnp.zeros(num_ref.shape, F32)

    cols = cols_ref[0, 0]
    lane = jax.lax.broadcasted_iota(jnp.int32, num_ref.shape[2:], 1)
    num = jnp.zeros(num_ref.shape[2:], F32)
    for i in range(hb):
        v, g = cols[:, i:i + 1], cols[:, hb + i:hb + i + 1]        # [d, 1]
        s = g * jnp.where(fresh, 0.0, s_ref[0, 0, i]) \
            + v * pk_ref[0, 0, i:i + 1, :]
        s_out_ref[0, 0, i] = s
        for j in range(i * grp, (i + 1) * grp):
            num = jnp.where(lane == j, jnp.sum(
                s * pq_ref[0, 0, j:j + 1, :], axis=1, keepdims=True), num)
    num_ref[0, 0] += num


def retention_step_slots(ret_s, ret_z, layer, slots, q, k, v, log_g,
                         fresh=None, impl=None,
                         heads_per_block: int = STEP_SLOTS_HEADS,
                         features_per_block: int = STEP_SLOTS_FEATURES):
    """`retention_step` where the state rests. ret_s [L, S, Hkv, d, F],
    ret_z [L, S, Hkv, F] float32: the whole leaves; layer: this layer's
    index in them (traced); slots [B] int32: each row's slot, -1 for a
    row that must change nothing (a DEAD row: padding, finished, or one
    whose tokens another form takes); q [B, H, d], k, v [B, Hkv, d],
    log_g [B, Hkv] float32 in ROW order; fresh [B] bool: the row starts
    from zeros whatever its slot holds. -> (o [B, H, d], ret_s', ret_z'),
    `ret_s` aliased in to out.

    Each live row's slot is read once and written once a call; no other
    slot of the leaf is touched but the SCRATCH slot, the leaf's last
    (`models/llama.init_state` makes it; the scheduler never hands it
    out). Every dead row names it with g = 1, k = 0 and v = 0 (an
    identity update; its o is 0 / EPS of whatever the slot holds times a
    zero phi(q), which the caller drops), so it keeps what it held and
    what it holds reaches no live row. Two live rows of one call never
    share a slot (a slot is one sequence's). `features_per_block` that is
    no multiple of 128 dividing F is refused: a ragged last block would
    read past the leaf."""
    impl = impl or retention_step_slots_impl()
    _, n_s, hkv, d, f = ret_s.shape
    rows, h = q.shape[:2]
    grp = h // hkv
    live = slots >= 0
    at = jnp.where(live, slots, n_s - 1).astype(jnp.int32)
    drop = jnp.where(live, slots, n_s)
    fresh = jnp.zeros((rows,), bool) if fresh is None else fresh
    q = jnp.where(live[:, None, None], q, 0.0)
    k, v = (jnp.where(live[:, None, None], a, 0.0) for a in (k, v))
    log_g = jnp.where(live[:, None], log_g, 0.0)
    if impl == "plain":
        keep = ~fresh
        s0 = jnp.where(keep[:, None, None, None], ret_s[layer, at], 0.0)
        z0 = jnp.where(keep[:, None, None], ret_z[layer, at], 0.0)
        o, s1, z1 = retention_step(q, k, v, log_g, s0, z0)
        # dead rows all name the scratch slot: theirs is dropped
        return (o, ret_s.at[layer, drop].set(s1, mode="drop"),
                ret_z.at[layer, drop].set(z1, mode="drop"))
    hb, fb = min(heads_per_block, hkv), min(features_per_block, f)
    if hkv % hb or f % fb or (fb % 128 and fb != f):
        raise ValueError(
            f"retention_step_slots: a block of {hb} heads x {fb} features "
            f"does not tile a state of {hkv} heads x {f} features (whole "
            f"128-lane tiles that divide it)")
    g = jnp.exp(log_g)
    pk, pq = phi(k), phi(q)
    # the normaliser: its rows gathered, moved on a token and scattered
    z1 = g[..., None] * jnp.where(
        fresh[:, None, None], 0.0,
        ret_z.at[layer, at].get(mode="clip")) + pk
    ret_z = ret_z.at[layer, drop].set(z1, mode="drop")
    den = _einsum("bcgf,bcf->bcg", _by_group(pq, hkv), z1).reshape(rows, h)
    # what scales a state's rows, as columns: [B, Hkv/hb, d, v | g of hb
    # heads]
    cols = jnp.stack([v, jnp.broadcast_to(g[..., None], v.shape)],
                     axis=1).reshape(rows, 2, hkv // hb, hb, d).transpose(
        0, 2, 4, 1, 3).reshape(rows, hkv // hb, d, 2 * hb)

    def state_block(i, c, j, l_ref, slot_ref, fresh_ref):
        return l_ref[0], slot_ref[i], c, 0, j

    state_spec = pl.BlockSpec((1, 1, hb, d, fb), state_block)
    block_bytes = 4 * hb * d * fb
    num, ret_s = pl.pallas_call(
        functools.partial(_step_slots_kernel, hb, grp),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(rows, hkv // hb, f // fb),
            in_specs=[
                pl.BlockSpec((1, 1, d, 2 * hb),
                             lambda i, c, j, *_: (i, c, 0, 0)),
                pl.BlockSpec((1, 1, hb, fb),
                             lambda i, c, j, *_: (i, c, 0, j)),
                pl.BlockSpec((1, 1, hb * grp, fb),
                             lambda i, c, j, *_: (i, c, 0, j)),
                state_spec],
            out_specs=[
                pl.BlockSpec((1, 1, d, hb * grp),
                             lambda i, c, j, *_: (i, c, 0, 0)),
                state_spec]),
        out_shape=[jax.ShapeDtypeStruct((rows, hkv // hb, d, hb * grp), F32),
                   jax.ShapeDtypeStruct(ret_s.shape, ret_s.dtype)],
        # operands count the three prefetched scalars: the leaf is the 7th
        input_output_aliases={6: 1},
        # two buffers each way of the state's block, and room for the rest
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=4 * block_bytes + (16 << 20)),
        # the op's name in a device trace (its share of the busy time reads it)
        name="retention_step_slots",
        interpret=impl == "interpret",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), at,
      fresh.astype(jnp.int32), cols, pk.reshape(rows, hkv // hb, hb, f),
      pq.reshape(rows, hkv // hb, hb * grp, f), ret_s)
    num = num.transpose(0, 1, 3, 2).reshape(rows, h, d)
    return num / (den[..., None] + EPS), ret_s, ret_z


def _retention_block(carry, xs):
    """One block of L <= BLOCK tokens: ((s [B, Hkv, d, F], z [B, Hkv, F]),
    (q [B, L, H, d], k, v [B, L, Hkv, d], log_g [B, L, Hkv])) -> ((s',
    z'), o [B, L, H, d])."""
    s, z = carry
    q, k, v, log_g = xs
    l, hkv = q.shape[1], k.shape[2]
    qg = _by_group(q, hkv)                               # [B, L, Hkv, G, d]
    gc = jnp.cumsum(log_g, axis=1)                       # G_t [B, L, Hkv], <= 0
    # pairwise decays exp(G_t - G_i), i <= t (<= 1); zero above the
    # diagonal, where the difference is positive and unbounded
    lower = jnp.tril(jnp.ones((l, l), bool))
    diff = gc[:, :, None] - gc[:, None, :]               # [B, t, i, Hkv]
    decay = jnp.where(lower[None, :, :, None],
                      jnp.exp(jnp.minimum(diff, 0.0)), 0.0)
    qk = _einsum("btcgd,bicd->bticg", qg, k)
    w = decay[..., None] * qk * qk                       # [B, t, i, Hkv, G]
    pq = _by_group(phi(q), hkv)                          # [B, L, Hkv, G, F]
    eg = jnp.exp(gc)[..., None]                          # [B, L, Hkv, 1]
    num = _einsum("bticg,bicv->btcgv", w, v) \
        + eg[..., None] * _einsum("btcgf,bcvf->btcgv", pq, s)
    den = jnp.sum(w, axis=2) + eg * _einsum("btcgf,bcf->btcg", pq, z)
    last = gc[:, -1]                                     # G_L [B, Hkv]
    pk = jnp.exp(last[:, None] - gc)[..., None] * phi(k)  # [B, L, Hkv, F]
    s = jnp.exp(last)[..., None, None] * s \
        + _einsum("bicv,bicf->bcvf", v, pk)
    z = jnp.exp(last)[..., None] * z + jnp.sum(pk, axis=1)
    return (s, z), (num / (den[..., None] + EPS)).reshape(q.shape)


def retention_chunk(q, k, v, log_g, s, z, block: int = BLOCK):
    """A chunk of T tokens a row. q [B, T, H, d], k, v [B, T, Hkv, d],
    log_g [B, T, Hkv], padding tokens already neutral (log g = 0, k = v =
    0: module docstring), s [B, Hkv, d, F], z [B, Hkv, F], float32 -> (o
    [B, T, H, d], s', z'). T is a multiple of `block` or smaller than
    it."""
    t = q.shape[1]
    if t <= block:
        (s, z), o = _retention_block((s, z), (q, k, v, log_g))
        return o, s, z
    n = t // block

    def blocks(a):      # [B, T, ...] -> [n, B, block, ...]
        return jnp.moveaxis(
            a.reshape((a.shape[0], n, block) + a.shape[2:]), 1, 0)

    (s, z), o = jax.lax.scan(_retention_block, (s, z), tuple(
        blocks(a) for a in (q, k, v, log_g)))
    o = jnp.moveaxis(o, 0, 1)                            # [B, n, block, ..]
    return o.reshape((o.shape[0], t) + o.shape[3:]), s, z
