"""Power retention's three forms (`ops/power_retention.py`) against each
other and against the masked quadratic form of the plain reference, and
the slot-addressed one-token update's kernel body, run in the Pallas
interpreter on the CPU, against the definition `retention_step` on the
rows' states gathered by slot.

Tolerance. Every side computes in float32 from the same numbers. The
quadratic form weighs by (q . k)^2 directly; the recurrent forms by phi(q)
. phi(k), F products whose sum is that square after cancellation: at d =
16 and unit-variance q and k a weight is ~16 and its terms sum to ~256, so
an element of o, O(1), differs by ~16 units in the last place times
sqrt(F): 3e-5 was the largest read over the sequences here, 2e-4 the limit.
The kernel sums a head's features in another order than the definition's
matmul: 2.3e-5 read, 1e-4 the limit, and on the touched states (one
product and one sum from the operands) 1e-6 read, 1e-5 the limit.
Everything NOT touched is held to the bit.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models import reference
from dynamo_tpu.ops import power_retention as pr

ATOL = 2e-4
LAYERS, SLOTS, ROWS = 3, 9, 5      # the leaf holds SLOTS + the scratch slot


def inputs(rng, b, t, h=4, hkv=2, d=16):
    def f(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)
    # g = 0.6 .. 0.999: some heads forget within a few tokens, some hardly
    log_g = jnp.log(jax.nn.sigmoid(0.5 + 3.0 * jnp.abs(f(b, t, hkv))))
    return f(b, t, h, d), f(b, t, hkv, d), f(b, t, hkv, d), log_g


def quadratic(q, k, v, log_g):
    """The reference's form in float64: no state, no features."""
    q, k, v, log_g = (np.asarray(a, np.float64) for a in (q, k, v, log_g))
    b, t, h, _ = q.shape
    grp = h // k.shape[2]
    gc = np.cumsum(log_g, axis=1)
    out = np.zeros(q.shape)
    for r in range(b):
        for head in range(h):
            c = head // grp
            for i in range(t):
                w = np.exp(gc[r, i, c] - gc[r, :i + 1, c]) \
                    * (k[r, :i + 1, c] @ q[r, i, head]) ** 2
                out[r, i, head] = w @ v[r, :i + 1, c] / w.sum()
    return out


# -- (i) the features ------------------------------------------------------------

@pytest.mark.parametrize("d", [2, 4, 16, 32, 128])
def test_phi_is_the_degree_two_embedding(d):
    """phi(x) . phi(y) = (x . y)^2 at d (d / 2 + 1) features, the same
    features in the same places as the reference's index form."""
    rng = np.random.default_rng(d)
    x, y = rng.normal(size=(2, 7, d)).astype(np.float32)
    px, py = np.asarray(pr.phi(x), np.float64), np.asarray(pr.phi(y),
                                                           np.float64)
    assert px.shape == (7, pr.features(d)) == (7, d * (d // 2 + 1))
    want = np.sum(x.astype(np.float64) * y, axis=-1) ** 2
    np.testing.assert_allclose(np.sum(px * py, axis=-1), want,
                               rtol=1e-5, atol=1e-5 * d * d)
    np.testing.assert_allclose(
        px, np.asarray(reference.retention_features(jnp.asarray(x))),
        rtol=1e-6)


def test_the_features_held_are_whole_lane_tiles_at_the_served_head():
    """8320 = 65 x 128: the 8256 distinct products and 64 repeats, never
    the 16 384 of the plain outer product; an odd head is refused."""
    assert pr.features(128) == 8320 == 65 * 128
    assert 128 * 129 // 2 == 8256 < pr.features(128) < 128 * 128
    with pytest.raises(ValueError, match="even head size"):
        pr.features(15)


# -- (i) three derivations of one function ---------------------------------------

@pytest.mark.parametrize("seed", [0, 1])
def test_the_recurrence_is_the_quadratic_form(seed):
    """`retention_step` a token at a time from a state of zeros, gate
    included, against the masked quadratic form."""
    rng = np.random.default_rng(seed)
    q, k, v, log_g = inputs(rng, 2, 40)
    f = pr.features(16)
    s, z = jnp.zeros((2, 2, 16, f)), jnp.zeros((2, 2, f))
    got = []
    for t in range(40):
        o, s, z = pr.retention_step(q[:, t], k[:, t], v[:, t], log_g[:, t],
                                    s, z)
        got.append(np.asarray(o))
    np.testing.assert_allclose(np.stack(got, axis=1),
                               quadratic(q, k, v, log_g), atol=ATOL)
    # and the state itself is the reference's recurrence
    want_s, want_z = reference.retention_state(k[0], v[0], log_g[0])
    np.testing.assert_allclose(np.asarray(s[0]), want_s, rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(z[0]), want_z, rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("lengths, chunk, block", [
    ((70, 33, 1), 32, 16), ((70, 33, 1), 64, 64), ((40, 5, 17), 16, 8),
    ((130, 64, 129), 128, 64)], ids=str)
def test_the_chunk_form_is_the_per_token_form(lengths, chunk, block):
    """`retention_chunk` over chunks of `chunk` tokens (blocks of `block`)
    with the state carried from chunk to chunk, rows of different lengths
    that are no multiple of either and padded at log g = 0, k = v = 0,
    against the quadratic form and, on the state, against
    `retention_step` from the same tokens."""
    rng = np.random.default_rng(len(lengths) + chunk)
    t_max = -(-max(lengths) // chunk) * chunk
    q, k, v, log_g = inputs(rng, len(lengths), t_max)
    valid = jnp.arange(t_max)[None, :] < jnp.asarray(lengths)[:, None]
    m = valid[:, :, None, None]
    k, v = jnp.where(m, k, 0.0), jnp.where(m, v, 0.0)
    log_g = jnp.where(valid[:, :, None], log_g, 0.0)
    f = pr.features(16)
    s = jnp.zeros((len(lengths), 2, 16, f))
    z = jnp.zeros((len(lengths), 2, f))
    s_t, z_t = s, z
    got = []
    for lo in range(0, t_max, chunk):
        o, s, z = pr.retention_chunk(
            q[:, lo:lo + chunk], k[:, lo:lo + chunk], v[:, lo:lo + chunk],
            log_g[:, lo:lo + chunk], s, z, block=block)
        got.append(np.asarray(o))
    for t in range(t_max):
        _, s_next, z_next = pr.retention_step(
            q[:, t], k[:, t], v[:, t], log_g[:, t], s_t, z_t)
        # a row past its end keeps its state: the padding IS that
        np.testing.assert_array_equal(
            np.asarray(s_next)[~np.asarray(valid[:, t])],
            np.asarray(s_t)[~np.asarray(valid[:, t])])
        s_t, z_t = s_next, z_next
    mask = np.asarray(valid)
    want = quadratic(q, k, v, log_g)
    np.testing.assert_allclose(np.concatenate(got, axis=1)[mask], want[mask],
                               atol=ATOL)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_t), rtol=2e-5,
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(z), np.asarray(z_t), rtol=2e-5,
                               atol=2e-5)


# -- (ii) the slot-addressed form -------------------------------------------------

# (query heads, key-value heads, head size, heads a block, features a
# block; None: all): small, a block of its own a head, a feature axis in
# blocks, and the served head (128 -> 8320 = 5 x 1664) in blocks of both
SHAPES = [(4, 2, 16, 1, None), (4, 2, 16, 2, None), (6, 2, 16, 1, None),
          (2, 2, 128, 1, 1664), (4, 2, 128, 2, 1664)]


def leaves(rng, hkv, d):
    f = pr.features(d)
    return (jnp.asarray(rng.normal(size=(LAYERS, SLOTS + 1, hkv, d, f)),
                        jnp.float32),
            jnp.asarray(np.abs(rng.normal(size=(LAYERS, SLOTS + 1, hkv, f)))
                        + 1.0, jnp.float32))


def operands(rng, h, hkv, d, rows=ROWS):
    q, k, v, log_g = inputs(rng, rows, 1, h, hkv, d)
    return q[:, 0], k[:, 0], v[:, 0], log_g[:, 0]


def kernel(state, layer, slots, ops, fresh=None, hb=1, fb=None,
           impl="interpret"):
    return jax.jit(lambda s, z, l, sl, fr, *o: pr.retention_step_slots(
        s, z, l, sl, *o, fresh=fr, impl=impl, heads_per_block=hb,
        features_per_block=fb or s.shape[-1]))(
            *state, layer, jnp.asarray(slots, jnp.int32),
            None if fresh is None else jnp.asarray(fresh), *ops)


def untouched(before, after, layer, touched):
    """Every (layer, slot) but the touched slots of `layer`, the scratch
    slot among them, keeps every bit."""
    before, after = np.asarray(before), np.asarray(after)
    for l in range(before.shape[0]):
        for slot in range(before.shape[1]):
            if l == layer and slot in touched:
                assert not np.array_equal(after[l, slot], before[l, slot])
            else:
                np.testing.assert_array_equal(
                    after[l, slot].view(np.uint32),
                    before[l, slot].view(np.uint32))


@pytest.mark.parametrize("impl", ["interpret", "plain"])
@pytest.mark.parametrize("shape", SHAPES, ids=str)
def test_a_permutation_of_slots_matches_the_definition(shape, impl):
    h, hkv, d, hb, fb = shape
    rng = np.random.default_rng(h + d)
    state, ops = leaves(rng, hkv, d), operands(rng, h, hkv, d)
    slots = rng.permutation(SLOTS)[:ROWS]
    layer = d % LAYERS
    o, s1, z1 = kernel(state, layer, slots, ops, hb=hb, fb=fb, impl=impl)
    want_o, want_s, want_z = pr.retention_step(
        *ops, state[0][layer, slots], state[1][layer, slots])
    np.testing.assert_allclose(o, want_o, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(s1[layer, slots], want_s, atol=1e-5)
    np.testing.assert_allclose(z1[layer, slots], want_z, atol=1e-5)
    untouched(state[0], s1, layer, set(slots.tolist()))
    untouched(state[1], z1, layer, set(slots.tolist()))


@pytest.mark.parametrize("impl", ["interpret", "plain"])
def test_dead_rows_name_the_scratch_slot_and_change_nothing(impl):
    """Rows at slot -1 (padding, finished, another form's) between live
    rows: the live rows' slots move as the definition says, and no other
    slot of the leaf, the scratch slot among them, changes a bit."""
    rng = np.random.default_rng(5)
    state, ops = leaves(rng, 2, 16), operands(rng, 4, 2, 16)
    slots = np.asarray([3, -1, 0, -1, 7])
    o, s1, z1 = kernel(state, 1, slots, ops, impl=impl)
    live = slots >= 0
    want_o, want_s, _ = pr.retention_step(
        *(a[live] for a in ops), state[0][1, slots[live]],
        state[1][1, slots[live]])
    np.testing.assert_allclose(np.asarray(o)[live], want_o, atol=1e-4,
                               rtol=1e-4)
    np.testing.assert_allclose(s1[1, slots[live]], want_s, atol=1e-5)
    assert np.isfinite(np.asarray(o)).all()      # a dead row's o is dropped
    untouched(state[0], s1, 1, set(slots[live].tolist()))
    untouched(state[1], z1, 1, set(slots[live].tolist()))


@pytest.mark.parametrize("impl", ["interpret", "plain"])
def test_a_fresh_row_starts_from_zeros_whatever_its_slot_held(impl):
    rng = np.random.default_rng(6)
    state, ops = leaves(rng, 2, 16), operands(rng, 4, 2, 16)
    slots = np.asarray([2, 5, 1, 8, 0])
    fresh = np.asarray([False, True, False, True, False])
    o, s1, z1 = kernel(state, 0, slots, ops, fresh=fresh, impl=impl)
    s0 = np.array(state[0][0, slots])
    z0 = np.array(state[1][0, slots])
    s0[fresh], z0[fresh] = 0.0, 0.0
    want_o, want_s, want_z = pr.retention_step(*ops, jnp.asarray(s0),
                                               jnp.asarray(z0))
    np.testing.assert_allclose(o, want_o, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(s1[0, slots], want_s, atol=1e-5)
    np.testing.assert_allclose(z1[0, slots], want_z, atol=1e-5)
    # a first token's quotient is its own value, whatever the key
    np.testing.assert_allclose(
        np.asarray(o)[fresh],
        np.repeat(np.asarray(ops[2])[fresh], 2, axis=1), atol=1e-4)


def test_steps_in_place_follow_the_references_recurrence():
    """Eight tokens a row through the kernel, each from the state the
    last left in the leaf, against the quadratic form over the eight."""
    rng = np.random.default_rng(8)
    q, k, v, log_g = inputs(rng, 3, 8)
    state = tuple(jnp.zeros_like(a) for a in leaves(rng, 2, 16))
    slots = np.asarray([4, 0, 6])
    got = []
    for t in range(8):
        o, *state = kernel(tuple(state), 2, slots,
                           (q[:, t], k[:, t], v[:, t], log_g[:, t]),
                           fresh=np.full(3, t == 0), hb=2)
        got.append(np.asarray(o))
    np.testing.assert_allclose(np.stack(got, axis=1),
                               quadratic(q, k, v, log_g), atol=ATOL)


@pytest.mark.parametrize("hb, fb", [(1, 100), (1, 128), (1, 72)])
def test_a_block_that_does_not_tile_the_state_is_refused(hb, fb):
    """F = 144 at d = 16: a block of 100 or of 128 features does not
    divide it, and one of 72 does but is no whole lane tile: refused,
    never padded (a ragged last block would read past the leaf)."""
    rng = np.random.default_rng(9)
    state, ops = leaves(rng, 2, 16), operands(rng, 4, 2, 16)
    with pytest.raises(ValueError, match="does not tile"):
        kernel(state, 0, np.arange(ROWS), ops, hb=hb, fb=fb)
