"""The slot-addressed one-token state update (`ops/linear_attention.
kda_step_slots`): the Pallas kernel's body, run in the interpreter on the
CPU, against the definition `kda_step` on the rows' states gathered by
slot, and over successive steps against the per-token recurrence of
`models/reference.attention_kda`.

Tolerance. Both sides compute in float32 from the same numbers; the
kernel sums a head's dk products in another order than the definition's
matmul, so an element of `o`, O(1), differs by a few units in the last
place times sqrt(dk): 4e-6 was the largest read at dk = 128, 2e-5 the
limit (and on the touched states, whose elements are one product and
one sum from the operands). Eight steps hand the difference on through
the recurrence: 1e-4. Everything NOT touched is held to the bit.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models import reference
from dynamo_tpu.ops import linear_attention as la

ATOL = 2e-5
LOWER_BOUND = -5.0
# (heads, dk, dv, heads a block): small, a head block of its own, and the
# served head (128 x 128) in two blocks of one
SHAPES = [(4, 16, 16, 2), (4, 16, 16, 4), (8, 16, 32, 8), (2, 128, 128, 1)]
LAYERS, SLOTS, ROWS = 3, 11, 6     # the leaf holds SLOTS + the scratch slot


def operands(rng, b, h, dk, dv, g=None):
    def f(*shape):
        return rng.normal(size=shape).astype(np.float32)
    q = np.asarray(la.l2_normalize(f(b, h, dk))) * dk ** -0.5
    k = np.asarray(la.l2_normalize(f(b, h, dk)))
    if g is None:
        g = LOWER_BOUND / (1.0 + np.exp(-f(b, h, dk)))
    beta = 1.0 / (1.0 + np.exp(-f(b, h)))
    return tuple(jnp.asarray(a, jnp.float32)
                 for a in (q, k, f(b, h, dv), g, beta))


def leaf(rng, h, dk, dv):
    return jnp.asarray(rng.normal(size=(LAYERS, SLOTS + 1, h, dk, dv)),
                       jnp.float32)


def kernel(kda_s, lk, slots, ops, fresh=None, hb=2, impl="interpret"):
    return jax.jit(lambda s, lk, sl, fr, *o: la.kda_step_slots(
        s, lk, sl, *o, fresh=fr, impl=impl, heads_per_block=hb))(
            kda_s, lk, jnp.asarray(slots, jnp.int32),
            None if fresh is None else jnp.asarray(fresh), *ops)


def untouched(before, after, lk, touched):
    """Every (layer, slot) but the touched slots of layer `lk`, the
    scratch slot among them, keeps every bit."""
    before, after = np.asarray(before), np.asarray(after)
    for layer in range(before.shape[0]):
        for slot in range(before.shape[1]):
            if layer == lk and slot in touched:
                assert not np.array_equal(after[layer, slot],
                                          before[layer, slot])
            else:
                np.testing.assert_array_equal(
                    after[layer, slot].view(np.uint32),
                    before[layer, slot].view(np.uint32))


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("shape", SHAPES, ids=str)
def test_a_permutation_of_slots_matches_the_definition(shape, seed):
    h, dk, dv, hb = shape
    rng = np.random.default_rng(seed)
    kda_s, ops = leaf(rng, h, dk, dv), operands(rng, ROWS, h, dk, dv)
    slots = rng.permutation(SLOTS)[:ROWS]
    lk = seed % LAYERS
    o, after = kernel(kda_s, lk, slots, ops, hb=hb)
    want_o, want_s = la.kda_step(*ops, kda_s[lk, slots])
    np.testing.assert_allclose(o, want_o, atol=ATOL, rtol=0)
    np.testing.assert_allclose(after[lk, slots], want_s, atol=ATOL, rtol=0)
    untouched(kda_s, after, lk, set(slots.tolist()))


@pytest.mark.parametrize("impl", ["interpret", "plain"])
@pytest.mark.parametrize("shape", SHAPES[:2] + SHAPES[3:], ids=str)
def test_dead_rows_change_nothing_and_read_the_scratch_slot(shape, impl):
    """A dead row (slot -1) writes no sequence's state, and what it
    reads is the scratch slot's: with NaN there its own output is NaN,
    every live row's is clean, and with zeros there it is zero."""
    h, dk, dv, hb = shape
    rng = np.random.default_rng(3)
    kda_s, ops = leaf(rng, h, dk, dv), operands(rng, ROWS, h, dk, dv)
    slots = np.array([4, -1, 0, -1, 9, -1])
    live = slots >= 0
    o, after = kernel(kda_s, 1, slots, ops, hb=hb, impl=impl)
    want_o, want_s = la.kda_step(*(a[live] for a in ops),
                                 kda_s[1, slots[live]])
    np.testing.assert_allclose(o[live], want_o, atol=ATOL, rtol=0)
    np.testing.assert_allclose(after[1, slots[live]], want_s, atol=ATOL,
                               rtol=0)
    untouched(kda_s, after, 1, set(slots[live].tolist()))
    poisoned = kda_s.at[:, SLOTS].set(jnp.nan)
    o_nan, after_nan = kernel(poisoned, 1, slots, ops, hb=hb, impl=impl)
    np.testing.assert_array_equal(o_nan[live], o[live])
    assert np.isnan(np.asarray(o_nan[~live])).all()
    np.testing.assert_array_equal(after_nan[:, :SLOTS], after[:, :SLOTS])
    o_zero, _ = kernel(kda_s.at[:, SLOTS].set(0.0), 1, slots, ops, hb=hb,
                       impl=impl)
    assert not np.asarray(o_zero[~live]).any()


@pytest.mark.parametrize("impl", ["interpret", "plain"])
@pytest.mark.parametrize("garbage", [3.0, np.inf, np.nan])
def test_a_fresh_row_starts_from_zeros_whatever_its_slot_held(garbage, impl):
    h, dk, dv, hb = SHAPES[0]
    rng = np.random.default_rng(4)
    kda_s, ops = leaf(rng, h, dk, dv), operands(rng, ROWS, h, dk, dv)
    slots = np.array([7, 2, 5, 0, 10, 3])
    fresh = np.array([False, True, False, True, False, False])
    dirty = kda_s.at[:, slots[fresh]].set(garbage)
    o, after = kernel(dirty, 2, slots, ops, fresh=fresh, hb=hb, impl=impl)
    start = jnp.where(fresh[:, None, None, None], 0.0, kda_s[2, slots])
    want_o, want_s = la.kda_step(*ops, start)
    np.testing.assert_allclose(o, want_o, atol=ATOL, rtol=0)
    np.testing.assert_allclose(after[2, slots], want_s, atol=ATOL, rtol=0)
    assert np.isfinite(np.asarray(after[2])).all()


@pytest.mark.parametrize("g", [LOWER_BOUND, 0.0, -1e-7])
@pytest.mark.parametrize("shape", [SHAPES[0], SHAPES[3]], ids=str)
def test_decays_at_their_bounds(shape, g):
    """Every channel at the lower bound (a = e^-5: the state all but
    forgotten in a token), at none, and next to none."""
    h, dk, dv, hb = shape
    rng = np.random.default_rng(5)
    kda_s = leaf(rng, h, dk, dv)
    ops = operands(rng, ROWS, h, dk, dv,
                   g=np.full((ROWS, h, dk), g, np.float32))
    slots = np.array([1, 8, 3, 6, 0, 10])
    o, after = kernel(kda_s, 0, slots, ops, hb=hb)
    want_o, want_s = la.kda_step(*ops, kda_s[0, slots])
    np.testing.assert_allclose(o, want_o, atol=ATOL, rtol=0)
    np.testing.assert_allclose(after[0, slots], want_s, atol=ATOL, rtol=0)


@pytest.mark.parametrize("impl", ["interpret", "plain"])
@pytest.mark.parametrize("shape", [SHAPES[1], SHAPES[3]], ids=str)
def test_eight_steps_follow_the_reference_recurrence(shape, impl):
    """Eight tokens a row through the leaf, rows coming and going (a row
    is dead on some steps, one starts fresh on the third), against the
    per-token recurrence `models/reference.attention_kda` scans."""
    h, dk, dv, hb = shape
    rng = np.random.default_rng(6)
    kda_s = leaf(rng, h, dk, dv)
    slots = np.array([9, 4, 0, 6, 2, 7])
    want_s = [np.asarray(kda_s[1, s]) for s in slots]
    for t in range(8):
        ops = operands(rng, ROWS, h, dk, dv)
        live = np.array([(t + i) % 4 != 0 for i in range(ROWS)])
        fresh = np.array([t == 2 and i == 3 for i in range(ROWS)])
        o, kda_s = kernel(kda_s, 1, np.where(live, slots, -1), ops,
                          fresh=fresh, hb=hb, impl=impl)
        for i in np.flatnonzero(live):
            start = np.zeros_like(want_s[i]) if fresh[i] else want_s[i]
            want_s[i], want_o = reference.kda_recurrence(
                jnp.asarray(start), tuple(a[i] for a in ops))
            np.testing.assert_allclose(o[i], want_o, atol=1e-4, rtol=0)
    for i, s in enumerate(slots):
        np.testing.assert_allclose(kda_s[1, s], want_s[i], atol=1e-4,
                                   rtol=0)


def test_the_form_is_chosen_by_the_backend_alone():
    assert jax.default_backend() == "cpu"
    assert la.kda_step_slots_impl() == "plain"
    h, dk, dv, _ = SHAPES[0]
    rng = np.random.default_rng(7)
    kda_s, ops = leaf(rng, h, dk, dv), operands(rng, ROWS, h, dk, dv)
    slots = np.array([5, -1, 1, 3, -1, 8])
    o, after = kernel(kda_s, 0, slots, ops, impl=None)
    o_k, after_k = kernel(kda_s, 0, slots, ops, impl="interpret")
    np.testing.assert_allclose(o, o_k, atol=ATOL, rtol=0)
    np.testing.assert_allclose(after, after_k, atol=ATOL, rtol=0)
