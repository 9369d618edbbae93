"""The sampler tail's keep mask, held to the code it replaced.

`sampler.keep_mask` builds the top-k / top-p mask in token space from a
per-row cutoff and a tie rule. The oracle below is the `sample()` of
before, copied: three sorts, the mask built in sorted order and carried
back by a `[B, V]` gather through the ranks. Mask and tokens at fixed keys
have to agree element for element, ties at the cutoff included.

The same file runs on the chip (`python3 -m pytest --noconftest
tests/test_sampler_tail.py` there: conftest.py holds JAX to the CPU). A
TPU's cumulative sum adds in another order, so `SAMPLER_TAIL_REPORT` names
a file in which every case leaves its platform and its count of rows whose
`sorted_keep` was not a prefix.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.analysis.jaxpr_audit import iter_eqns
from dynamo_tpu.engine import sampler

NEG_INF = sampler.NEG_INF


def oracle(logits, temperature, top_k, top_p, keys):
    """sample() as it stood before, returning what the test compares:
    (keep [B, V], tokens [B], sorted_keep [B, V])."""
    b, v = logits.shape
    greedy_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    temp = jnp.maximum(temperature, 1e-6)[:, None]
    scaled = logits / temp

    sorted_logits = jnp.sort(scaled, axis=-1)[:, ::-1]            # [B, V] desc
    ranks = jnp.argsort(jnp.argsort(scaled, axis=-1)[:, ::-1], axis=-1)

    # top-k: keep ranks < k (k==0 disables)
    k = jnp.where(top_k > 0, top_k, v)[:, None]
    keep_k = ranks < k

    # top-p: keep the smallest prefix of sorted probs with cumsum >= top_p,
    # always keeping the argmax.
    sorted_probs = jax.nn.softmax(sorted_logits, axis=-1)
    cumprobs = jnp.cumsum(sorted_probs, axis=-1)
    sorted_keep = (cumprobs - sorted_probs) < top_p[:, None]
    keep_p = jnp.take_along_axis(sorted_keep, ranks, axis=-1)

    keep = keep_k & keep_p
    masked = jnp.where(keep, scaled, NEG_INF)
    sampled = jax.vmap(
        lambda k, row: jax.random.categorical(k, row)
    )(keys, masked).astype(jnp.int32)
    return (keep, jnp.where(temperature <= 0.0, greedy_tok, sampled),
            sorted_keep)


def tail(logits, temperature, top_k, top_p, keys):
    """The code under test, returning (keep [B, V], tokens [B])."""
    scaled = logits / jnp.maximum(temperature, 1e-6)[:, None]
    return (sampler.keep_mask(scaled, top_k, top_p),
            sampler.sample(logits, temperature, top_k, top_p, keys))


# one row of each kind, cycled over the batch: (temperature, top_k, top_p)
ROW_KINDS = [
    (0.7, 0, 0.95),     # the benchmark's every request
    (1.0, 50, 1.0),     # top_k only
    (0.8, 0, 0.9),      # top_p only
    (1.3, 5, 0.5),      # both
    (1.0, 0, 1.0),      # neither
    (0.0, 0, 0.95),     # temperature 0: greedy
    (0.7, 0, 1e-6),     # the argmax alone
    (0.7, 0, 0.0),      # top_p 0: the kept set is empty
    (0.7, 1, 0.95),     # top_k 1
    (0.0, 50, 1.0),     # greedy with a top_k
]


def params(b):
    rows = [ROW_KINDS[i % len(ROW_KINDS)] for i in range(b)]
    t, k, p = zip(*rows)
    return (jnp.asarray(t, jnp.float32), jnp.asarray(k, jnp.int32),
            jnp.asarray(p, jnp.float32))


def make_logits(kind, b, v, seed):
    rng = np.random.default_rng(seed)
    x = (rng.standard_normal((b, v)) * 3.0).astype(np.float32)
    if kind == "bf16":
        # logits that went through bfloat16 hold many equal values, some
        # of them at the cutoff
        x = np.asarray(jnp.asarray(x).astype(jnp.bfloat16).astype(jnp.float32))
    elif kind == "all_equal":
        x[0::2] = 1.25
    elif kind == "banned":
        # the eos ban's -1e30 columns, and a row of little else
        x[:, rng.integers(0, v, size=7)] = NEG_INF
        x[b // 2, 3:] = NEG_INF
    elif kind == "coarse":
        # a few dozen distinct values a row: every cutoff is a tie
        x = np.round(x * 2.0) / 2.0
    else:
        assert kind == "f32", kind
    return jnp.asarray(x)


def leading_run(sorted_keep):
    """Length of each row's leading run of True."""
    sk = np.asarray(sorted_keep)
    return np.where(sk.all(-1), sk.shape[1], sk.argmin(-1))


def first_n_of_the_order(row, n):
    """The first n tokens of one row in the oracle's order, in numpy."""
    keep = np.zeros(row.shape, bool)
    keep[np.argsort(row, kind="stable")[::-1][:n]] = True
    return keep


def report(case, **fields):
    path = os.environ.get("SAMPLER_TAIL_REPORT")
    if path:
        with open(path, "a") as f:
            f.write(json.dumps({"case": case, **fields}) + "\n")


@pytest.mark.parametrize("kind",
                         ["f32", "bf16", "all_equal", "banned", "coarse"])
@pytest.mark.parametrize("v", [32000, 50304])
@pytest.mark.parametrize("b", [1, 8, 16, 32])
def test_mask_and_tokens_equal_the_oracle(b, v, kind):
    seed = 1000 * b + v % 997 + len(kind)
    logits = make_logits(kind, b, v, seed)
    temperature, top_k, top_p = params(b)
    keys = sampler.make_keys(jnp.arange(b, dtype=jnp.int32) + seed,
                             jnp.arange(b, dtype=jnp.int32) * 3)
    args = (logits, temperature, top_k, top_p, keys)
    want_keep, want_tok, sorted_keep = jax.jit(oracle)(*args)
    got_keep, got_tok = jax.jit(tail)(*args)
    want_keep, got_keep = np.asarray(want_keep), np.asarray(got_keep)

    # where the oracle's sorted_keep is a prefix (everywhere, unless a
    # rounded cumulative sum dips) the masks agree element for element
    run = leading_run(sorted_keep)
    is_prefix = np.asarray(sorted_keep).sum(-1) == run
    mismatched = int((want_keep != got_keep)[is_prefix].sum())
    report(f"{b}-{v}-{kind}", platform=jax.devices()[0].platform,
           rows=b, rows_not_a_prefix=int((~is_prefix).sum()),
           mask_mismatches=mismatched,
           token_mismatches=int(
               (np.asarray(want_tok) != np.asarray(got_tok)).sum()))
    assert mismatched == 0
    assert np.array_equal(np.asarray(want_tok)[is_prefix],
                          np.asarray(got_tok)[is_prefix])
    # a row with top_p 0 keeps nothing, as it did
    assert not got_keep[np.asarray(top_p) == 0.0].any()
    # the prefix is the meaning: on any other row the mask is the first
    # min(k, leading run) tokens of the same order
    k = np.where(np.asarray(top_k) > 0, np.asarray(top_k), v)
    scaled = np.asarray(logits / jnp.maximum(temperature, 1e-6)[:, None])
    for r in np.nonzero(~is_prefix)[0]:
        want = first_n_of_the_order(scaled[r], min(k[r], run[r]))
        assert np.array_equal(want, got_keep[r])


def test_one_value_sort_and_no_full_vocabulary_gather():
    """The jaxpr of sample(): one sort over the vocabulary, of values alone
    (an argsort is a sort with a second operand), and no gather or scatter
    whose result is [B, V]. Fails on the tail it replaced."""
    b, v = 8, 32000
    temperature, top_k, top_p = params(b)
    keys = sampler.make_keys(jnp.arange(b, dtype=jnp.int32),
                             jnp.arange(b, dtype=jnp.int32))
    jaxpr = jax.make_jaxpr(sampler.sample)(
        jnp.zeros((b, v), jnp.float32), temperature, top_k, top_p, keys)
    sorts, moved = [], []
    for eqn in iter_eqns(jaxpr.jaxpr):
        name = eqn.primitive.name
        if name == "sort":
            sorts.append(len(eqn.invars))
        elif name == "gather" or name.startswith("scatter"):
            moved += [name for out in eqn.outvars
                      if tuple(out.aval.shape) == (b, v)]
    assert sorts == [1], sorts
    assert moved == [], moved
