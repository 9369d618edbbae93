"""The sampler tail's keep mask, held to its meaning and to the code before it.

`sampler.keep_mask` finds the top-k / top-p cut of a row by a search over
values (no sort) and builds the mask in token space from the cut and a tie
rule. What every case is held to:

1. the mask is EXACTLY a prefix of the one order (descending by value,
   equal values by descending token id): its first n' tokens, n' <= k;
2. n' lies in the float64 band: with the nucleus length n64(p) computed in
   numpy float64 from the float32 `scaled`, n64(top_p - 1e-5) <= n' <=
   n64(top_p + 1e-5), capped by k (a row with top_p >= 1 keeps all that k
   allows, a row with top_p 0 nothing). Only the ORDER in which float32
   adds the probabilities differs between the search (a tree over the
   entries above a threshold), the sorted cumulative sum it replaced, and
   a TPU's cumulative sum: the band is what they may differ by;
3. wherever that band is one value, mask AND sampled tokens equal the
   oracle's element for element. The oracle is the `sample()` of before
   PR 28, copied: three sorts, the mask built in sorted order and carried
   back by a `[B, V]` gather through the ranks, with the one amendment
   that top_p >= 1.0 means disabled, as the parameter is documented.

`one_sort_keep_mask` is PR 28's tail (one value sort), kept as a second
oracle for `tools/sampler_tail_bench.py` to time.

The same file runs on the chip (`python3 -m pytest --noconftest
tests/test_sampler_tail.py` there: conftest.py holds JAX to the CPU).
`SAMPLER_TAIL_REPORT` names a file in which every case leaves its platform
and its counts.
"""
import json
import os
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.analysis.jaxpr_audit import iter_eqns
from dynamo_tpu.engine import sampler

NEG_INF = sampler.NEG_INF


def oracle(logits, temperature, top_k, top_p, keys):
    """sample() as it stood before PR 28 (top_p >= 1.0: disabled),
    returning what the test compares: (keep [B, V], tokens [B])."""
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
    sorted_keep = (((cumprobs - sorted_probs) < top_p[:, None])
                   | (top_p >= 1.0)[:, None])
    keep_p = jnp.take_along_axis(sorted_keep, ranks, axis=-1)

    keep = keep_k & keep_p
    masked = jnp.where(keep, scaled, NEG_INF)
    sampled = jax.vmap(
        lambda k, row: jax.random.categorical(k, row)
    )(keys, masked).astype(jnp.int32)
    return keep, jnp.where(temperature <= 0.0, greedy_tok, sampled)


def one_sort_keep_mask(scaled, top_k, top_p):
    """sampler.keep_mask as PR 28 left it: one descending value sort, a
    softmax and a cumulative sum over the sorted block, the cut taken
    from it. Timed by tools/sampler_tail_bench.py."""
    v = scaled.shape[-1]
    sorted_logits = jnp.sort(scaled, axis=-1)[:, ::-1]            # [B, V] desc
    k = jnp.where(top_k > 0, top_k, v)
    sorted_probs = jax.nn.softmax(sorted_logits, axis=-1)
    cumprobs = jnp.cumsum(sorted_probs, axis=-1)
    sorted_keep = (cumprobs - sorted_probs) < top_p[:, None]
    first_out = jnp.where(sorted_keep, v, jnp.arange(v, dtype=jnp.int32))
    n = jnp.minimum(k, jnp.min(first_out, axis=-1))               # [B]
    cut = jnp.take_along_axis(
        sorted_logits, jnp.maximum(n - 1, 0)[:, None], axis=-1)   # [B, 1]
    above = scaled > cut
    tie = scaled == cut
    need = n - jnp.sum(above, axis=-1, dtype=jnp.int32)
    ties_from_here_up = jax.lax.cumsum(
        tie.astype(jnp.int32), axis=1, reverse=True)
    return above | (tie & (ties_from_here_up <= need[:, None]))


def one_sort_sample(*args):
    """sampler.sample over `one_sort_keep_mask` (swapped in while it is
    traced: `sample` stays the one definition of the rest of the tail)."""
    with mock.patch.object(sampler, "keep_mask", one_sort_keep_mask):
        return sampler.sample(*args)


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
    elif kind == "zeros":
        # a tenth small positives, a third far below, the rest +0.0 and
        # -0.0 mixed: most rows' cut is the one value zero
        u = rng.random((b, v))
        x = np.where(u < 0.1, rng.random((b, v)) * 0.5,
                     np.where(u < 0.4, -20.0 - np.abs(x),
                              np.where(rng.random((b, v)) < 0.5, 0.0, -0.0))
                     ).astype(np.float32)
    else:
        assert kind == "f32", kind
    return jnp.asarray(x)


BAND = 1e-5


def nucleus_lengths64(row, ps):
    """n64(p) for each p of ps: how many tokens of one float32 row, in the
    one order, have less than p of the float64 probability mass in front
    of them (the smallest prefix that reaches p; 0 at p <= 0)."""
    order = np.argsort(row, kind="stable")[::-1]
    e = np.exp(row[order].astype(np.float64) - float(row.max()))
    before = np.concatenate([[0.0], np.cumsum(e / e.sum())[:-1]])
    return order, [int(np.searchsorted(before, p, side="left")) for p in ps]


def report(case, **fields):
    path = os.environ.get("SAMPLER_TAIL_REPORT")
    if path:
        with open(path, "a") as f:
            f.write(json.dumps({"case": case, **fields}) + "\n")


KINDS = ["f32", "bf16", "all_equal", "banned", "coarse", "zeros"]
# [rows, vocabulary] as the cells sample: Mistral / Mixtral and OLMoE at
# every row bucket, then Moonlight, Trinity, Mellum and Ling at their own
SHAPES = ([(b, v) for b in (1, 8, 16, 32) for v in (32000, 50304)]
          + [(8, 163840), (8, 200192), (8, 98304), (64, 39296)])


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("b,v", SHAPES)
def test_mask_and_tokens_equal_the_oracle(b, v, kind):
    seed = 1000 * b + v % 997 + len(kind)
    logits = make_logits(kind, b, v, seed)
    temperature, top_k, top_p = params(b)
    keys = sampler.make_keys(jnp.arange(b, dtype=jnp.int32) + seed,
                             jnp.arange(b, dtype=jnp.int32) * 3)
    args = (logits, temperature, top_k, top_p, keys)
    want_keep, want_tok = map(np.asarray, jax.jit(oracle)(*args))
    got_keep, got_tok = map(np.asarray, jax.jit(tail)(*args))
    scaled = np.asarray(logits / jnp.maximum(temperature, 1e-6)[:, None])
    top_k, top_p = np.asarray(top_k), np.asarray(top_p)

    not_a_prefix, outside_band, oracle_outside_band = [], [], []
    one_value = np.zeros((b,), bool)
    for r in range(b):
        k = min(int(top_k[r]), v) if top_k[r] > 0 else v
        p = float(top_p[r])
        order, (lo, hi) = nucleus_lengths64(scaled[r], (p - BAND, p + BAND))
        if p >= 1.0:
            lo = hi = v
        elif p <= 0.0:
            lo = hi = 0
        lo, hi = min(lo, k), min(hi, k)
        one_value[r] = lo == hi
        n = int(got_keep[r].sum())
        prefix = np.zeros((v,), bool)
        prefix[order[:n]] = True
        if not np.array_equal(prefix, got_keep[r]):
            not_a_prefix.append(r)
        if not lo <= n <= hi:
            outside_band.append((r, lo, n, hi))
        if not lo <= int(want_keep[r].sum()) <= hi:
            oracle_outside_band.append(r)
    mask_mismatches = int((want_keep != got_keep)[one_value].sum())
    token_mismatches = int((want_tok != got_tok)[one_value].sum())
    report(f"{b}-{v}-{kind}", platform=jax.devices()[0].platform, rows=b,
           rows_band_one_value=int(one_value.sum()),
           rows_not_a_prefix=len(not_a_prefix),
           rows_outside_band=len(outside_band),
           oracle_rows_outside_band=len(oracle_outside_band),
           mask_mismatches=mask_mismatches,
           token_mismatches=token_mismatches,
           token_mismatches_all_rows=int((want_tok != got_tok).sum()))
    assert not_a_prefix == []
    assert outside_band == []
    assert mask_mismatches == 0 and token_mismatches == 0
    # a row with top_p 0 keeps nothing, as it did
    assert not got_keep[top_p == 0.0].any()


def test_no_sort_and_no_full_vocabulary_gather():
    """The jaxpr of sample(): NO sort, and no gather or scatter whose
    result is [B, V]. Fails on the one-sort tail it replaced (and on the
    three-sort one before that)."""
    b, v = 8, 32000
    temperature, top_k, top_p = params(b)
    keys = sampler.make_keys(jnp.arange(b, dtype=jnp.int32),
                             jnp.arange(b, dtype=jnp.int32))
    operands = (jnp.zeros((b, v), jnp.float32), temperature, top_k, top_p,
                keys)

    def sorts_and_moves(fn):
        sorts, moved = [], []
        for eqn in iter_eqns(jax.make_jaxpr(fn)(*operands).jaxpr):
            name = eqn.primitive.name
            if name == "sort":
                sorts.append(len(eqn.invars))
            elif name == "gather" or name.startswith("scatter"):
                moved += [name for out in eqn.outvars
                          if tuple(out.aval.shape) == (b, v)]
        return sorts, moved

    assert sorts_and_moves(sampler.sample) == ([], [])
    assert sorts_and_moves(one_sort_sample) == ([1], [])
