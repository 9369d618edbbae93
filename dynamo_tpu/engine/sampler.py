"""Token sampling (greedy / temperature / top-k / top-p) as one jitted kernel.

Matches the sampling-option surface the reference forwards to its engines
(reference: lib/llm/src/protocols/common.rs:248 SamplingOptions — temperature,
top_k, top_p, seed; greedy when nvext.greed_sampling or temperature==0).

All-batch vectorized with static vocab: top-k's and top-p's one cutoff is
found by a threshold search over the row's values (`keep_mask`: a fixed
number of masked reductions, no sort of the vocabulary) and compared in
token order; XLA fuses the rest.
"""
# dynalint: hot-path — every op here runs inside jitted decode/prefill programs;
# host syncs (.item(), device_get, float()) are dynalint R6 findings
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30

# static top-k width for logprob alternatives (OpenAI caps top_logprobs
# lower in practice; one static width keeps the compiled program set small)
TOP_LOGPROBS = 8


def _slot_key(reqs) -> tuple:
    """Cache key for a decode slot set: (request_id, epoch) per slot.

    The epoch distinguishes a preempted-and-readmitted request from an
    uninterrupted one (its params are the same but its output restarted)."""
    return tuple((s.request_id, s.epoch) if s is not None else None
                 for s in reqs)


class SamplingArrayCache:
    """Host staging for per-slot sampling parameter arrays.

    The decode loop used to rebuild (temperature, top_k, top_p, seed,
    min_tokens) from the per-request SamplingParams dict on EVERY window —
    pure host latency on the hot path, paid even when the slot set had not
    changed. Parameters are immutable per request, so the static block is
    rebuilt only when the slot -> request mapping changes; the per-step
    counters column (tokens emitted so far) is the only array built per
    call. Used by engine._sampling_arrays; the cached block also backs the
    pipelined decode loop's "greedy plan" check without a params scan."""

    def __init__(self):
        self._key = None
        self._static = None
        self._greedy = True

    def invalidate(self) -> None:
        self._key = None

    def arrays(self, reqs, params_of):
        """(temp, top_k, top_p, seeds, counters, min_toks) float32/int32
        numpy arrays, one row per slot; params_of maps request_id ->
        SamplingParams."""
        key = _slot_key(reqs)
        if key != self._key:
            n = len(reqs)
            temp = np.zeros((n,), np.float32)
            top_k = np.zeros((n,), np.int32)
            top_p = np.ones((n,), np.float32)
            seeds = np.zeros((n,), np.int32)
            min_toks = np.zeros((n,), np.int32)
            for i, seq in enumerate(reqs):
                if seq is None:
                    continue
                p = params_of(seq.request_id)
                temp[i] = p.temperature
                top_k[i] = p.top_k
                top_p[i] = p.top_p
                seeds[i] = p.seed & 0x7FFFFFFF
                min_toks[i] = p.min_tokens
            self._static = (temp, top_k, top_p, seeds, min_toks)
            self._greedy = bool(np.all(temp <= 0.0))
            self._key = key
        temp, top_k, top_p, seeds, min_toks = self._static
        counters = np.fromiter(
            (len(s.output) if s is not None else 0 for s in reqs),
            np.int32, count=len(reqs))
        return temp, top_k, top_p, seeds, counters, min_toks

    @property
    def all_greedy(self) -> bool:
        """Every slot in the last-built set samples greedily."""
        return self._greedy


class RepPenaltyCache:
    """Incremental host staging for repetition-penalty history rows.

    hist rows are each sequence's seen tokens (prompt + generated) padded
    with vocab_size; rebuilding the full [S, Hb] block every window is
    O(total tokens) host work per step. Instead the block persists across
    windows: on a slot-set hit only the tokens generated since the last
    call are appended per row; the block is rebuilt only when the slot set
    changes or the length bucket Hb grows."""

    def __init__(self):
        self._key = None
        self._any = False
        self._pens = None
        self._hist = None
        self._filled = None   # tokens already staged per row

    def invalidate(self) -> None:
        self._key = None

    @staticmethod
    def _tail(seq, start: int):
        """seq.all_tokens[start:] without materializing the full concat."""
        n_prompt = len(seq.prompt)
        if start < n_prompt:
            return seq.prompt[start:] + seq.output
        return seq.output[start - n_prompt:]

    def arrays(self, reqs, params_of, vocab_size: int, bucket_of):
        """(hist [S, Hb], rep_penalty [S]) or None when no slot penalizes.
        bucket_of maps a length to its padded bucket Hb."""
        key = _slot_key(reqs)
        if key != self._key:
            pens = np.ones((len(reqs),), np.float32)
            self._any = False
            for i, seq in enumerate(reqs):
                if seq is None:
                    continue
                rp = params_of(seq.request_id).repetition_penalty
                if rp and rp != 1.0:
                    self._any = True
                    pens[i] = rp
            self._pens = pens
            self._hist = None
            self._filled = None
            self._key = key
        if not self._any:
            return None
        longest = max((s.total_len for s in reqs if s is not None),
                      default=1)
        hb = bucket_of(max(1, longest))
        if self._hist is None or hb > self._hist.shape[1]:
            self._hist = np.full((len(reqs), hb), vocab_size, np.int32)
            self._filled = np.zeros((len(reqs),), np.int64)
        hist, filled = self._hist, self._filled
        for i, seq in enumerate(reqs):
            if seq is None:
                continue
            have, want = int(filled[i]), seq.total_len
            if want > have:
                hist[i, have:want] = self._tail(seq, have)
                filled[i] = want
        return hist, self._pens


def seen_token_mask(hist: jax.Array, vocab: int) -> jax.Array:
    """[B, Hb] token-id history (pad >= vocab) -> [B, V] presence mask."""
    b = hist.shape[0]
    rows = jnp.arange(b, dtype=jnp.int32)[:, None]
    return jnp.zeros((b, vocab), bool).at[rows, hist].set(True, mode="drop")


def apply_repetition_penalty(logits: jax.Array, seen: jax.Array,
                             penalty: jax.Array) -> jax.Array:
    """HF/vLLM semantics: for tokens already seen (prompt + generated),
    divide positive logits by the penalty, multiply negative ones
    (reference surface: nvext repetition_penalty,
    lib/llm/src/protocols/openai/nvext.rs; engines apply it exactly so)."""
    p = jnp.maximum(penalty, 1e-6)[:, None]
    pen = jnp.where(logits > 0, logits / p, logits * p)
    return jnp.where(seen, pen, logits)


def compute_logprobs(logits: jax.Array, sampled: jax.Array):
    """Per-row logprob of the sampled token + top-K alternatives.

    Returns (sampled_lp [B], top_ids [B, K] int32, top_lps [B, K]) over the
    UNMODIFIED (pre-temperature) distribution — the reference's engines
    report logprobs of the model distribution, not the sampling one.
    """
    logp = jax.nn.log_softmax(logits, axis=-1)
    samp = jnp.take_along_axis(logp, sampled[:, None].astype(jnp.int32),
                               axis=-1)[:, 0]
    top_lps, top_ids = jax.lax.top_k(logp, TOP_LOGPROBS)
    return samp, top_ids.astype(jnp.int32), top_lps


def make_keys(seeds: jax.Array, counters: jax.Array) -> jax.Array:
    """Per-row PRNG keys: deterministic in (request seed, token index)."""
    base = jax.random.PRNGKey(0)
    return jax.vmap(
        lambda s, c: jax.random.fold_in(jax.random.fold_in(base, s), c)
    )(seeds, counters)


def _ordered(bits: jax.Array) -> jax.Array:
    """float32 bits (as int32) <-> the int32 that orders as the floats do:
    non-negative floats keep their bits, negative ones have the bits under
    the sign flipped. Its own inverse."""
    return bits ^ ((bits >> 31) & 0x7FFFFFFF)


# the key below every float's: where a row's search starts (never looked at)
_BELOW_NEG_INF = int(_ordered(np.float32(-np.inf).view(np.int32))) - 1
CUT_SEARCH_STEPS = 32   # halvings that take any int32 interval to one value


def keep_mask(
    scaled: jax.Array,        # [B, V] f32 logits / temperature
    top_k: jax.Array,         # [B] int32; 0 => disabled
    top_p: jax.Array,         # [B] f32; 1.0 => disabled
) -> jax.Array:               # [B, V] bool
    """The tokens top-k and top-p leave, in token order.

    Both masks are prefixes of ONE order: descending by value and, among
    equal values, by descending token id (a stable ascending argsort,
    reversed). top-k keeps its first k; top-p keeps the smallest prefix
    whose float32 probability mass reaches top_p, always with the argmax,
    nothing at top_p 0, and everything at top_p >= 1.0 (disabled: the mass
    is not looked at). So the kept set is the first n = min(k, n_p) tokens
    of that order, and it is found here WITHOUT the order: no sort.

    For a value x let C(x) be the count of the row's entries > x and M(x)
    the sum of exp(scaled - rowmax) over them, over the row's whole sum
    (as jax.nn.softmax takes them). The group of entries equal to x is
    entered iff C(x) < k and M(x) < top_p. Both fall as x rises (a sum of
    non-negative float32 in one fixed tree never falls when an addend
    rises), so the cut is the smallest value whose group is entered:
    bisected over the int32 that orders as float32 does, CUT_SEARCH_STEPS
    masked reductions over [B, V], all rows at once. The smallest such x
    IS a row value (C and M only change there); comparisons are made in
    float, so -0.0 and +0.0 are one value. Everything above the cut is
    kept; of the entries tied with it the highest ids, the i-th of them
    (from 0) while C + i < k and M + i * p(cut) < top_p.
    No [B, V] gather or scatter either."""
    v = scaled.shape[-1]
    k = jnp.where(top_k > 0, jnp.minimum(top_k, v), v)[:, None]   # [B, 1]
    top_p = top_p[:, None]
    open_p = top_p >= 1.0

    row_max = jnp.max(scaled, axis=-1, keepdims=True)
    unnorm = jnp.exp(scaled - row_max)
    total = jnp.sum(unnorm, axis=-1, keepdims=True)

    def entered(x):
        """(C(x) < k and M(x) < top_p, C(x), M(x)), [B, 1] each."""
        over = scaled > x
        count = jnp.sum(over, axis=-1, keepdims=True, dtype=jnp.int32)
        mass = jnp.sum(jnp.where(over, unnorm, 0.0), axis=-1,
                       keepdims=True) / total
        return (count < k) & (open_p | (mass < top_p)), count, mass

    def value(key):
        return jax.lax.bitcast_convert_type(_ordered(key), jnp.float32)

    def halve(_, state):
        # lo: not entered, hi: entered (the row's maximum always is, unless
        # top_p is 0, and then nothing moves hi off it), with C and M at hi
        lo, hi, count, mass = state
        mid = (lo | hi) - ((lo ^ hi) >> 1)      # ceil of the mean, no overflow
        ok, c, m = entered(value(mid))
        return (jnp.where(ok, lo, mid), jnp.where(ok, mid, hi),
                jnp.where(ok, c, count), jnp.where(ok, m, mass))

    hi = _ordered(jax.lax.bitcast_convert_type(row_max, jnp.int32))
    _, hi, count, mass = jax.lax.fori_loop(
        0, CUT_SEARCH_STEPS, halve,
        (jnp.full_like(hi, _BELOW_NEG_INF), hi,
         jnp.zeros_like(hi), jnp.zeros_like(row_max)))

    # ties at the cut go to the highest ids, while both conditions hold
    cut = value(hi)                                               # [B, 1]
    above = scaled > cut
    tie = scaled == cut
    ties_from_here_up = jax.lax.cumsum(
        tie.astype(jnp.int32), axis=1, reverse=True)
    p_cut = jnp.exp(cut - row_max) / total
    mass_before = mass + (ties_from_here_up - 1).astype(jnp.float32) * p_cut
    return above | (tie & (count + ties_from_here_up <= k)
                    & (open_p | (mass_before < top_p)))


def sample(
    logits: jax.Array,        # [B, V] f32
    temperature: jax.Array,   # [B] f32; 0 => greedy
    top_k: jax.Array,         # [B] int32; 0 => disabled
    top_p: jax.Array,         # [B] f32; 1.0 => disabled
    keys: jax.Array,          # [B] PRNG keys (make_keys)
) -> jax.Array:               # [B] int32
    greedy_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    temp = jnp.maximum(temperature, 1e-6)[:, None]
    scaled = logits / temp

    masked = jnp.where(keep_mask(scaled, top_k, top_p), scaled, NEG_INF)
    sampled = jax.vmap(
        lambda k, row: jax.random.categorical(k, row)
    )(keys, masked).astype(jnp.int32)
    return jnp.where(temperature <= 0.0, greedy_tok, sampled)


@jax.named_scope("sampler")
def sample_logits(logits, eos_ids, temperature, top_k, top_p, seeds,
                  counters, min_tokens, seen=None, rep_penalty=None,
                  with_lp=False, greedy=False):
    """Shared tail of every engine step: repetition penalty (optional) +
    eos ban below min_tokens + sample (+ logprobs when with_lp).

    Returns (tokens [B], sampled_lp [B], top_ids [B, K], top_lps [B, K]);
    the lp outputs are None unless with_lp — the full-vocab log_softmax +
    top_k and their host transfer cost real decode latency, so the common
    path must not pay for them. Logprobs are taken over the penalized (but
    pre-temperature, pre-ban) distribution — what the reference's engines
    report. Lives here (not engine.py) so the pipeline-parallel decode
    window (models/pp.py) samples through the identical code path as the
    single-mesh engine — oracle-exact at a fixed seed."""
    if rep_penalty is not None:
        logits = apply_repetition_penalty(logits, seen, rep_penalty)
    basis = logits
    if eos_ids:
        ban = (counters < min_tokens)[:, None]      # [B, 1]
        eos = jnp.asarray(eos_ids, jnp.int32)
        eos_mask = jnp.zeros((logits.shape[-1],), bool).at[eos].set(True)
        logits = jnp.where(ban & eos_mask[None, :], -1e30, logits)
    if greedy:
        # all-greedy plan: argmax only — the full sampler's cut search,
        # noise and argmax over the vocabulary cost 0.7-1.3 ms a call
        # (tools/sampler_tail_bench.py, v5e, host-timed)
        toks = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    else:
        keys = make_keys(seeds, counters)
        toks = sample(logits, temperature, top_k, top_p, keys)
    if not with_lp:
        return toks, None, None, None
    samp_lp, top_ids, top_lps = compute_logprobs(basis, toks)
    return toks, samp_lp, top_ids, top_lps
