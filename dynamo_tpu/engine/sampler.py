"""Token sampling (greedy / temperature / top-k / top-p) as one jitted kernel.

Matches the sampling-option surface the reference forwards to its engines
(reference: lib/llm/src/protocols/common.rs:248 SamplingOptions — temperature,
top_k, top_p, seed; greedy when nvext.greed_sampling or temperature==0).

All-batch vectorized with static vocab: one descending value sort gives both
top-k's and top-p's cutoff, compared in token order; XLA fuses the rest.
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
        self._fusable = True

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
            self._fusable = bool(np.all(top_p >= 1.0))
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

    @property
    def fused_eligible(self) -> bool:
        """Every slot in the last-built set has top_p disabled (== 1.0), so
        the fused top_p-free sampler (`sample_fused`) draws token-identical
        samples — the decode window's common-path tail. Rows requesting a
        real top_p force the window onto the unfused `sample` tail."""
        return self._fusable


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


def keep_mask(
    scaled: jax.Array,        # [B, V] f32 logits / temperature
    top_k: jax.Array,         # [B] int32; 0 => disabled
    top_p: jax.Array,         # [B] f32; 1.0 => disabled
) -> jax.Array:               # [B, V] bool
    """The tokens top-k and top-p leave, in token order.

    Both masks are prefixes of ONE order: descending by value and, among
    equal values, by descending token id (a stable ascending argsort,
    reversed). top-k keeps its first k; top-p keeps the smallest prefix of
    the sorted probabilities whose cumulative sum reaches top_p, always
    with the argmax (the prefix is the meaning: should a rounded
    `cumprobs - sorted_probs` ever dip after it has crossed top_p, what
    follows the first crossing stays out). So the kept set is the first
    n = min(k, n_p) tokens of that order, and it is rebuilt here without
    the order's ranks: everything above the n-th sorted value, and of the
    tokens tied with it the highest ids, as many as are still missing.
    One value sort; no argsort, and no [B, V] gather or scatter."""
    v = scaled.shape[-1]
    sorted_logits = jnp.sort(scaled, axis=-1)[:, ::-1]            # [B, V] desc

    # top-k: the first k of the order (k==0 disables)
    k = jnp.where(top_k > 0, top_k, v)

    # top-p: the leading run of sorted_keep
    sorted_probs = jax.nn.softmax(sorted_logits, axis=-1)
    cumprobs = jnp.cumsum(sorted_probs, axis=-1)
    sorted_keep = (cumprobs - sorted_probs) < top_p[:, None]
    first_out = jnp.where(sorted_keep, v, jnp.arange(v, dtype=jnp.int32))
    n = jnp.minimum(k, jnp.min(first_out, axis=-1))               # [B]

    # the n-th sorted value cuts; ties at the cut go to the highest ids.
    # n == 0 (top_p 0) keeps nothing: need is 0, a tie's count at least 1
    cut = jnp.take_along_axis(
        sorted_logits, jnp.maximum(n - 1, 0)[:, None], axis=-1)   # [B, 1]
    above = scaled > cut
    tie = scaled == cut
    need = n - jnp.sum(above, axis=-1, dtype=jnp.int32)
    ties_from_here_up = jax.lax.cumsum(
        tie.astype(jnp.int32), axis=1, reverse=True)
    return above | (tie & (ties_from_here_up <= need[:, None]))


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


def sample_fused(
    logits: jax.Array,        # [B, V] f32
    temperature: jax.Array,   # [B] f32; 0 => greedy
    top_k: jax.Array,         # [B] int32; 0 => disabled
    keys: jax.Array,          # [B] PRNG keys (make_keys)
) -> jax.Array:               # [B] int32
    """The fused decode-window sampling tail: temperature + top-k only.

    Valid ONLY when every row's top_p is 1.0 (disabled) — the common
    serving shape (SamplingArrayCache.fused_eligible gates it). Token-
    identical to `sample` there, by construction:

    - order: `keep_mask` keeps the first n tokens of the stable descending
      order (equal values by descending id). Scattering iota through that
      SAME permutation (`ranks[order[j]] = j`) gives each token its place
      in it, so `ranks < k` is the first k of the same order, ties
      included.
    - masked set: with top_p == 1.0, `keep_mask`'s top-p prefix is the whole
      row (the strict `cumprobs - sorted_probs < 1.0` can only exclude a
      tail element once the f32 cumsum has rounded up to 1.0: what is left
      there is probability the sum can no longer see), so k alone decides.
    - draw: same make_keys stream, same categorical over the same masked
      row => the same token.

    What it bought inside the jitted window, when the full tail still
    sorted three times and gathered its mask back through the ranks: one
    argsort and one scatter in their place. Since PR 28 `sample` builds
    its mask in token space from ONE value sort, with no argsort and no
    [B, V] gather or scatter, so this tail's argsort + scatter are
    probably the dearer pair now (not measured: no benchmark cell runs an
    all-top_p-1 batch; ROADMAP queue D)."""
    b, v = logits.shape
    greedy_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    temp = jnp.maximum(temperature, 1e-6)[:, None]
    scaled = logits / temp

    order = jnp.argsort(scaled, axis=-1)[:, ::-1]          # [B, V] desc perm
    rows = jnp.arange(b, dtype=jnp.int32)[:, None]
    iota = jnp.broadcast_to(jnp.arange(v, dtype=jnp.int32), (b, v))
    ranks = jnp.zeros((b, v), jnp.int32).at[rows, order].set(iota)

    k = jnp.where(top_k > 0, top_k, v)[:, None]
    masked = jnp.where(ranks < k, scaled, NEG_INF)
    sampled = jax.vmap(
        lambda kk, row: jax.random.categorical(kk, row)
    )(keys, masked).astype(jnp.int32)
    return jnp.where(temperature <= 0.0, greedy_tok, sampled)


@jax.named_scope("sampler")
def sample_logits(logits, eos_ids, temperature, top_k, top_p, seeds,
                  counters, min_tokens, seen=None, rep_penalty=None,
                  with_lp=False, greedy=False, fused=False):
    """Shared tail of every engine step: repetition penalty (optional) +
    eos ban below min_tokens + sample (+ logprobs when with_lp).

    `fused` selects the top_p-free `sample_fused` tail; callers must only
    set it when every row's top_p is 1.0 (SamplingArrayCache.fused_eligible)
    — the engine stages it as a static window-key bit, so a plan mixing in
    a real top_p row recompiles onto the unfused tail, token-identically.

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
        # all-greedy plan: argmax only — the full sampler's vocab sort
        # costs ~1.5 ms/step on a 128k vocab (measured, v5e)
        toks = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    elif fused:
        keys = make_keys(seeds, counters)
        toks = sample_fused(logits, temperature, top_k, keys)
    else:
        keys = make_keys(seeds, counters)
        toks = sample(logits, temperature, top_k, top_p, keys)
    if not with_lp:
        return toks, None, None, None
    samp_lp, top_ids, top_lps = compute_logprobs(basis, toks)
    return toks, samp_lp, top_ids, top_lps
