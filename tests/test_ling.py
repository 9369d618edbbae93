"""Ling-3.0-flash-VL's language model (`bailing_hybrid`): Kimi Delta
Attention layers with a state slot a sequence beside the latent page cache,
a chip's share of the experts, the group-limited pick; the served path
against the plain reference (dynamo_tpu/models/reference.py), on LOGITS.

Tiny widths with every kind present: 2 dense leads + one period of 6
(kinds K K | K K K M K K), 32 experts in 8 groups of which 4 are picked, a
share of 2 of the 8 groups held (experts 8-15).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.config import EngineConfig, ModelConfig
from dynamo_tpu.engine.engine import NativeEngine
from dynamo_tpu.models import llama, reference
from dynamo_tpu.observability.ledger import LEDGER_STATS, LedgerStats
from dynamo_tpu.ops import linear_attention as la
from dynamo_tpu.ops import moe
from tests.test_olmoe import ENGINE_KW, Recorder, drive

TINY = ModelConfig(
    name="tiny-ling", vocab_size=128, hidden_size=64, num_layers=8,
    num_heads=4, num_kv_heads=4, head_dim=16, linear_group_size=6,
    linear_head_dim=16, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, query_scale=24 ** -0.5, mla_qk_norm=True,
    mla_gate=True, num_experts=32, experts_held=8, expert_first=8,
    moe_n_group=8, moe_topk_group=4, first_dense_layers=2,
    num_experts_per_tok=4, intermediate_size=32,
    dense_intermediate_size=96, shared_expert_size=32,
    moe_router_bias=True, moe_scoring="sigmoid", moe_routed_scale=2.5,
    rms_norm_eps=1e-6, rope_theta=6e6, dtype="float32", max_model_len=256)

# Two readings a comparison in float32, over served positions, of max
# |logit difference| over the vocabulary (logits are O(1), largest ~4.5):
# the largest, held to 3e-4, and the MEDIAN, held to 1e-4. Both sides
# compute in float32 from the same weights; they differ in summation order
# and in the FORM of every mixer (chunkwise WY form and a one-token form
# over state slots against the per-token recurrence, absorbed against
# expanded latent attention, sorted dispatch against every expert masked).
# Read on this CPU: largest 3.6e-5 / 4.1e-5 and median 1.0e-5 / 1.0e-5
# (seeds 0, 1), so the limits are seven and ten times the readings. Seven
# linear layers carry float32 rounding further than Moonlight's three
# latent ones (7e-6): the recurrence hands a perturbation on. (With the
# decay drawn so that a state forgets within ONE token the largest read
# 4e-4 to 2.5e-3: o_t ~ (k_t . q_t) v_t then, and its head norm flips sign
# with k . q; models/llama._init_layer_stack draws a memory of a few to a
# few dozen tokens.) The mutations are judged on the median, which nothing
# but a real change of the function moves: each must read 1000 times its
# limit.
TOL = (3e-4, 1e-4)
REQUESTS = ((70, 10), (37, 9), (21, 6))


def served_run(monkeypatch, cfg=TINY, seed=0, **engine_kw):
    rec = Recorder(monkeypatch)
    eng = NativeEngine(cfg, EngineConfig(**dict(ENGINE_KW, **engine_kw)),
                       seed=seed)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(2, cfg.vocab_size, n).tolist()
               for n, _ in REQUESTS]
    outs = drive(eng, prompts, [g for _, g in REQUESTS])
    assert [len(o) for o in outs] == [g for _, g in REQUESTS]
    return rec.entries, [p + o for p, o in zip(prompts, outs)], eng


def readings(entries, seqs, want, every_position=True, strays=False):
    """(largest, median, 90th percentile) over served positions of max
    |logit difference| from `want` (a [T, V] array a sequence). A served
    (token, position) belongs to the request that has it; where two have
    the same token at the same position, to the one it is closer to. Every
    fed position must have been compared (not under a mutation, where
    "closer" no longer tells two such requests apart)."""
    found, seen = [], [set() for _ in seqs]
    for token, pos, logits in entries:
        errs = [(float(np.max(np.abs(logits - want[i][pos]))), i)
                for i, s in enumerate(seqs)
                if pos < len(s) and s[pos] == token]
        if strays and not errs:
            continue    # a pipelined window ran on past a row's stop id
        assert errs, f"token {token} at {pos} belongs to no request"
        err, who = min(errs)
        seen[who].add(pos)
        found.append(err)
    if every_position:
        for s, got in zip(seqs, seen):
            assert got >= set(range(len(s) - 1))
    return (max(found), float(np.median(found)),
            float(np.percentile(found, 90)))


def reference_logits(params, seqs, cfg=TINY, **arch_changes):
    arch = {**reference.arch_kwargs(cfg), **arch_changes}
    return [np.asarray(reference.forward(params, jnp.asarray(s), **arch))
            for s in seqs]


@pytest.fixture(scope="module")
def served_f32():
    """One float32 run of the served path (prefill chunks, mixed steps,
    decode windows), shared by the comparison and by every mutation of
    what it is compared with."""
    with pytest.MonkeyPatch.context() as mp:
        before = LEDGER_STATS.snapshot()
        entries, seqs, eng = served_run(mp)
        params = jax.device_get(eng.params)
        m = eng.metrics()
        delta = {k: v - before[k] for k, v in LEDGER_STATS.snapshot().items()
                 if k.startswith(("moe_", "linattn_"))}
        stats = dict(mixed=m.mixed_steps, windows=m.decode_windows,
                     cache={k: (v.shape, str(v.dtype))
                            for k, v in eng.cache.items()},
                     slots_used=eng.scheduler.state_slots.used,
                     page_bytes=m.kv_page_bytes, delta=delta,
                     slot_bytes=LEDGER_STATS.state_bytes_per_slot)
    return entries, seqs, params, stats


def test_served_logits_match_the_plain_reference(served_f32):
    entries, seqs, params, stats = served_f32
    largest, median, _ = readings(entries, seqs,
                                  reference_logits(params, seqs))
    assert largest < TOL[0] and median < TOL[1], (largest, median)
    assert stats["mixed"] > 0 and stats["windows"] > 0, stats
    # the cache runs over the ONE latent layer, the state over the seven
    # linear ones, a slot a decode slot and a prefill-batch row, and the
    # scratch slot of the slot-addressed update (llama.init_state)
    slots = ENGINE_KW["max_slots"] + EngineConfig().max_prefill_batch + 1
    assert stats["cache"] == {
        "k": ((1, 1, 64, 16, 128), "float32"),    # 32 + 8 in a lane tile
        "kda_s": ((7, slots, 4, 16, 16), "float32"),
        "kda_conv": ((7, slots, 3, 192), "float32")}
    assert stats["slots_used"] == 0          # every sequence finished
    assert stats["page_bytes"] == 16 * 128 * 4      # the pool's
    assert TINY.kv_bytes_per_token() == 40 * 4      # the model's
    assert stats["slot_bytes"] == TINY.state_bytes_per_slot() \
        == 7 * (4 * 16 * 16 * 4 + 3 * 192 * 4)


def test_served_logits_match_in_bfloat16(monkeypatch):
    """bfloat16 rounds every activation; the state stays float32. With
    the published pick a bfloat16-sized change of the input flips a GROUP
    of a token now and then, and with it up to all four of its experts:
    the tiny model reads a median of 0.5-0.6 that way (seeds 0, 1), which
    says nothing about the mixers. So here every expert is picked (no
    group, no top-k: nothing can flip; the share still holds 8 of 32) and
    the mixers' own rounding is what is read: median 0.21, 90th
    percentile 0.38 on this CPU (seed 0)."""
    cfg = dataclasses.replace(TINY, dtype="bfloat16", moe_n_group=1,
                              moe_topk_group=1, num_experts_per_tok=32)
    entries, seqs, eng = served_run(monkeypatch, cfg)
    assert eng.cache["kda_s"].dtype == jnp.float32
    assert eng.cache["kda_conv"].dtype == jnp.bfloat16
    _, median, p90 = readings(entries, seqs, reference_logits(
        jax.device_get(eng.params), seqs, cfg))
    assert median < 0.5 and p90 < 0.9, (median, p90)


def test_the_share_and_the_state_are_counted(served_f32):
    *_, stats = served_f32
    d = stats["delta"]
    assert d["moe_dropped_total"] == 0
    held = d["moe_routed_total"] / (d["moe_routed_total"]
                                    + d["moe_routed_absent_total"])
    assert 0.1 < held < 0.5, held       # 2 of 8 groups, skewed by the bias
    # every fed token, and the few steps a window runs past a row's end
    tokens = sum(n + g - 1 for n, g in REQUESTS)
    assert 7 * tokens <= d["linattn_tokens_total"] <= 7 * (tokens + 12)
    assert 0 < d["linattn_chunk_tokens_total"] < d["linattn_tokens_total"]
    # updated where the state rests: every token of a window, and none
    # of these steps' (three rows: no step here splits its rows)
    assert not llama.kda_mix_splits(ENGINE_KW["max_slots"], 16)
    assert d["linattn_inplace_updates_total"] \
        == d["linattn_tokens_total"] - d["linattn_chunk_tokens_total"]
    assert d["linattn_state_bytes_total"] > d[
        "linattn_window_state_bytes_total"] > 0
    assert d["linattn_steps_total"] > d["linattn_window_steps_total"] > 0
    assert {"moe_routed_absent_total", "linattn_tokens_total",
            "linattn_chunk_tokens_total", "linattn_inplace_updates_total",
            "linattn_state_bytes_total",
            "state_slots_used", "state_bytes_per_slot"} \
        <= set(LedgerStats.FIELDS)


# -- the bare forward(): every chunk bucket, and the state between chunks -----

def _bare_step(params, cache, toks, pos, n, tb, slot=2, rows=2):
    """One forward() over a [rows, tb] grid whose LAST row holds tokens
    pos..pos+n of `toks` (the others are padding), pages 3.. of 16."""
    ps = 16
    tok = np.zeros((rows, tb), np.int32)
    tok[-1, :n] = toks[pos:pos + n]
    positions = np.zeros((rows, tb), np.int32)
    positions[-1, :] = pos + n - 1
    positions[-1, :n] = np.arange(pos, pos + n)
    w = np.full((rows, tb), -1, np.int32)
    w[-1, :n] = np.arange(pos, pos + n) + ps * 3
    pt = np.zeros((rows, 16), np.int32)
    pt[-1] = np.arange(16) + 3
    kv = np.zeros((rows,), np.int32)
    kv[-1] = pos + n
    slots = np.full((rows,), -1, np.int32)
    slots[-1] = slot
    meta = dict(positions=positions, page_table=pt, kv_lens=kv,
                write_idx=w, state_slots=slots)

    @jax.jit
    def step(params, tok, cache, meta):
        return llama.forward(params, TINY, tok, cache,
                             llama.AttnMetadata(**meta))
    logits, cache = step(params, jnp.asarray(tok), cache,
                         jax.tree.map(jnp.asarray, meta))
    return np.asarray(logits[-1, :n]), cache


def _fresh_cache(garbage=False):
    cache = llama.init_cache(TINY, 64, 16)
    state = llama.init_state(TINY, 4)
    if garbage:     # what a finished sequence left in every slot
        state = {k: jnp.full_like(v, 3.0) for k, v in state.items()}
    return {**cache, **state}


@pytest.fixture(scope="module")
def bare():
    params = llama.init_params(jax.random.PRNGKey(0), TINY)
    toks = np.random.default_rng(5).integers(2, TINY.vocab_size, 120)
    want = np.asarray(reference.forward(
        params, toks, **reference.arch_kwargs(TINY)))
    return params, toks, want


@pytest.mark.parametrize("chunks", [
    ((120, 128),), ((8, 8), (16, 16), (32, 32), (64, 64)),
    ((5, 8), (11, 16), (17, 32), (33, 64), (54, 64)),
    ((1, 8),) * 6 + ((16, 16),) * 3, ((64, 64), (1, 16), (32, 32))],
    ids=["one-chunk", "every-bucket-full", "every-bucket-ragged",
         "single-tokens-then-blocks", "mixed"])
def test_chunks_at_every_bucket_match_the_reference(bare, chunks):
    """forward() in chunks of every bucket (one block of the chunkwise
    form, and 2, 4, 8 blocks through its scan), full and ragged, carries
    the state and the convolution's tail from chunk to chunk."""
    params, toks, want = bare
    cache, pos, got = _fresh_cache(), 0, []
    for n, tb in chunks:
        out, cache = _bare_step(params, cache, toks, pos, n, tb)
        got.append(out)
        pos += n
    err = np.abs(np.concatenate(got) - want[:pos])
    assert err.max() < TOL[0] and np.median(err.max(axis=1)) < TOL[1], (
        err.max(), np.median(err.max(axis=1)))


def _grid_step(params, cache, toks, rows, tb):
    """One forward() over a [len(rows), tb] grid. rows: (pos, n) a row,
    the row holding tokens pos..pos+n of `toks` in state slot i and pages
    1 + 3 i.., or None for a row of padding. -> ([pos.., V] logits a
    row, cache)."""
    ps, b = 16, len(rows)
    tok = np.zeros((b, tb), np.int32)
    positions = np.zeros((b, tb), np.int32)
    w = np.full((b, tb), -1, np.int32)
    pt = np.zeros((b, 4), np.int32)
    kv = np.zeros((b,), np.int32)
    slots = np.full((b,), -1, np.int32)
    for i, row in enumerate(rows):
        if row is None:
            continue
        pos, n = row
        tok[i, :n] = toks[pos:pos + n]
        positions[i, :] = pos + n - 1
        positions[i, :n] = np.arange(pos, pos + n)
        pt[i] = np.arange(4) + 1 + 3 * i
        w[i, :n] = pt[i][positions[i, :n] // ps] * ps + positions[i, :n] % ps
        kv[i], slots[i] = pos + n, i
    meta = dict(positions=positions, page_table=pt, kv_lens=kv,
                write_idx=w, state_slots=slots)

    @jax.jit
    def step(params, tok, cache, meta):
        return llama.forward(params, TINY, tok, cache,
                             llama.AttnMetadata(**meta))
    logits, cache = step(params, jnp.asarray(tok), cache,
                         jax.tree.map(jnp.asarray, meta))
    return [None if r is None else np.asarray(logits[i, :r[1]])
            for i, r in enumerate(rows)], cache


@pytest.mark.parametrize("chunks", [
    (32, 20, 2), (32, 31, 30, 29, 28, 20, 17, 16, 9, 2, 2),
    (32,) * 15], ids=["one-group", "two-groups", "every-row-a-chunk"])
def test_a_mixed_step_loses_no_row(bare, chunks):
    """A step of more rows than the chunkwise form takes at a time (20 >
    KDA_CHUNK_ROWS): its one-token rows take the one-token form, its
    chunk rows the chunkwise form in as many groups as there are, and
    every row of either kind reads what the recurrence reads, however
    many chunk rows the step holds. The decode rows continue sequences
    that an earlier step of the same grid prefilled."""
    params, toks, want = bare
    assert len(chunks) + 5 <= 20 > llama.KDA_CHUNK_ROWS
    cache = {**llama.init_cache(TINY, 64, 16), **llama.init_state(TINY, 20)}
    had = (5, 16, 17, 31, 32)
    first = [None] * 15 + [(0, m) for m in had]
    got, cache = _grid_step(params, cache, toks, first, 32)
    for m, out in zip(had, got[15:]):
        assert np.abs(out - want[:m]).max() < TOL[0], m
    second = [(0, n) for n in chunks] + [None] * (15 - len(chunks)) \
        + [(m, 1) for m in had]
    got, cache = _grid_step(params, cache, toks, second, 32)
    for (pos, n), out in zip((r for r in second if r), (
            g for g in got if g is not None)):
        assert np.abs(out - want[pos:pos + n]).max() < TOL[0], (pos, n)
    # ... and the state each left is what the same tokens leave alone
    alone, c_alone = _bare_step(params, _fresh_cache(), toks, 0, chunks[0],
                                32)
    np.testing.assert_allclose(cache["kda_s"][:, 0], c_alone["kda_s"][:, 2],
                               atol=2e-5)


@pytest.mark.parametrize("impl", ["plain", "interpret"])
def test_a_full_mixed_step_updates_every_row_once(bare, monkeypatch, impl):
    """The served mixed step: 61 decode rows beside 3 chunk rows, one a
    continued chunk, one a FRESH chunk over a slot that held another
    sequence's state, one a fresh ONE-token chunk. The one-token rows are
    updated where their state rests (`kda_step_slots`, dead to the
    chunkwise groups), the others by `kda_chunk` (dead to the kernel):
    every row's logits are the recurrence's and every slot holds what its
    own tokens leave alone, so the split loses no row and updates none
    twice. Once in the form a CPU takes and once with the kernel's body
    in the Pallas interpreter."""
    monkeypatch.setattr(la, "kda_step_slots_impl", lambda: impl)
    params, toks, want = bare
    cache = {**llama.init_cache(TINY, 256, 16), **llama.init_state(TINY, 64)}
    had = [(5, 16, 17, 31)[i % 4] for i in range(61)]
    first = [(0, m) for m in had] + [(0, 9), (0, 30), None]
    got, cache = _grid_step(params, cache, toks, first, 32)
    for (_, m), out in zip(first[:63], got):
        assert np.abs(out - want[:m]).max() < TOL[0], m
    # slot 62 held 30 tokens of a finished sequence: the fresh chunk in
    # it starts from zeros; slot 63 was never used
    second = [(m, 1) for m in had] + [(9, 17), (0, 20), (0, 1)]
    got, cache = _grid_step(params, cache, toks, second, 32)
    for (pos, n), out in zip(second, got):
        assert np.abs(out - want[pos:pos + n]).max() < TOL[0], (pos, n)
    # ... to 1e-4: alone they rode ONE chunk, here a chunk and a token
    # or two chunks (2.4e-5 read); a token lost or doubled moves a state
    # by a tenth
    ends = [pos + n for pos, n in second]
    alone = {}
    for end in sorted(set(ends)):
        _, c = _bare_step(params, _fresh_cache(), toks, 0, end, 32)
        alone[end] = c
    for name in ("kda_s", "kda_conv"):
        for slot, end in enumerate(ends):
            np.testing.assert_allclose(
                cache[name][:, slot], alone[end][name][:, 2], atol=1e-4,
                err_msg=f"{name} slot {slot} after {end} tokens")
        # the scratch slot is no row's: dead rows left it as it was
        assert not np.asarray(cache[name][:, 64]).any()


def test_a_reused_slot_starts_from_zeros_and_padding_rows_write_nothing(
        bare):
    """A row at position 0 starts from zeros whatever its slot held; the
    padding rows of the grid and the slots no row names keep every bit."""
    params, toks, want = bare
    clean, _ = _bare_step(params, _fresh_cache(), toks, 0, 40, 64)
    dirty_cache = _fresh_cache(garbage=True)
    dirty, after = _bare_step(params, dirty_cache, toks, 0, 40, 64)
    np.testing.assert_array_equal(clean, dirty)
    others = np.array([0, 1, 3])
    for name in ("kda_s", "kda_conv"):
        np.testing.assert_array_equal(np.asarray(after[name][:, others]),
                                      np.asarray(dirty_cache[name][:, others]))
        assert not np.array_equal(np.asarray(after[name][:, 2]),
                                  np.asarray(dirty_cache[name][:, 2]))


# -- the chunkwise form against the recurrence --------------------------------

@pytest.mark.parametrize("t", [16, 32, 64])
def test_chunkwise_equals_the_recurrence_with_decays_at_the_bound(t):
    """kda_chunk over T tokens (1, 2, 4 blocks) == kda_step T times ==
    the recurrence written out, with every decay within 1e-3 of the -5
    bound on half the channels (exp(-5 x 64) underflows: only pairwise
    decays keep this finite)."""
    rng = np.random.default_rng(t)
    b, h, d = 2, 3, 8
    f32 = jnp.float32
    q = la.l2_normalize(jnp.asarray(rng.normal(size=(b, t, h, d)), f32))
    k = la.l2_normalize(jnp.asarray(rng.normal(size=(b, t, h, d)), f32))
    v = jnp.asarray(rng.normal(size=(b, t, h, d)), f32)
    logit = rng.normal(size=(b, t, h, d)) * 3
    logit[..., ::2] = 9.0
    g = -5 * jax.nn.sigmoid(jnp.asarray(logit, f32))
    assert float(g.min()) < -4.999
    beta = jax.nn.sigmoid(jnp.asarray(rng.normal(size=(b, t, h)), f32))
    s0 = jnp.asarray(rng.normal(size=(b, h, d, d)), f32)

    s, want = s0, []
    for i in range(t):          # the equations of ISSUE 33, written out
        s = jnp.exp(g[:, i])[..., None] * s
        s = s + jnp.einsum("bhk,bhv->bhkv", k[:, i], beta[:, i][..., None]
                           * (v[:, i] - jnp.einsum("bhk,bhkv->bhv",
                                                   k[:, i], s)))
        want.append(jnp.einsum("bhkv,bhk->bhv", s, q[:, i]))
    want = jnp.stack(want, 1)

    s_step, got_step = s0, []
    for i in range(t):
        o, s_step = la.kda_step(q[:, i], k[:, i], v[:, i], g[:, i],
                                beta[:, i], s_step)
        got_step.append(o)
    np.testing.assert_allclose(jnp.stack(got_step, 1), want, atol=2e-6)
    np.testing.assert_allclose(s_step, s, atol=2e-6)
    o, s_chunk = jax.jit(la.kda_chunk)(q, k, v, g, beta, s0)
    assert np.isfinite(np.asarray(o)).all()
    np.testing.assert_allclose(o, want, atol=5e-6)
    np.testing.assert_allclose(s_chunk, s, atol=5e-6)


def test_padding_tokens_are_identity_updates():
    """beta 0, g 0 and zero q, k, v (what kda_mix makes of a padding
    cell) leave the state bit for bit."""
    rng = np.random.default_rng(1)
    s0 = jnp.asarray(rng.normal(size=(1, 2, 8, 8)), jnp.float32)
    zero = jnp.zeros((1, 16, 2, 8), jnp.float32)
    o, s1 = la.kda_chunk(zero, zero, zero, zero, jnp.zeros((1, 16, 2)), s0)
    np.testing.assert_array_equal(s1, s0)
    np.testing.assert_array_equal(o, jnp.zeros_like(o))


# -- a chip's share of an expert layer ----------------------------------------

def test_the_four_shares_add_up_to_the_uncut_layer():
    """The routed parts that the four shares (8 experts each of 32)
    compute, with the shared expert counted once, add up to what the
    uncut reference gives for the whole layer: the dropless dispatch told
    which experts it holds against every expert evaluated and masked."""
    whole = dataclasses.replace(TINY, experts_held=0, expert_first=0)
    params = llama.init_params(jax.random.PRNGKey(3), whole)
    lp = {k: v[1] for k, v in params["run1"].items()}
    x = jnp.asarray(np.random.default_rng(2).normal(size=(2, 24, 64)),
                    jnp.float32)
    arch = reference.arch_kwargs(whole)
    router = {k: arch[k] for k in (
        "num_experts_per_tok", "norm_topk_prob", "moe_scoring",
        "moe_routed_scale", "n_group", "topk_group")}
    with jax.default_matmul_precision("highest"):
        want = reference.expert_mlp(x.reshape(48, 64), lp, **router)
        shared = reference.dense_mlp(x.reshape(48, 64), lp,
                                     ("ws_gate", "ws_up", "ws_down"))
        total, absent, routed = shared, 0.0, 0.0
        for first in (0, 8, 16, 24):
            cfg = dataclasses.replace(TINY, expert_first=first)
            part = {**lp, **{k: lp[k][first:first + 8]
                             for k in llama.EXPERT_LEAVES}}
            out, stats = moe.moe_dropless_mlp(x, part, cfg)
            total = total + out.reshape(48, 64)
            absent += float(stats["moe_routed_absent"])
            routed += float(stats["moe_routed"])
    np.testing.assert_allclose(total, want, atol=2e-5)
    # every assignment is held by exactly one share
    assert routed == 48 * 4 and absent == 3 * 48 * 4


def test_the_grouped_pick_is_the_references(served_f32):
    """route_topk's group-limited pick chooses what the reference's
    does, from 4 of the 8 groups only."""
    *_, params, _ = served_f32
    lp = {k: jnp.asarray(v[0]) for k, v in params["run1"].items()}
    x = jnp.asarray(np.random.default_rng(4).normal(size=(50, 64)),
                    jnp.float32)
    weights, idx = moe.route(x, lp, TINY)
    arch = reference.arch_kwargs(TINY)
    with jax.default_matmul_precision("highest"):
        want = reference.router_weights(x, lp, **{k: arch[k] for k in (
            "num_experts_per_tok", "norm_topk_prob", "moe_scoring",
            "moe_routed_scale", "n_group", "topk_group")})
    got = np.zeros((50, 32), np.float32)
    np.put_along_axis(got, np.asarray(idx), np.asarray(weights), axis=1)
    np.testing.assert_allclose(got, want, atol=1e-6)
    assert all(len({i // 4 for i in row}) <= 4 for row in np.asarray(idx))
    np.testing.assert_allclose(np.asarray(weights).sum(1), 2.5, rtol=1e-5)


# -- each way of serving another model fails, by a wide margin -----------------

def _mutant_kda(no_conv=False, no_l2=False, scalar_decay=False,
                softplus_gate=False, no_beta=False):
    """reference.attention_kda's lines with one thing changed."""
    def attention(x, lp, *, num_heads, head_dim, lower_bound, rms_norm_eps,
                  state_dtype=jnp.float32):
        t, h, d = x.shape[0], num_heads, head_dim
        pre = x @ lp["kda_wqkv"]
        qkv = jax.nn.silu(pre if no_conv else reference.causal_conv(
            pre, lp["kda_conv_w"]))
        q, k, v = (a.reshape(t, h, d) for a in jnp.split(qkv, 3, axis=-1))
        if not no_l2:
            q, k = reference.l2_normalize(q), reference.l2_normalize(k)
        q = q * d ** -0.5
        f = (x @ lp["kda_wf"] + lp["kda_dt_bias"]).reshape(t, h, d)
        a = jnp.exp(lp["kda_a_log"])[None, :, None]
        g = -a * jax.nn.softplus(f) if softplus_gate \
            else lower_bound * jax.nn.sigmoid(a * f)
        if scalar_decay:        # one decay a head, not one a channel
            g = jnp.broadcast_to(jnp.mean(g, -1, keepdims=True), g.shape)
        beta = jnp.ones((t, h)) if no_beta \
            else jax.nn.sigmoid(x @ lp["kda_wb"])

        def step(s, xs):
            q_t, k_t, v_t, g_t, b_t = xs
            s = jnp.exp(g_t)[:, :, None] * s
            s = s + jnp.einsum("hk,hv->hkv", k_t, b_t[:, None] * (
                v_t - jnp.einsum("hk,hkv->hv", k_t, s)))
            return s, jnp.einsum("hkv,hk->hv", s, q_t)
        _, o = jax.lax.scan(step, jnp.zeros((h, d, d)),
                            (q, k, v, g, beta))
        o = reference.rms_norm(o, lp["kda_o_norm"], rms_norm_eps)
        o = o * jax.nn.sigmoid(x @ lp["kda_wg"]).reshape(t, h, d)
        return o.reshape(t, h * d) @ lp["wo"]
    return attention


def _renormalised_over_the_held(x, lp, expert_first=0, **router):
    """The absent experts renormalised away: the held picks' weights
    rescaled to sum to the routed scale."""
    w = reference.router_weights(x, lp, **router)
    w = w[:, expert_first:expert_first + lp["w_gate"].shape[0]]
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20) \
        * router["moe_routed_scale"]
    hidden = (jax.nn.silu(jnp.einsum("td,edf->etf", x, lp["w_gate"]))
              * jnp.einsum("td,edf->etf", x, lp["w_up"]))
    y = jnp.einsum("te,etd->td", w,
                   jnp.einsum("etf,efd->etd", hidden, lp["w_down"]))
    return y + reference.dense_mlp(x, lp, ("ws_gate", "ws_up", "ws_down"))


def _without(*names):
    def change(params):
        return {**params, "run2": {k: v for k, v in params["run2"].items()
                                   if k not in names}}
    return change


def _kda_where_the_mla_layer_belongs(params):
    """Layer 5 given a linear layer's mixer (layer 6's) where its latent
    attention is; its expert block stays."""
    mixer = {k: v[:1] for k, v in params["run3"].items()
             if k.startswith("kda_") or k in ("wo", "attn_norm")}
    rest = {k: v for k, v in params["run2"].items()
            if k.startswith(("router", "w_", "ws_", "mlp_norm"))
            and k != "w_attn_gate"}
    return {**params, "run2": {**rest, **mixer}}


MUTATIONS = {
    "no_convolution": dict(patch=("attention_kda", _mutant_kda(no_conv=True))),
    "no_l2_norm": dict(patch=("attention_kda", _mutant_kda(no_l2=True))),
    "scalar_decay_for_per_channel": dict(
        patch=("attention_kda", _mutant_kda(scalar_decay=True))),
    "softplus_gate_for_the_lower_bound_one": dict(
        patch=("attention_kda", _mutant_kda(softplus_gate=True))),
    "beta_dropped": dict(patch=("attention_kda", _mutant_kda(no_beta=True))),
    # the state rounded to bfloat16 after every token: what the chip's
    # check cannot see beside bfloat16 activations (PERF.md section 6,
    # PR 33) reads 0.38 here, in float32
    "bfloat16_state": dict(arch=dict(kda=dict(
        reference.arch_kwargs(TINY)["kda"], state_dtype=jnp.bfloat16))),
    "group_pick_skipped": dict(arch=dict(n_group=1, topk_group=1)),
    "absent_experts_renormalised_away": dict(
        patch=("expert_mlp", _renormalised_over_the_held)),
    "mla_gate_skipped": dict(params=_without("w_attn_gate")),
    "mla_qk_norm_skipped": dict(params=_without("mla_q_norm", "mla_k_norm")),
    "kda_where_the_mla_layer_belongs": dict(
        params=_kda_where_the_mla_layer_belongs),
}


@pytest.mark.parametrize("name", sorted(MUTATIONS))
def test_the_tolerance_is_tight(monkeypatch, served_f32, name):
    """The served path against the reference WITH one thing changed: the
    median over positions reads at least 1000 times its limit."""
    entries, seqs, params, _ = served_f32
    change = MUTATIONS[name]
    if "patch" in change:
        monkeypatch.setattr(reference, *change["patch"])
    params = change.get("params", lambda p: p)(params)
    want = reference_logits(params, seqs, **change.get("arch", {}))
    _, median, _ = readings(entries, seqs, want, every_position=False)
    assert median > 1000 * TOL[1], (name, median)


def test_the_mutant_is_the_reference(monkeypatch, served_f32):
    """With nothing changed the mutant reads what the reference reads."""
    _, seqs, params, _ = served_f32
    want = reference_logits(params, seqs[:1])
    monkeypatch.setattr(reference, "attention_kda", _mutant_kda())
    np.testing.assert_allclose(reference_logits(params, seqs[:1])[0],
                               want[0], atol=1e-5)
