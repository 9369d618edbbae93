"""A KV pool row is a whole lane tile (PR 51): where head_dim < 128 an
engine keeps f = 128 / head_dim adjacent KV heads to one row of its device
pool, [L, Hkv / f, P, ps, f * hd] over the same bytes, because a pool of
narrower rows rests pages-minor on a TPU and every program that writes
rows re-lays both leaves out whole. The rule (shapes, the cache's kind and
`tp`, nothing a user sets), the served path over such rows against the
plain reference and against the same engine a head a row, the page movers
(a page leaves and enters a head a row whatever the pool holds, so two
ends of a transfer may differ), and the scale, which must stay the HEAD's.
"""
import asyncio
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import engine as eng_mod
from dynamo_tpu.engine.config import (
    EngineConfig, ModelConfig, kv_heads_per_row, with_kv_rows,
)
from dynamo_tpu.engine.engine import NativeEngine
from dynamo_tpu.engine.scheduler import EngineRequest, SamplingParams
from dynamo_tpu.models import llama
from dynamo_tpu.observability.ledger import LEDGER_STATS
from dynamo_tpu.ops.attention import attend, dense_causal_attention
from dynamo_tpu.parallel.mesh import make_mesh
from tests import test_disagg, test_lfm2
from tests.test_ling import readings

# 4 KV heads of 32 under 8 query heads: f = 4 on one device (ONE row a
# token), 1 on a tp=2 mesh (a shard's 2 heads do not fill a row)
ROWS4 = ModelConfig(name="tiny-rows4", dtype="float32", num_heads=8,
                    num_kv_heads=4, head_dim=32, max_model_len=512)
# test_lfm2's conv hybrid with 64-wide heads: f = 2, the benchmark's case
LFM2_64 = dataclasses.replace(test_lfm2.TINY, name="tiny-lfm2-64",
                              head_dim=64, num_kv_heads=4, num_heads=8)


# -- (a) the rule ---------------------------------------------------------------

@pytest.mark.parametrize("hd,hkv,tp,changes,want", [
    (64, 8, 1, {}, 2),
    (32, 8, 1, {}, 4),
    (128, 8, 1, {}, 1),
    (96, 8, 1, {}, 1),              # 96 does not divide a lane tile
    (256, 8, 1, {}, 1),
    (64, 1, 1, {}, 1),              # one head fills half a row
    (64, 8, 2, {}, 2),
    (64, 8, 4, {}, 2),
    (64, 8, 8, {}, 1),              # a shard's one head fills half a row
    (32, 4, 2, {}, 1),
    (64, 8, 1, dict(kv_lora_rank=512, qk_rope_head_dim=64), 1),  # latent
    (64, 8, 1, dict(kv_quant="int8"), 1),   # a row's scale is per head
    (64, 8, 1, dict(decode_kernel="interpret"), 1),
    (64, 8, 1, dict(decode_kernel="off"), 2),
], ids=lambda v: str(v).replace(" ", ""))
def test_the_rule_reads_shapes_the_caches_kind_and_tp(hd, hkv, tp, changes,
                                                      want):
    cfg = ModelConfig(num_heads=2 * hkv, num_kv_heads=hkv, head_dim=hd,
                      **changes)
    assert kv_heads_per_row(cfg, tp) == want
    served = with_kv_rows(cfg, tp)
    assert served.kv_row_heads == want
    # what is stored: the same bytes a token, whatever a row holds
    assert served.kv_bytes_per_token() == cfg.kv_bytes_per_token()
    if not cfg.is_mla:
        assert served.kv_cache_leaves()["k"] == (hkv // want, want * hd)


def test_the_engine_resolves_it_and_says_so():
    """Whatever stands in the field is overwritten by the rule; the gauge
    beside `kv_bytes_per_token` says what the pool holds; streamed decode
    keeps a head a row (its staged pages meet the resident ones in the
    form they travel in)."""
    ecfg = dict(page_size=16, num_pages=32, max_slots=2, max_model_len=256)
    eng = NativeEngine(dataclasses.replace(ROWS4, kv_row_heads=2),
                       EngineConfig(**ecfg))
    assert eng.model_cfg.kv_row_heads == 4
    assert LEDGER_STATS.kv_heads_per_row == 4
    leaves = eng.model_cfg.kv_cache_leaves()
    assert leaves == {"k": (1, 128), "v": (1, 128)}
    assert {k: v.shape for k, v in eng.cache.items()} == {
        k: (2, 1, 32, 16, 128) for k in leaves}
    assert eng.metrics().kv_page_bytes == 16 * ROWS4.kv_bytes_per_token()
    plain = NativeEngine(ModelConfig(dtype="float32", max_model_len=256),
                         EngineConfig(**ecfg))
    assert plain.model_cfg.kv_row_heads == 1      # 2 heads of 32
    assert LEDGER_STATS.kv_heads_per_row == 1
    streamed = NativeEngine(ROWS4, EngineConfig(
        **ecfg, host_pages=8, stream_pages=2))
    assert streamed.model_cfg.kv_row_heads == 1
    assert streamed.cache["k"].shape == (2, 4, 32, 16, 32)


# -- (b) served over shared rows against the plain reference ---------------------

@pytest.fixture(scope="module")
def served_rows():
    """test_lfm2's served run (prefill chunks, mixed steps, decode
    windows; a 70-token prompt crosses four 16-token pages and two
    32-token chunk edges) of the conv hybrid with 64-wide heads."""
    with pytest.MonkeyPatch.context() as mp:
        entries, seqs, eng = test_lfm2.served_run(mp, cfg=LFM2_64)
        m = eng.metrics()
        return (entries, seqs, jax.device_get(eng.params),
                dict(mixed=m.mixed_steps, windows=m.decode_windows,
                     f=eng.model_cfg.kv_row_heads,
                     cache={k: v.shape for k, v in eng.cache.items()}))


def test_served_logits_over_shared_rows_match_the_plain_reference(
        served_rows):
    entries, seqs, params, stats = served_rows
    assert stats["f"] == 2 and stats["cache"]["k"] == (2, 2, 64, 16, 128) \
        == stats["cache"]["v"]
    assert stats["mixed"] > 0 and stats["windows"] > 0, stats
    largest, median, _ = readings(
        entries, seqs, test_lfm2.reference_logits(params, seqs, LFM2_64))
    assert largest < test_lfm2.TOL[0] and median < test_lfm2.TOL[1], \
        (largest, median)


def test_a_scale_read_from_the_rows_width_fails_the_comparison(
        served_rows, monkeypatch):
    """The trap: `ops/attention._scale` takes the width from the operand's
    last axis, 128 where rows are shared. Served that way the comparison
    fails by orders of magnitude: it is `attn_scale` that holds it."""
    assert llama.attn_scale(with_kv_rows(LFM2_64)) == 64 ** -0.5
    assert llama.attn_scale(LFM2_64) == 0.0      # a head a row: as it was
    gemma = dataclasses.replace(LFM2_64, query_scale=0.0625)
    assert llama.attn_scale(with_kv_rows(gemma)) == 0.0625
    monkeypatch.setattr(llama, "attn_scale", lambda cfg: cfg.query_scale)
    entries, seqs, eng = test_lfm2.served_run(monkeypatch, cfg=LFM2_64)
    largest, median, _ = readings(
        entries, seqs, test_lfm2.reference_logits(
            jax.device_get(eng.params), seqs, LFM2_64))
    assert median > 300 * test_lfm2.TOL[1], (largest, median)


@pytest.mark.parametrize("h,hkv,hd", [(8, 4, 64), (8, 8, 32), (4, 4, 32)])
def test_attention_over_shared_rows_is_attention_over_heads(h, hkv, hd):
    """The form alone, no engine: queries zero outside their KV head's
    lanes against rows of f heads, the scale named, each head's own lanes
    kept, against the oracle over heads; and the same with the scale left
    to the operand's width, which is not."""
    cfg = with_kv_rows(ModelConfig(num_heads=h, num_kv_heads=hkv,
                                   head_dim=hd))
    f = cfg.kv_row_heads
    assert f == 128 // hd
    b, t = 2, 12
    q, k, v = (jax.random.normal(key, (b, t, n, hd), jnp.float32)
               for key, n in zip(jax.random.split(jax.random.PRNGKey(0), 3),
                                 (h, hkv, hkv)))
    pos = jnp.broadcast_to(jnp.arange(t), (b, t))
    want = dense_causal_attention(q, k, v, pos)

    def rows(a):        # [B, T, Hkv, hd] -> gathered rows [Hkv/f, B, T, f*hd]
        return a.reshape(b, t, hkv // f, f * hd).transpose(2, 0, 1, 3)
    lens = jnp.full((b,), t, jnp.int32)
    got = attend(llama._query_rows(q, cfg), rows(k), rows(v), lens, pos,
                 q_scale=llama.attn_scale(cfg))
    np.testing.assert_allclose(llama._head_values(got, cfg), want,
                               atol=2e-6)
    trap = attend(llama._query_rows(q, cfg), rows(k), rows(v), lens, pos)
    assert float(jnp.max(jnp.abs(llama._head_values(trap, cfg) - want))) \
        > 1e-2


# -- (c) pages leave and enter a head a row ---------------------------------------

def _engine(cfg, mesh=None, **kw):
    return NativeEngine(cfg, EngineConfig(**dict(dict(
        page_size=8, num_pages=64, max_slots=4, max_prefill_chunk=32,
        prefill_buckets=(8, 16, 32), max_model_len=512), **kw)),
        mesh=mesh, seed=0)


def _prefilled(eng, prompt):
    eng.add_request(EngineRequest("p", prompt, SamplingParams(
        max_tokens=4, ignore_eos=True), prefill_only=True))
    while eng.has_work():
        eng.step()
    return eng.scheduler.parked["p"].pages


def test_a_page_of_shared_rows_leaves_as_the_page_of_heads(monkeypatch):
    """The same pool bytes held 4 heads a row and a head a row (the rule
    switched off for the second engine, whose prefilled pool the first is
    handed re-viewed): the extracted pages are [L, Hkv, Nb, ps, hd] and
    equal BYTE FOR BYTE; what the first engine's own prefill wrote is the
    same to a rounding of the attention's sums. Injected back (whole, and
    by slices cut inside a row) the pages restore the pool they came
    from."""
    prompt = list(range(10, 47))
    shared = _engine(ROWS4)
    pages = _prefilled(shared, prompt)
    own = jax.device_get(shared.extract_pages(pages))
    monkeypatch.setattr(eng_mod, "kv_heads_per_row", lambda cfg, tp=1: 1)
    plain = _engine(ROWS4)
    assert plain.model_cfg.kv_row_heads == 1
    assert _prefilled(plain, prompt) == pages
    want = jax.device_get(plain.extract_pages(pages))

    def as_rows(a, f=4):    # [L, Hkv, P, ps, hd] -> [L, Hkv/f, P, ps, f*hd]
        l, hkv, p, ps, hd = a.shape
        return a.reshape(l, hkv // f, f, p, ps, hd).transpose(
            0, 1, 3, 4, 2, 5).reshape(l, hkv // f, p, ps, f * hd)
    shared.cache = {key: jnp.asarray(as_rows(np.asarray(leaf)))
                    for key, leaf in plain.cache.items()}
    got = jax.device_get(shared.extract_pages(pages))
    for key in ("k", "v"):
        l, hkv, nb, ps, hd = got[key].shape
        assert (l, hkv, ps, hd) == (2, 4, 8, 32) and nb >= len(pages)
        assert got[key].shape == want[key].shape
        assert got[key].tobytes() == want[key].tobytes()
        np.testing.assert_allclose(own[key][:, :, :len(pages)],
                                   want[key][:, :, :len(pages)], atol=1e-5)
    # back in, into blank pools of either form: whole, then two slices of
    # KV heads that cut a row of four in the middle (heads 0..0, 1..3)
    for form, rule in (("shared", None), ("plain", 1)):
        if rule is None:
            monkeypatch.undo()
        else:
            monkeypatch.setattr(eng_mod, "kv_heads_per_row",
                                lambda cfg, tp=1: 1)
        whole, sliced = _engine(ROWS4), _engine(ROWS4)
        whole.inject_pages(pages, jnp.asarray(want["k"]),
                           jnp.asarray(want["v"]))
        for start, count in ((0, 1), (1, 3)):
            sliced.inject_pages_shard(
                pages, jnp.asarray(want["k"][:, start:start + count]),
                jnp.asarray(want["v"][:, start:start + count]),
                ((0, 0, 2), (1, start, count)))
        for eng in (whole, sliced):
            back = jax.device_get(eng.extract_pages(pages))
            n = len(pages)
            for key in ("k", "v"):
                assert back[key][:, :, :n].tobytes() \
                    == want[key][:, :, :n].tobytes(), (form, key)


def test_a_transfer_between_pools_of_unlike_rows_serves_the_same_tokens(
        monkeypatch):
    """Prefill on one device (4 heads a row), decode on a tp=2 mesh (a
    head a row): tests/test_disagg's stack, the pages resharded on the
    way as they always were."""
    monkeypatch.setattr(test_disagg, "CFG", ROWS4)
    devs = jax.devices()
    assert len(devs) >= 2
    decode_mesh = make_mesh(tp=2, devices=devs[:2])
    prompt = list(range(60, 85))
    params = SamplingParams(max_tokens=6, temperature=0.0, ignore_eos=True)
    expect = test_disagg.make_engine(decode_mesh).generate(
        prompt, params, "direct")

    async def main():
        from dynamo_tpu.runtime.engine import Context
        from dynamo_tpu.runtime.transports.memory import MemoryPlane
        decode, prefill = test_disagg._build_stack(
            MemoryPlane(), decode_mesh=decode_mesh)
        rows = (prefill.worker.engine.model_cfg.kv_row_heads,
                decode.engine.model_cfg.kv_row_heads)
        await decode.start()
        await prefill.start()
        try:
            toks, _ = await test_disagg._drive(decode.generate(
                test_disagg.pre_request("t1", prompt).model_dump(
                    exclude_none=True), Context("t1")))
        finally:
            await prefill.stop()
            await decode.stop()
        return toks, decode.remote_prefills, rows

    toks, n_remote, rows = asyncio.run(main())
    assert rows == (4, 1)
    assert n_remote == 1
    assert toks == expect


def test_the_host_tier_holds_pages_a_head_a_row_and_hands_them_back():
    """tests/test_offload's round trip over a pool of 4 heads a row: A's
    pages are evicted to the host slab (laid out [L, Hkv, ps, hd], as
    every pool's are), B runs, A is sent again and its pages come back
    into rows: the same tokens as an engine that never evicted."""
    params = SamplingParams(max_tokens=4, temperature=0.0, ignore_eos=True)
    prompt_a, prompt_b = list(range(10, 34)), list(range(100, 140))
    expect = _engine(ROWS4, max_slots=2).generate(prompt_a, params, "a")
    eng = _engine(ROWS4, num_pages=8, max_slots=2, host_pages=16)
    assert eng.model_cfg.kv_row_heads == 4
    assert eng.host_pool.k_slab.shape[1:] == (2, 4, 8, 32)
    assert eng.generate(prompt_a, params, "a1") == expect
    eng.generate(prompt_b, params, "b")
    assert eng.host_pool.stats.offloaded > 0
    assert eng.generate(prompt_a, params, "a2") == expect
    assert eng.host_pool.stats.onboarded > 0 \
        and eng.host_pool.stats.host_hits > 0
