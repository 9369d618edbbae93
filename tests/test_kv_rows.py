"""A KV pool row is a whole lane tile (PR 51): where head_dim < 128 an
engine keeps f = 128 / head_dim adjacent KV heads to one row of its device
pool, [L, Hkv / f, P, ps, f * hd] over the same bytes, because a pool of
narrower rows rests pages-minor on a TPU and every program that writes
rows re-lays both leaves out whole. The rule (shapes, the cache's kind and
`tp`, nothing a user sets), the served path over such rows against the
plain reference and against the same engine a head a row, the page movers
(a page leaves and enters a head a row whatever the pool holds, so two
ends of a transfer may differ), and the scale, which must stay the HEAD's.

PR 53, the last pool whose rows were no whole number of tiles: a latent
cache's ONE row a token (kv_lora_rank + qk_rope_head_dim values, 576 at
the published widths: 4.5 tiles) is stored in the next multiple of 128
lanes, zeros in the pad of the stored row and of the query
(`kv_row_lanes`). The rule's latent cases, the served path over padded
rows against the plain reference and against the same engine unpadded,
and the page movers: a page leaves at the model's width and comes back
with zero pad lanes (through the jitted movers: the engine's own entry
points still refuse a one-leaf cache, engine/config.UNSERVED).
"""
import asyncio
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import engine as eng_mod
from dynamo_tpu.engine.config import (
    EngineConfig, ModelConfig, kv_heads_per_row, kv_row_lanes, with_kv_rows,
)
from dynamo_tpu.engine.engine import NativeEngine
from dynamo_tpu.engine.scheduler import EngineRequest, SamplingParams
from dynamo_tpu.models import llama
from dynamo_tpu.observability.ledger import LEDGER_STATS
from dynamo_tpu.ops.attention import attend, dense_causal_attention
from dynamo_tpu.parallel.mesh import make_mesh
from tests import test_disagg, test_lfm2, test_ling, test_moonlight
from tests.test_decode_pipeline import _bench_config
from tests.test_ling import readings

# 4 KV heads of 32 under 8 query heads: f = 4 on one device (ONE row a
# token), 1 on a tp=2 mesh (a shard's 2 heads do not fill a row)
ROWS4 = ModelConfig(name="tiny-rows4", dtype="float32", num_heads=8,
                    num_kv_heads=4, head_dim=32, max_model_len=512)
# test_lfm2's conv hybrid with 64-wide heads: f = 2, the benchmark's case
LFM2_64 = dataclasses.replace(test_lfm2.TINY, name="tiny-lfm2-64",
                              head_dim=64, num_kv_heads=4, num_heads=8)


# -- (a) the rule ---------------------------------------------------------------

LATENT = dict(kv_lora_rank=512, qk_rope_head_dim=64)


@pytest.mark.parametrize("hd,hkv,tp,changes,want,lanes", [
    (64, 8, 1, {}, 2, 128),
    (32, 8, 1, {}, 4, 128),
    (128, 8, 1, {}, 1, 128),
    (96, 8, 1, {}, 1, 96),          # 96 does not divide a lane tile
    (256, 8, 1, {}, 1, 256),
    (64, 1, 1, {}, 1, 64),          # one head fills half a row
    (64, 8, 2, {}, 2, 128),
    (64, 8, 4, {}, 2, 128),
    (64, 8, 8, {}, 1, 64),          # a shard's one head fills half a row
    (32, 4, 2, {}, 1, 32),
    # a latent cache: one head a row whatever head_dim says, its 576
    # values in five whole tiles
    (64, 8, 1, LATENT, 1, 640),
    (128, 8, 1, LATENT, 1, 640),
    (64, 8, 2, LATENT, 1, 640),     # the rule asks no mesh (none serves it)
    # a width that is whole already stays; a narrow one takes one tile
    (64, 8, 1, dict(kv_lora_rank=448, qk_rope_head_dim=64), 1, 512),
    (64, 8, 1, dict(kv_lora_rank=32, qk_rope_head_dim=8), 1, 128),
    (64, 8, 1, dict(kv_lora_rank=128, qk_rope_head_dim=1), 1, 256),
    # the forms that keep the model's own rows, for either kind of cache
    (64, 8, 1, dict(kv_quant="int8"), 1, 64),   # a row's scale is per head
    (64, 8, 1, dict(decode_kernel="interpret"), 1, 64),
    (64, 8, 1, dict(decode_kernel="off"), 2, 128),
    (64, 8, 1, dict(LATENT, kv_quant="int8"), 1, 576),
    (64, 8, 1, dict(LATENT, decode_kernel="on"), 1, 576),
    (64, 8, 1, dict(LATENT, decode_kernel="off"), 1, 640),
], ids=lambda v: str(v).replace(" ", ""))
def test_the_rule_reads_shapes_the_caches_kind_and_tp(hd, hkv, tp, changes,
                                                      want, lanes):
    cfg = ModelConfig(num_heads=2 * hkv, num_kv_heads=hkv, head_dim=hd,
                      **changes)
    assert kv_heads_per_row(cfg, tp) == want
    assert kv_row_lanes(cfg, tp) == lanes
    served = with_kv_rows(cfg, tp)
    assert (served.kv_row_heads, served.kv_row_lanes) == (want, lanes)
    # the MODEL's bytes a token, whatever a row holds or is padded to
    assert served.kv_bytes_per_token() == cfg.kv_bytes_per_token()
    if not cfg.is_mla:
        assert served.kv_cache_leaves()["k"] == (hkv // want, want * hd)
        assert served.kv_row_pad == 0
        return
    # what is stored: one row of `lanes`, of which the pad is the pool's
    width = cfg.kv_lora_rank + cfg.qk_rope_head_dim
    assert cfg.kv_cache_leaves() == {"k": (1, width)}      # a raw config
    assert served.kv_cache_leaves() == {"k": (1, lanes)}
    assert served.kv_row_pad == lanes - width
    assert cfg.kv_bytes_per_token() == cfg.num_layers * width * 2


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
    assert streamed.model_cfg.kv_row_lanes == 0
    assert streamed.cache["k"].shape == (2, 4, 32, 16, 32)
    assert LEDGER_STATS.kv_row_lanes == 32


def test_the_engine_pads_a_latent_row_and_says_so():
    """The second gauge: the lanes a row is stored in, 128 for the tiny
    latent model's 32 + 8 values; `kv_bytes_per_token` beside it stays
    the model's 40 values a layer while a page's bytes are the pool's;
    whatever stands in the field is overwritten; and the forms that would
    keep the model's own rows (streamed decode, the tiers) are refused
    for a one-leaf cache before any pool is made."""
    ecfg = dict(page_size=16, num_pages=32, max_slots=2, max_model_len=256)
    tiny = test_moonlight.TINY
    eng = NativeEngine(dataclasses.replace(tiny, kv_row_lanes=256),
                       EngineConfig(**ecfg))
    assert (eng.model_cfg.kv_row_lanes, eng.model_cfg.kv_row_pad) \
        == (128, 88)
    assert LEDGER_STATS.kv_row_lanes == 128
    assert LEDGER_STATS.kv_heads_per_row == 1
    assert {k: v.shape for k, v in eng.cache.items()} \
        == {"k": (3, 1, 32, 16, 128)}
    assert LEDGER_STATS.kv_bytes_per_token == tiny.kv_bytes_per_token() \
        == 3 * 40 * 4
    assert eng.metrics().kv_page_bytes == 3 * 16 * 128 * 4
    assert llama.attn_scale(eng.model_cfg) == tiny.query_scale == 24 ** -0.5
    # no scale is read from the operand's width, named or not
    assert llama.attn_scale(dataclasses.replace(
        eng.model_cfg, query_scale=0.0)) == 24 ** -0.5
    with pytest.raises(ValueError, match="ONE cache leaf.*--stream-pages"):
        NativeEngine(tiny, EngineConfig(**ecfg, host_pages=8,
                                        stream_pages=2))


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


@pytest.mark.parametrize("name,mod", [
    ("rehearsal-tiny-moonlight", test_moonlight),
    ("rehearsal-tiny-ling", test_ling)])
def test_padded_latent_rows_serve_the_references_and_the_unpadded_logits(
        name, mod):
    """The benchmark's rehearsal configurations in float32, served as
    their test files serve them (prefill chunks, mixed steps, decode
    windows): over rows of 64 + 16 values in one 128-lane tile the
    logits are the plain reference's inside the file's own limits, and
    the SAME engine with the rule patched off (an 80-wide pool, the
    parent's programs) serves the same tokens and logits that differ by
    float32 rounding at most: the pad adds exact zeros to every score, a
    128-long sum may only be taken in another order than an 80-long
    one."""
    cfg = _bench_config(name)
    width = cfg.latent_width
    assert 0 < width < 128

    def run(lanes):
        with pytest.MonkeyPatch.context() as mp:
            if not lanes:
                mp.setattr(eng_mod, "kv_row_lanes", lambda cfg, tp=1: 0)
            entries, seqs, eng = mod.served_run(mp, cfg)
        m = eng.metrics()
        assert m.mixed_steps > 0 and m.decode_windows > 0
        assert eng.cache["k"].shape[-1] == (lanes or width)
        assert LEDGER_STATS.kv_row_lanes == (lanes or width)
        assert LEDGER_STATS.kv_bytes_per_token == cfg.kv_bytes_per_token()
        return entries, seqs, jax.device_get(eng.params)
    entries, seqs, params = run(128)
    largest, median = readings(
        entries, seqs, mod.reference_logits(params, seqs, cfg))[:2]
    tol = mod.TOL["float32"] if isinstance(mod.TOL, dict) else mod.TOL
    assert largest < tol[0] and median < tol[1], (largest, median)
    plain, plain_seqs, _ = run(0)
    assert plain_seqs == seqs and len(plain) == len(entries)

    def in_order(found):    # the callbacks arrive in no fixed order
        return sorted(found, key=lambda e: (e[1], e[0], float(e[2][0])))
    for (tok, pos, got), (tok0, pos0, want) in zip(in_order(entries),
                                                   in_order(plain)):
        assert (tok, pos) == (tok0, pos0)
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


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


def test_a_latent_page_leaves_at_the_models_width_and_returns_zero_padded(
        monkeypatch):
    """The latent case of the test above, through the jitted movers (the
    engine's entry points refuse a one-leaf cache by name, as they did:
    the wire, the tiers and the shared pool name "k" AND "v"). The same
    pool bytes held 128 lanes a row and 40 (the rule switched off for the
    second engine, whose prefilled pool the first is handed zero-padded):
    the extracted pages are [L, 1, Nb, ps, 40] and equal BYTE FOR BYTE.
    Injected into a pool whose every lane holds a one (whole, and by a
    slice of layers), the page's 40 values land and its pad lanes are
    ZEROS, not what the slot held; an untouched page keeps its ones."""
    tiny = test_moonlight.TINY
    prompt = list(range(10, 47))
    padded = _engine(tiny)
    pages = _prefilled(padded, prompt)
    ids = jnp.asarray(padded._bucket_ids(pages))
    n = len(pages)
    assert padded.model_cfg.kv_row_pad == 88
    own = jax.device_get(padded._extract_fn(padded.cache, ids))
    with pytest.raises(ValueError, match="whole-page extraction"):
        padded.extract_pages(pages)
    monkeypatch.setattr(eng_mod, "kv_row_lanes", lambda cfg, tp=1: 0)
    plain = _engine(tiny)
    assert plain.model_cfg.kv_row_pad == 0
    assert plain.cache["k"].shape == (3, 1, 64, 8, 40)
    assert _prefilled(plain, prompt) == pages
    want = jax.device_get(plain._extract_fn(plain.cache, ids))
    monkeypatch.undo()
    padded.cache = {"k": jnp.pad(plain.cache["k"],
                                 [(0, 0)] * 4 + [(0, 88)])}
    got = jax.device_get(padded._extract_fn(padded.cache, ids))
    assert set(got) == {"k"} and got["k"].shape == want["k"].shape \
        == (3, 1, len(ids), 8, 40)
    assert got["k"].tobytes() == want["k"].tobytes()
    np.testing.assert_allclose(own["k"][:, :, :n], want["k"][:, :, :n],
                               atol=1e-5)
    spare = next(p for p in range(64) if p not in pages)
    shard = jax.jit(functools.partial(
        eng_mod._inject_pages_slice, slices=((0, 1, 2),),
        pad=padded.model_cfg.kv_row_pad), donate_argnums=(0,))
    for inject, layers in ((padded._inject_fn, slice(0, 3)),
                           (shard, slice(1, 3))):
        ones = {"k": jnp.ones((3, 1, 64, 8, 128), jnp.float32)}
        back = np.asarray(inject(
            ones, ids, {"k": jnp.asarray(want["k"][layers])})["k"])
        landed = back[layers][:, :, pages]
        assert landed[..., :40].tobytes() \
            == want["k"][layers, :, :n].tobytes()
        assert not landed[..., 40:].any()
        assert (back[:, :, spare] == 1).all()
        assert (back[:layers.start] == 1).all()


@pytest.mark.parametrize("what,kw,says", [
    ("a transfer between pools of unlike rows",
     dict(mesh=True), "ONE cache leaf.*cannot be sharded"),
    ("the host tier", dict(host_pages=16), "ONE cache leaf.*--host-pages"),
])
def test_a_padded_latent_pool_is_still_asked_for_neither(what, kw, says):
    """The latent cases of the two tests below: the decode end's tp=2
    mesh and the host slab are refused for a one-leaf cache at
    construction, by name, as they were before its rows were padded; so
    nothing outside the device pool ever sees a 128-lane latent row."""
    if kw.pop("mesh", False):
        kw["mesh"] = make_mesh(tp=2, devices=jax.devices()[:2])
    with pytest.raises(ValueError, match=says):
        _engine(test_moonlight.TINY, **kw)


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
