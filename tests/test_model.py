"""Model-level tests: paged attention correctness against dense oracles.

Strategy mirrors the reference's hardware-independent unit tests (SURVEY.md
§4.5): tiny configs, CPU devices, exact comparisons where possible.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dynamo_tpu.engine.config import ModelConfig
from dynamo_tpu.models import llama
from dynamo_tpu.models.llama import AttnMetadata
from dynamo_tpu.ops.attention import (
    dense_causal_attention, paged_attention, write_kv_pages,
)

CFG = ModelConfig(dtype="float32")  # f32 on CPU for tight comparisons


def test_paged_attention_matches_dense():
    """Scatter KV into shuffled pages; paged attn must equal dense attn."""
    rng = np.random.default_rng(0)
    b, t, h, hkv, hd, ps = 2, 48, 4, 2, 16, 8
    n_pages = 32
    q = jnp.asarray(rng.standard_normal((b, t, h, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, t, hkv, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, t, hkv, hd)), jnp.float32)

    # assign each sequence non-contiguous pages
    perm = rng.permutation(n_pages)
    pages_per_seq = t // ps
    page_table = np.zeros((b, pages_per_seq + 2), np.int32)  # padded bucket
    k_cache = jnp.zeros((hkv, n_pages, ps, hd), jnp.float32)
    v_cache = jnp.zeros((hkv, n_pages, ps, hd), jnp.float32)
    for i in range(b):
        pages = perm[i * pages_per_seq:(i + 1) * pages_per_seq]
        page_table[i, :pages_per_seq] = pages
        write_idx = np.array([pages[p // ps] * ps + p % ps for p in range(t)],
                             np.int32)[None, :]
        k_cache, v_cache = write_kv_pages(
            k_cache, v_cache, k[i:i + 1], v[i:i + 1], jnp.asarray(write_idx))

    positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
    kv_lens = jnp.full((b,), t, jnp.int32)
    out = paged_attention(q, k_cache, v_cache, jnp.asarray(page_table),
                          kv_lens, positions)
    expected = dense_causal_attention(q, k, v, positions)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=1e-5, atol=1e-5)


def test_write_kv_pages_drops_negative_indices():
    k_cache = jnp.zeros((1, 2, 4, 8), jnp.float32)
    v_cache = jnp.zeros((1, 2, 4, 8), jnp.float32)
    k_new = jnp.ones((1, 3, 1, 8), jnp.float32)
    write_idx = jnp.asarray([[0, -1, 5]], jnp.int32)
    k2, _ = write_kv_pages(k_cache, v_cache, k_new, k_new, write_idx)
    flat = np.asarray(k2).reshape(8, 8)
    assert flat[0].sum() == 8 and flat[5].sum() == 8
    assert np.abs(flat[[1, 2, 3, 4, 6, 7]]).sum() == 0


def _full_forward_logits(params, cfg, tokens_np):
    """Oracle: one prefill pass over the whole sequence, all positions."""
    t = len(tokens_np)
    ps = 8
    n_pages = (t + ps - 1) // ps + 1
    cache = llama.init_cache(cfg, n_pages, ps)
    meta = AttnMetadata(
        positions=jnp.arange(t, dtype=jnp.int32)[None],
        page_table=jnp.arange(n_pages, dtype=jnp.int32)[None],
        kv_lens=jnp.asarray([t], jnp.int32),
        write_idx=jnp.arange(t, dtype=jnp.int32)[None],
    )
    logits, _ = llama.forward(params, cfg, jnp.asarray(tokens_np)[None], cache, meta)
    return np.asarray(logits[0])


def test_chunked_prefill_and_decode_match_full_forward():
    """KV built incrementally (chunks + single-token decode) must give the
    same logits as one full-sequence pass."""
    cfg = CFG
    params = llama.init_params(jax.random.PRNGKey(1), cfg)
    rng = np.random.default_rng(2)
    t = 20
    tokens = rng.integers(0, cfg.vocab_size, t).astype(np.int32)
    full = _full_forward_logits(params, cfg, tokens)

    ps = 8
    n_pages = 8
    cache = llama.init_cache(cfg, n_pages, ps)
    page_table = jnp.arange(n_pages, dtype=jnp.int32)[None]
    got = np.zeros_like(full)
    # chunked prefill: [0,8), [8,16)
    for start, end in [(0, 8), (8, 16)]:
        meta = AttnMetadata(
            positions=jnp.arange(start, end, dtype=jnp.int32)[None],
            page_table=page_table,
            kv_lens=jnp.asarray([end], jnp.int32),
            write_idx=jnp.arange(start, end, dtype=jnp.int32)[None],
        )
        logits, cache = llama.forward(
            params, cfg, jnp.asarray(tokens[start:end])[None], cache, meta)
        got[start:end] = np.asarray(logits[0])
    # decode one token at a time: positions 16..19
    for pos in range(16, t):
        meta = AttnMetadata(
            positions=jnp.asarray([[pos]], jnp.int32),
            page_table=page_table,
            kv_lens=jnp.asarray([pos + 1], jnp.int32),
            write_idx=jnp.asarray([[pos]], jnp.int32),
        )
        logits, cache = llama.forward(
            params, cfg, jnp.asarray([[tokens[pos]]]), cache, meta)
        got[pos] = np.asarray(logits[0, 0])

    np.testing.assert_allclose(got, full, rtol=2e-4, atol=2e-4)


def test_moe_forward_runs():
    cfg = ModelConfig(name="tiny-moe", dtype="float32", num_experts=4,
                      num_experts_per_tok=2)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    logits = _full_forward_logits(params, cfg, np.arange(10, dtype=np.int32))
    assert logits.shape == (10, cfg.vocab_size)
    assert np.isfinite(logits).all()


def test_moe_dispatch_matches_dense_compute():
    """Capacity dispatch (EP path) == dense-compute oracle when nothing is
    dropped (capacity_factor = E guarantees room for any routing)."""
    import dataclasses

    from dynamo_tpu.ops.moe import moe_dispatch_mlp

    cfg = ModelConfig(name="tiny-moe", dtype="float32", num_experts=4,
                      num_experts_per_tok=2)
    params = llama.init_params(jax.random.PRNGKey(1), cfg)
    lp = jax.tree.map(lambda a: a[0], params["layers"])  # layer 0 weights
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((2, 12, cfg.hidden_size)),
                    jnp.float32)
    dense = llama._moe_mlp(x, lp, cfg)
    disp = moe_dispatch_mlp(x, lp, cfg, capacity_factor=float(cfg.num_experts))
    np.testing.assert_allclose(np.asarray(disp), np.asarray(dense),
                               rtol=1e-4, atol=1e-4)


def test_moe_dispatch_drop_accounting():
    """Forced routing imbalance: the (dropped, routed) counters are exact.

    ADVICE r1 (medium): GShard capacity dispatch drops tokens silently;
    the counters make the degradation observable."""
    from dynamo_tpu.ops.moe import moe_dispatch_mlp

    cfg = ModelConfig(name="tiny-moe", dtype="float32", num_experts=4,
                      num_experts_per_tok=2)
    params = llama.init_params(jax.random.PRNGKey(1), cfg)
    lp = dict(jax.tree.map(lambda a: a[0], params["layers"]))
    t, k, e = 16, cfg.num_experts_per_tok, cfg.num_experts
    rng = np.random.default_rng(5)
    x_np = rng.standard_normal((1, t, cfg.hidden_size)).astype(np.float32)
    out, stats = moe_dispatch_mlp(
        jnp.asarray(x_np), lp, cfg, capacity_factor=0.25,
        return_dropped=True)
    dropped, routed = stats["moe_dropped"], stats["moe_routed"]
    # numpy replication of the routing + capacity accounting
    logits = x_np[0] @ np.asarray(lp["router"], np.float32)       # [t, e]
    top2 = np.argsort(-logits, axis=-1, kind="stable")[:, :k]     # [t, k]
    cap = max(int(t * k / e * 0.25), 1)                           # 2
    counts = np.zeros(e, np.int64)
    kept = 0
    for tok in range(t):                  # token-major order, like cumsum
        for c in range(k):
            ex = top2[tok, c]
            if counts[ex] < cap:
                kept += 1
            counts[ex] += 1
    assert int(routed) == t * k
    assert int(dropped) == t * k - kept
    assert int(dropped) > 0, "capacity 0.25 must actually drop"
    assert np.isfinite(np.asarray(out)).all()


def test_moe_dispatch_parity_and_no_drops_at_shipped_capacity():
    """At the shipped capacity_factor=2.0 with near-balanced routing the
    dispatch path matches the dense oracle exactly and drops nothing —
    the parity coverage ADVICE r1 flagged as missing for the serving
    default."""
    from dynamo_tpu.ops.moe import moe_dispatch_mlp

    cfg = ModelConfig(name="tiny-moe", dtype="float32", num_experts=4,
                      num_experts_per_tok=2)
    params = llama.init_params(jax.random.PRNGKey(1), cfg)
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.standard_normal((2, 24, cfg.hidden_size)),
                    jnp.float32)
    disp, stats = moe_dispatch_mlp(x, lp, cfg, return_dropped=True)
    assert int(stats["moe_dropped"]) == 0, (
        "seeded routing should stay under capacity at the shipped factor")
    dense = llama._moe_mlp(x, lp, cfg)
    np.testing.assert_allclose(np.asarray(disp), np.asarray(dense),
                               rtol=1e-4, atol=1e-4)


def test_moe_engine_surfaces_drop_counters():
    """Engine-level: a dispatch-MoE engine accumulates routed/dropped."""
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import NativeEngine
    from dynamo_tpu.engine.scheduler import SamplingParams

    cfg = ModelConfig(name="tiny-moe", dtype="float32", num_experts=4,
                      num_experts_per_tok=2, max_model_len=128)
    ecfg = EngineConfig(page_size=8, num_pages=16, max_slots=2,
                        max_prefill_chunk=16, prefill_buckets=(8, 16),
                        max_model_len=128)
    eng = NativeEngine(cfg, ecfg, seed=0)
    out = eng.generate(list(range(10)),
                       SamplingParams(max_tokens=3, ignore_eos=True), "m")
    assert len(out) == 3
    assert eng.moe_routed_tokens > 0
    assert 0.0 <= eng.moe_drop_rate() <= 1.0


def test_moe_dispatch_sharded_over_ep_mesh():
    """Expert weights sharded over an ep mesh axis; jit compiles + matches."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dynamo_tpu.ops.moe import moe_dispatch_mlp
    from dynamo_tpu.parallel.mesh import make_mesh

    cfg = ModelConfig(name="tiny-moe", dtype="float32", num_experts=4,
                      num_experts_per_tok=2)
    params = llama.init_params(jax.random.PRNGKey(1), cfg)
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    mesh = make_mesh(ep=4, tp=2)
    shard = {
        "router": NamedSharding(mesh, P(None, None)),
        "w_gate": NamedSharding(mesh, P("ep", None, "tp")),
        "w_up": NamedSharding(mesh, P("ep", None, "tp")),
        "w_down": NamedSharding(mesh, P("ep", "tp", None)),
    }
    lp_sh = {k: (jax.device_put(v, shard[k]) if k in shard else v)
             for k, v in lp.items()}
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.standard_normal((1, 16, cfg.hidden_size)),
                    jnp.float32)
    ref = moe_dispatch_mlp(x, lp, cfg, capacity_factor=4.0)
    got = jax.jit(lambda a, w: moe_dispatch_mlp(a, w, cfg, 4.0))(x, lp_sh)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_moe_dispatch_sharded_shard_map_matches_and_bounds_memory():
    """The explicit shard_map EP dispatch (O(E/ep) per-shard buffers,
    VERDICT r2 next #7) matches the dense dispatch, keeps drop accounting,
    and its compiled per-shard dispatch tensors carry only E/ep experts."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dynamo_tpu.ops.moe import moe_dispatch_mlp, moe_dispatch_mlp_sharded
    from dynamo_tpu.parallel.mesh import make_mesh

    cfg = ModelConfig(name="tiny-moe", dtype="float32", num_experts=4,
                      num_experts_per_tok=2)
    params = llama.init_params(jax.random.PRNGKey(1), cfg)
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    mesh = make_mesh(ep=4, tp=2)
    shard = {
        "router": NamedSharding(mesh, P(None, None)),
        "w_gate": NamedSharding(mesh, P("ep", None, "tp")),
        "w_up": NamedSharding(mesh, P("ep", None, "tp")),
        "w_down": NamedSharding(mesh, P("ep", "tp", None)),
    }
    lp_sh = {k: (jax.device_put(v, shard[k]) if k in shard else v)
             for k, v in lp.items()}
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((1, 16, cfg.hidden_size)),
                    jnp.float32)
    ref, want = moe_dispatch_mlp(
        x, lp, cfg, capacity_factor=2.0, return_dropped=True)
    fn = jax.jit(lambda a, w: moe_dispatch_mlp_sharded(
        a, w, cfg, mesh, 2.0, return_dropped=True))
    got, stats = fn(x, lp_sh)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)
    assert sorted(stats) == sorted(want)
    for key in want:
        assert float(stats[key]) == float(want[key]), key
    # compiled-HLO check: no per-shard buffer carries the FULL expert dim
    # with a capacity axis — dispatch/combine must be [_, S, E/ep, C]
    txt = fn.lower(x, lp_sh).compile().as_text()
    s_tok, e, cap = 16 * 2, 4, 16  # S = T*k; cap = T*k/E*2.0
    full = f"{s_tok},{e},{cap}"      # what the dense path would allocate
    local = f"{s_tok},{e // 4},{cap}"
    assert local.lower() in txt.lower().replace(" ", ""), "local dispatch missing"
    assert full.lower() not in txt.lower().replace(" ", ""), \
        "full-expert capacity buffer present on a shard"


# -- the KV pool through forward(): written in place, read by (layer, page) --

def _reference_forward(params, cfg, tokens, cache, meta):
    """The plain layer loop forward() is held to: layer by layer, slice that
    layer's pool out of the stack, write the new rows into it
    (write_kv_pages), read it back (paged_attention), put it back. Returns
    (logits [B, Tq, V], cache, aux) like forward(with_aux=True)."""
    from dynamo_tpu.models.llama import apply_rope, rms_norm, scale_embeds
    from dynamo_tpu.ops.attention import _softcap, write_kv_pages_quant
    from dynamo_tpu.ops.quant import wmat
    b, tq = tokens.shape
    h, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    kvq = bool(cfg.kv_quant)
    eps, p1 = cfg.rms_norm_eps, cfg.norm_plus_one
    # the test draws its ids in range  # dynalint: disable-next-line=R1
    x = scale_embeds(jnp.take(params["embed"], tokens, axis=0), cfg)
    cache = dict(cache)
    lw = cfg.layer_windows()
    dropped = routed = 0.0
    for li in range(cfg.num_layers):
        lp = jax.tree_util.tree_map(lambda a: a[li], params["layers"])
        xn = rms_norm(x, lp["attn_norm"], eps, p1)
        q, k, v = (jnp.einsum("btd,de->bte", xn, wmat(lp[w], xn.dtype))
                   for w in ("wq", "wk", "wv"))
        q = apply_rope(q.reshape(b, tq, h, hd), meta.positions,
                       cfg.rope_theta)
        k = apply_rope(k.reshape(b, tq, hkv, hd), meta.positions,
                       cfg.rope_theta)
        v = v.reshape(b, tq, hkv, hd)
        layer = {key: leaf[li] for key, leaf in cache.items()}
        if kvq:
            layer = dict(zip(("k", "v", "k_scale", "v_scale"),
                             write_kv_pages_quant(
                                 layer["k"], layer["v"], layer["k_scale"],
                                 layer["v_scale"], k, v, meta.write_idx)))
        else:
            layer["k"], layer["v"] = write_kv_pages(
                layer["k"], layer["v"], k, v, meta.write_idx)
        cache = {key: cache[key].at[li].set(layer[key]) for key in cache}
        attn = paged_attention(
            q, layer["k"], layer["v"], meta.page_table, meta.kv_lens,
            meta.positions, softcap=cfg.attn_softcap,
            window=None if lw is None else jnp.int32(lw[li]),
            q_scale=cfg.query_scale, k_scale=layer.get("k_scale"),
            v_scale=layer.get("v_scale"))
        attn = jnp.einsum("bte,ed->btd", attn.reshape(b, tq, h * hd),
                          wmat(lp["wo"], x.dtype))
        if cfg.post_norms:
            attn = rms_norm(attn, lp["post_attn_norm"], eps, p1)
        x = x + attn
        xn = rms_norm(x, lp["mlp_norm"], eps, p1)
        mlp, stats = llama._mlp_block(xn, lp, cfg, None,
                                      meta.write_idx >= 0)
        if stats is not None:
            dropped = dropped + stats["moe_dropped"]
            routed = routed + stats["moe_routed"]
        if cfg.post_norms:
            mlp = rms_norm(mlp, lp["post_mlp_norm"], eps, p1)
        x = x + mlp
    x = rms_norm(x, params["final_norm"], eps, p1)
    logits = _softcap(jnp.einsum(
        "btd,dv->btv", x, wmat(params["lm_head"], x.dtype)
    ).astype(jnp.float32), cfg.final_softcap)
    aux = ({"moe_dropped": dropped, "moe_routed": routed}
           if cfg.is_moe and cfg.moe_impl == "dispatch" else {})
    return logits, cache, aux


def _pool_case(prefix, n_valid, tq, ps=8):
    """Rows of one step: row i holds `prefix[i]` tokens already and brings
    `n_valid[i]` new ones in a chunk padded to `tq` (0 new tokens = a
    padding row). Pages are handed out shuffled. Padding tokens carry the
    last valid position and write_idx -1, as the scheduler builds them."""
    rng = np.random.default_rng(7)
    b = len(prefix)
    pb = max(-(-(p + n) // ps) for p, n in zip(prefix, n_valid)) + 1
    n_pages = b * pb + 3
    free = list(rng.permutation(n_pages))
    page_table = np.zeros((b, pb), np.int32)
    positions = np.zeros((b, tq), np.int32)
    write_idx = np.full((b, tq), -1, np.int32)
    kv_lens = np.zeros((b,), np.int32)
    for i, (p, n) in enumerate(zip(prefix, n_valid)):
        if n == 0:
            continue
        for j in range(-(-(p + n) // ps)):
            page_table[i, j] = free.pop()
        pos = np.minimum(p + np.arange(tq), p + n - 1)
        positions[i] = pos
        write_idx[i, :n] = page_table[i, pos[:n] // ps] * ps + pos[:n] % ps
        kv_lens[i] = p + n
    meta = AttnMetadata(positions=jnp.asarray(positions),
                        page_table=jnp.asarray(page_table),
                        kv_lens=jnp.asarray(kv_lens),
                        write_idx=jnp.asarray(write_idx))
    return meta, n_pages, ps


_MOE = dict(name="tiny-moe", num_experts=4, num_experts_per_tok=2)
_POOL_CASES = {
    # name: (config overrides, prefix per row, new tokens per row, Tq)
    "empty_prefix": ({}, [0, 0], [8, 5], 8),
    "prefix_and_padding_tokens": ({}, [13, 4, 0], [3, 8, 0], 8),
    # 5 rows x 16 = 80 token slots > KV_WRITE_BLOCK, 38 of them real
    "padding_rows_many_slots": ({}, [9, 0, 0, 30, 2], [16, 0, 5, 1, 16], 16),
    "chunk_crosses_pages": ({}, [5, 14], [8, 8], 8),
    "tq1": ({}, [11, 0, 7, 24], [1, 1, 0, 1], 1),
    # the Pallas decode kernel reads the stacked carry by layer index
    "tq1_kernel": (dict(decode_kernel="interpret"), [11, 0, 7, 24],
                   [1, 1, 0, 1], 1),
    "sliding_window_softcap": (
        dict(sliding_window=6, attn_softcap=20.0, query_scale=0.2,
             post_norms=True), [13, 2], [8, 7], 8),
    "moe": (_MOE, [6, 0, 17], [8, 0, 3], 8),
    "int8_pool": (dict(kv_quant="int8"), [13, 0, 4], [3, 8, 0], 8),
    "int8_pool_many_slots": (dict(kv_quant="int8"), [9, 0, 0, 30, 2],
                             [16, 0, 5, 1, 16], 16),
}


@pytest.mark.parametrize("case", sorted(_POOL_CASES))
def test_forward_writes_in_place_what_a_plain_layer_loop_writes(case):
    """forward() carries the stacked pool through its layer scan, scatters
    rows at (layer, head, page, slot) and gathers pages by (layer, page).
    Logits AND every leaf of the returned pool must be what the plain
    slice / write / read / put-back loop gives, starting from a pool that
    already holds other requests' KV (random values of order 1, so a row
    written to the wrong slot or a page read from the wrong layer shows
    at any tolerance)."""
    over, prefix, n_valid, tq = _POOL_CASES[case]
    cfg = ModelConfig(dtype="float32", **over)
    params = llama.init_params(jax.random.PRNGKey(3), cfg)
    meta, n_pages, ps = _pool_case(prefix, n_valid, tq)
    rng = np.random.default_rng(11)
    cache = llama.init_cache(cfg, n_pages, ps)
    cache = {key: (jnp.asarray(rng.integers(-127, 128, leaf.shape), jnp.int8)
                   if leaf.dtype == jnp.int8 else
                   jnp.asarray(rng.uniform(0.01, 0.03, leaf.shape)
                               if key.endswith("_scale")
                               else rng.standard_normal(leaf.shape),
                               leaf.dtype))
             for key, leaf in cache.items()}
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (len(prefix), tq)),
                         jnp.int32)
    want_logits, want_cache, want_aux = _reference_forward(
        params, cfg, tokens, cache, meta)
    logits, got_cache, aux = jax.jit(
        lambda p, t, c, *m: llama.forward(p, cfg, t, c, AttnMetadata(*m),
                                          with_aux=True)
    )(params, tokens, cache, meta.positions, meta.page_table, meta.kv_lens,
      meta.write_idx)
    assert sorted(got_cache) == sorted(want_cache)
    written = np.zeros((n_pages * ps,), bool)
    written[np.asarray(meta.write_idx)[np.asarray(meta.write_idx) >= 0]] = True
    for key in want_cache:
        got, want = np.asarray(got_cache[key]), np.asarray(want_cache[key])
        flat = (got.shape[0], got.shape[1], n_pages * ps) + got.shape[4:]
        got, want = got.reshape(flat), want.reshape(flat)
        # slots the step does not name keep their bytes; the rows it wrote
        # agree to rounding (the loop above runs op by op, forward() fused;
        # an int8 value may round the other way)
        np.testing.assert_array_equal(
            got[:, :, ~written], np.asarray(cache[key]).reshape(flat)[
                :, :, ~written], err_msg=f"pool leaf {key}, untouched slots")
        np.testing.assert_allclose(
            got[:, :, written].astype(np.float32),
            want[:, :, written].astype(np.float32),
            rtol=2e-5, atol=1.0 if got.dtype == np.int8 else 2e-5,
            err_msg=f"pool leaf {key}, written rows")
    real = np.asarray(meta.write_idx) >= 0
    np.testing.assert_allclose(np.asarray(logits)[real],
                               np.asarray(want_logits)[real],
                               rtol=2e-5, atol=2e-5)
    assert set(want_aux) <= set(aux)
    for key in want_aux:
        assert float(aux[key]) == float(want_aux[key]), key


@pytest.mark.parametrize("n", [5, 32, 33, 70])
@pytest.mark.parametrize("scales", [False, True])
def test_write_kv_rows_matches_numpy(n, scales):
    """The stacked row writer against numpy, below, at and above one block
    (KV_WRITE_BLOCK = 32), a third of the slots padding, two layers of
    four written; what it does not name it does not touch."""
    from dynamo_tpu.ops.attention import kv_write_plan, write_kv_rows
    rng = np.random.default_rng(n)
    l, hkv, p, ps, hd = 4, 2, 11, 8, 16
    layers = np.array([2, 0], np.int32)
    pools = [rng.standard_normal((l, hkv, p, ps, hd)).astype(np.float32)]
    rows = [rng.standard_normal((2, n, hkv, hd)).astype(np.float32)]
    if scales:
        pools.append(rng.uniform(size=(l, hkv, p, ps)).astype(np.float32))
        rows.append(rng.uniform(size=(2, n, hkv)).astype(np.float32))
    write_idx = rng.permutation(p * ps)[:n].astype(np.int32)
    write_idx[rng.permutation(n)[:n // 3]] = -1
    got = jax.jit(lambda pools, rows, idx, layers: write_kv_rows(
        pools, rows, kv_write_plan(idx), layers))(
            tuple(map(jnp.asarray, pools)), tuple(map(jnp.asarray, rows)),
            jnp.asarray(write_idx), jnp.asarray(layers))
    for pool, new, out in zip(pools, rows, got):
        want = pool.copy()
        for li, layer in enumerate(layers):
            for i, w in enumerate(write_idx):
                if w >= 0:
                    want[layer, :, w // ps, w % ps] = new[li, i]
        np.testing.assert_array_equal(np.asarray(out), want)


def test_gather_pages_by_layer_is_the_slice_then_take():
    from dynamo_tpu.ops.attention import gather_pages
    rng = np.random.default_rng(5)
    stack = jnp.asarray(rng.standard_normal((3, 2, 9, 4, 8)), jnp.float32)
    scale = jnp.asarray(rng.standard_normal((3, 2, 9, 4)), jnp.float32)
    page_table = jnp.asarray(rng.integers(0, 9, (5, 3)), jnp.int32)
    for layer in range(3):
        for leaf in (stack, scale):
            np.testing.assert_array_equal(
                np.asarray(jax.jit(gather_pages)(leaf, page_table,
                                                 jnp.int32(layer))),
                np.asarray(gather_pages(leaf[layer], page_table)))
