"""Attention over a paged KV cache.

TPU-first design: both prefill (Tq tokens) and decode (Tq=1) run the same
"gather pages -> masked attention" computation with bucketed static shapes, so
XLA sees a small, fixed set of programs and everything lands on the MXU. The
page gather is a plain `take` on the page axis, which XLA lowers to an
efficient dynamic-gather; a Pallas kernel that reads HBM pages directly (no
materialized gather) lives in dynamo_tpu/ops/paged_attention.py and is used on
TPU for decode (dispatch in models/llama.py).

Gemma-2-class models add three knobs, threaded through every path here:
- `softcap`: attention logits pass through tanh(s/cap)*cap before masking;
- `window`: a per-call sliding-window width — keys with q_pos - k_pos >=
  window are masked. Passed as a TRACED scalar so a lax.scan over layers
  can alternate sliding/global layers (Gemma-2's pattern) with one
  compiled body: global layers just carry a 2**30 sentinel width.
- `q_scale`: query scaling override (query_pre_attn_scalar**-0.5);
  0.0 selects the standard head_dim**-0.5.

Reference equivalent: the engines' paged attention (vLLM/TRT-LLM internals) and
the KV block layout in lib/llm/src/kv/layer.rs:100-616. We keep K and V as
separate [n_kv_heads, num_pages, page_size, head_dim] arrays per layer
(stacked over layers) instead of the reference's 5-D
[2, blocks, block_size, heads, head_dim] tensor: head-major keeps one
(head, page) slice contiguous (the decode kernel's DMA unit) and lets the
kv-head axis shard cleanly over the `tp` mesh axis.

Every shape below is written for a pool row of ONE kv head, [L, Hkv, P,
ps, hd]. What is stored is `ModelConfig.kv_cache_leaves()`: where
head_dim < 128 an engine may keep f adjacent heads to a 128-lane row,
[L, Hkv / f, P, ps, f * hd] over the same bytes (engine/config.
kv_heads_per_row), and nothing here knows: models/llama.layer_front
hands these ops Hkv / f "heads" of width f * hd, queries that are zero
outside their own head's lanes, and the scale by name (`q_scale`; 0
would read the ROW's width, `_scale`).

The engine's programs touch the stacked leaves [L, Hkv, P, ps, hd] only
through `gather_pages(..., layer=)` (the pages a page table names, by
(layer, page)) and `write_kv_rows` (the rows a step produced, by (layer,
head, page, slot)), both in place in the stored layout; the per-layer
`write_kv_pages*` serve the pipeline-parallel and streaming layers, which
keep per-stage and per-layer pools of their own.
"""
# dynalint: hot-path — every op here runs inside jitted decode/prefill programs;
# host syncs (.item(), device_get, float()) are dynalint R6 findings
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from dynamo_tpu.ops.kv_quant import dequantize_rows, quantize_rows

NEG_INF = -1e30


def _scale(hd: int, q_scale: float) -> float:
    """`hd`: the operand's last axis, which is head_dim only while a pool
    row is one head; a caller whose rows hold more names the scale."""
    return q_scale if q_scale else hd ** -0.5


def _softcap(scores: jax.Array, cap: float) -> jax.Array:
    """tanh soft-cap (Gemma-2); identity when cap == 0 (trace-time)."""
    if not cap:
        return scores
    return jnp.tanh(scores / cap) * cap


@jax.named_scope("attention.gather")
def gather_pages(cache: jax.Array, page_table: jax.Array,
                 layer: Optional[jax.Array] = None) -> jax.Array:
    """[Hkv, P, ps, hd] gathered by [B, Pb] -> [Hkv, B, Pb*ps, hd]; a
    scale leaf [Hkv, P, ps] gives [Hkv, B, Pb*ps]. (As stored: Hkv and
    hd are the pool's rows a token and their width, whatever a row
    holds.)

    With `layer` (a traced int32 scalar) `cache` is the STACKED leaf
    [L, Hkv, P, ps, ...] and the pages are read by (layer, page) in one
    gather on it. `cache[layer]` followed by a take would do the same
    arithmetic, but XLA materializes the [Hkv, P, ps, hd] slice first: a
    copy of one layer's whole pool (134 MB at Mistral-7B widths and 1024
    pages) to read a few dozen pages of it (PERF.md section 6, PR 26)."""
    b, pb = page_table.shape
    ids = page_table.reshape(-1)
    if layer is None:
        gathered = jnp.take(cache, ids, axis=1)
    else:
        gathered = jax.lax.gather(
            cache,
            jnp.stack([jnp.full_like(ids, layer), ids], axis=-1),
            jax.lax.GatherDimensionNumbers(
                # output [Hkv, B*Pb, ps[, hd]]: what the take above gives
                offset_dims=(0,) + tuple(range(2, cache.ndim - 1)),
                collapsed_slice_dims=(0, 2), start_index_map=(0, 2)),
            slice_sizes=(1, cache.shape[1], 1) + cache.shape[3:],
            mode="clip")
    hkv, _, ps = gathered.shape[:3]
    return gathered.reshape((hkv, b, pb * ps) + gathered.shape[3:])


@jax.named_scope("attention.gather")
def gather_values(cache: jax.Array, scale: Optional[jax.Array],
                  page_table: jax.Array, dtype,
                  layer: Optional[jax.Array] = None) -> jax.Array:
    """The K or V a page table names, [Hkv, B, Pb*ps, hd]: the pages as
    stored, or on an int8 pool (`scale` given) dequantized at the gather
    boundary to `dtype` — the one codec read site of the gather paths."""
    pages = gather_pages(cache, page_table, layer)
    if scale is None:
        return pages
    return dequantize_rows(pages, gather_pages(scale, page_table, layer),
                           dtype)


@jax.named_scope("attention.gather")
def gather_kv(k_cache: jax.Array, v_cache: Optional[jax.Array],
              page_table: jax.Array, dtype,
              k_scale: Optional[jax.Array] = None,
              v_scale: Optional[jax.Array] = None,
              layer: Optional[jax.Array] = None) -> tuple:
    """(k, v), each [Hkv, B, Pb*ps, hd]: what `page_table` names of both
    leaves (gather_values); v is None for a one-leaf cache (`v_cache`
    None), whose gathered keys serve as values whole."""
    k = gather_values(k_cache, k_scale, page_table, dtype, layer)
    if v_cache is None:
        return k, None
    return k, gather_values(v_cache, v_scale, page_table, dtype, layer)


def attend(
    q: jax.Array,            # [B, Tq, H, hd]
    k: jax.Array,            # [Hkv, B, Lk, hd] — the rows' gathered keys
    v: Optional[jax.Array],  # [Hkv, B, Lk, hd]; None: the keys are the values
    kv_lens: jax.Array,      # [B] int32 — valid kv length per sequence
    q_positions: jax.Array,  # [B, Tq] int32 — absolute position of each query
    softcap: float = 0.0,
    window: Optional[jax.Array] = None,  # scalar int32 sliding width
    q_scale: float = 0.0,
) -> jax.Array:
    """Causal attention of every query of a [B, Tq] grid against its
    row's gathered keys (gather_kv): float32 scores, masked by the row's
    length, the query's position and the window, one softmax a query.
    Returns [B, Tq, H, hd]. THE arithmetic of the gather path: the grid
    form (paged_attention) is one call of it, the row form
    (attention_rows) one call a piece."""
    b, tq, h, hd = q.shape
    hkv, _, lk = k.shape[:3]
    g = h // hkv
    if v is None:
        v = k

    qg = q.reshape(b, tq, hkv, g, hd)
    scores = jnp.einsum(
        "btkgd,kbsd->bkgts", qg.astype(jnp.float32), k.astype(jnp.float32)
    )
    scores = _softcap(scores * _scale(hd, q_scale), softcap)

    kv_pos = jnp.arange(lk, dtype=jnp.int32)[None, :]          # [1, Lk]
    causal = kv_pos[:, None, :] <= q_positions[:, :, None]      # [B, Tq, Lk]
    valid = kv_pos < kv_lens[:, None]                           # [B, Lk]
    mask = causal & valid[:, None, :]                           # [B, Tq, Lk]
    if window is not None:
        # keep keys inside (q_pos - window, q_pos]
        mask = mask & (q_positions[:, :, None] - kv_pos[:, None, :] < window)
    scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)

    probs = jax.nn.softmax(scores, axis=-1)
    # rows past kv_lens are whatever the recycled page last held — zero
    # them so a stale non-finite value can't ride 0 * NaN through the
    # masked probabilities (the mask already zeroes their probs; IEEE
    # multiplication does not). Masked-out K is safe: the jnp.where on
    # scores discards it before the softmax.
    v = jnp.where(valid[None, :, :, None], v.astype(jnp.float32), 0.0)
    out = jnp.einsum("bkgts,kbsd->btkgd", probs, v)
    return out.reshape(b, tq, h, hd).astype(q.dtype)


@jax.named_scope("attention")
def paged_attention(
    q: jax.Array,            # [B, Tq, H, hd]
    k_cache: jax.Array,      # [Hkv, P, ps, hd]
    v_cache: Optional[jax.Array],  # [Hkv, P, ps, hd]; None: a one-leaf cache
    page_table: jax.Array,   # [B, Pb] int32
    kv_lens: jax.Array,      # [B] int32 — valid kv length per sequence
    q_positions: jax.Array,  # [B, Tq] int32 — absolute position of each query
    softcap: float = 0.0,
    window: Optional[jax.Array] = None,  # scalar int32 sliding width
    q_scale: float = 0.0,
    k_scale: Optional[jax.Array] = None,  # [Hkv, P, ps] f32 — int8 cache
    v_scale: Optional[jax.Array] = None,
    layer: Optional[jax.Array] = None,  # caches/scales are [L, ...] stacks
) -> jax.Array:
    """Causal attention of q against the paged KV prefix. Returns [B, Tq, H, hd].

    With `layer` the caches (and scales) are the stacked leaves and only
    the pages of that layer which `page_table` names are read
    (gather_pages). An int8 cache is dequantized at the gather boundary;
    downstream math is unchanged.

    A one-leaf cache (latent attention, `v_cache` None): here, in
    attention_rows, in decode_attention_split and in
    decode_attention_deferred the gathered keys serve as values whole,
    and the caller keeps the columns of the output that are its values
    (models/llama._mla_out): an eighth more multiply-adds than slicing
    the operand, and no copy of the gathered rows."""
    k, v = gather_kv(k_cache, v_cache, page_table, q.dtype, k_scale,
                     v_scale, layer)
    return attend(q, k, v, kv_lens, q_positions, softcap, window, q_scale)


class StepRows(NamedTuple):
    """What the row forms read of a step's plan (attention_rows; models/
    llama.kda_mix_rows, ssm_mix_rows), the same for every layer: computed
    once a program, outside the layer scan (`step_rows`)."""
    start: jax.Array    # [B] the token row that holds a row's first token
    n_valid: jax.Array  # [B] a row's real tokens
    order: jax.Array    # [B] rows, longest first
    n_long: jax.Array   # () chunk rows: rows of more than one token


def step_rows(valid: jax.Array, start: jax.Array) -> StepRows:
    """valid [B, T]: real tokens, a prefix of each row; start [B]: where
    in the step's B * T token rows a row's tokens begin. They are
    CONTIGUOUS there in both layouts a step has: on the grid row r
    starts at r * T, and a compact step (compact_index) keeps the grid's
    row-major order, so row r starts at the flat row of its first cell."""
    n_valid = jnp.sum(valid, axis=1).astype(jnp.int32)
    return StepRows(start.astype(jnp.int32), n_valid,
                    jnp.argsort(-n_valid).astype(jnp.int32),
                    jnp.sum(n_valid > 1).astype(jnp.int32))


def attention_rows_pay(chunk: int, heads: int, kv_values: int) -> bool:
    """Whether a compact `[rows, chunk]` step's attention takes the row
    form (attention_rows) or stays on the grid outside the `cond`
    (paged_attention), a STATIC fact of the step's shape and the model:
    a row and key of the grid's float32 scores (`heads` x `chunk`) hold
    at least as many values as the row and key gathered for them
    (`kv_values`: kv heads x width, summed over the cache's leaves).
    Where they do ([8, 64] and [64, 64] steps of every served model)
    the scores are what the grid form spends its time on and the row
    form takes 15-33 % of it; where they do not (a [32, 16] step: 512
    against Mistral's 2048, 256 against OLMoE's 4096) the gathered K / V
    are, and handing them to a `cond` cost more than the scores saved
    (21.8 -> 24.2 ms a Mistral step, 25.5 -> 33.6 an OLMoE one: PERF.md
    section 6, PR 47)."""
    return heads * chunk >= kv_values


def attention_rows(
    q: jax.Array,            # [N, H, hd] — the step's token rows
    k: jax.Array,            # [Hkv, B, Lk, hd] — the rows' gathered keys
    v: Optional[jax.Array],  # None: the keys are the values
    kv_lens: jax.Array,      # [B] int32
    q_positions: jax.Array,  # [B, Tq] int32, on the grid
    rows: StepRows,
    valid: jax.Array,        # [B, Tq] bool: real tokens, a prefix of each row
    softcap: float = 0.0,
    window: Optional[jax.Array] = None,
    q_scale: float = 0.0,
) -> jax.Array:
    """`attend` for a step's REAL queries alone, over its ROWS by what
    each holds and never over its [B, Tq] grid; q: the step's token rows
    in either layout (`step_rows`). Returns [N, H, hd]: each real token's
    row written, every other row zero.

    Every row's LAST real token is one query against the row's keys: one
    `attend` over [B, 1], which is all a decode row (a one-token row)
    asks for. A row of more (a chunk row): ONE a loop pass, for as many
    passes as the step holds such rows, its [1, Tq] queries against that
    row's keys alone (a slice of the gathered K / V; three rows a pass
    gathered them and cost twice the time at every served shape, PERF.md
    section 6, PR 47). Each real query sees the keys, mask, scale,
    softcap and float32 softmax that the grid form gives it; the grid's
    other cells (512 - 47 of a [32, 16] mixed step's, 4096 - 253 of a
    [64, 64] one's) are not computed."""
    n = q.shape[0]
    tq = valid.shape[1]
    att = functools.partial(attend, softcap=softcap, window=window,
                            q_scale=q_scale)
    last_t = jnp.maximum(rows.n_valid - 1, 0)
    last = rows.start + last_t
    o1 = att(q.at[last].get(mode="clip")[:, None], k, v, kv_lens,
             jnp.take_along_axis(q_positions, last_t[:, None], axis=1))
    o = jnp.zeros_like(q).at[
        jnp.where(rows.n_valid == 1, last, n)].set(o1[:, 0], mode="drop")

    def one(a, r, axis=0):      # row r of `a`, kept as an axis of one
        return jax.lax.dynamic_slice_in_dim(a, r, 1, axis)

    def chunk_row(j, o):
        r = rows.order[j]       # the longest rows lead: one of `n_long`
        # its token rows, past the step's last one read clipped
        cells = rows.start[r] + jnp.arange(tq, dtype=jnp.int32)
        o_r = att(q.at[cells].get(mode="clip")[None], one(k, r, 1),
                  None if v is None else one(v, r, 1), one(kv_lens, r),
                  one(q_positions, r))
        return o.at[jnp.where(valid[r], cells, n)].set(o_r[0], mode="drop")

    return jax.lax.fori_loop(0, rows.n_long, chunk_row, o)


@jax.named_scope("attention")
def decode_attention_split(
    q: jax.Array,            # [B, H, hd] — one query token per sequence
    k_base: jax.Array,       # [Hkv, B, Lb, hd] — read-only pre-window KV
    v_base: Optional[jax.Array],   # None (all three): a one-leaf cache
    k_win: jax.Array,        # [Hkv, B, Nw, hd] — in-window KV buffer
    v_win: Optional[jax.Array],
    k_new: jax.Array,        # [B, Hkv, hd] — this step's kv (self-term)
    v_new: Optional[jax.Array],
    base_lens: jax.Array,    # [B] int32 — valid kv at WINDOW start
    win_lens: jax.Array,     # [B] int32 — tokens written in-window so far
    softcap: float = 0.0,
    window: Optional[jax.Array] = None,  # scalar int32 sliding width
    q_scale: float = 0.0,
) -> jax.Array:
    """Decode attention over a base-plus-window split KV view.

    The window decode gathers each slot's VALID prefix pages once per
    window into a read-only base buffer (positions [0, base_lens)) and
    accumulates in-window tokens into a tiny [.., Nw, ..] buffer at the
    step index (absolute position base_lens + j). The three score groups
    — base, window, current-token self-term — merge in one joint softmax
    (exact: decode is causal, so the union covers precisely the valid
    prefix). Versus carrying one full-allocation-width gathered buffer
    (the round-3 design), the base is sliced to the bucket of the TRUE
    kv length (not the admission-time page allocation, which reserves
    for max_tokens), and the only scan-carried KV state is the Nw-wide
    window buffer — ~page_bucket*page_size/Nw times smaller.
    Sliding-window masking uses the same absolute coordinates: the query
    sits at base_lens + win_lens; base keys at their index, window-buffer
    keys at base_lens + j. Returns [B, H, hd].
    """
    b, h, hd = q.shape
    hkv = k_base.shape[0]
    g = h // hkv
    lb = k_base.shape[2]
    nw = k_win.shape[2]
    if v_base is None:
        v_base, v_win, v_new = k_base, k_win, k_new
    sc = _scale(hd, q_scale)
    qg = q.reshape(b, hkv, g, hd)
    sb = _softcap(jnp.einsum(
        "bkgd,kbsd->bkgs", qg, k_base,
        preferred_element_type=jnp.float32) * sc, softcap)
    base_pos = jnp.arange(lb, dtype=jnp.int32)[None, :]
    base_mask = base_pos < base_lens[:, None]
    if window is not None:
        q_pos = (base_lens + win_lens)[:, None]      # [B, 1]
        base_mask = base_mask & (q_pos - base_pos < window)
    sb = jnp.where(base_mask[:, None, None, :], sb, NEG_INF)
    sw = _softcap(jnp.einsum(
        "bkgd,kbsd->bkgs", qg, k_win,
        preferred_element_type=jnp.float32) * sc, softcap)
    win_pos = jnp.arange(nw, dtype=jnp.int32)[None, :]
    win_mask = win_pos < win_lens[:, None]
    if window is not None:
        # q_pos - (base_lens + j) = win_lens - j
        win_mask = win_mask & (win_lens[:, None] - win_pos < window)
    sw = jnp.where(win_mask[:, None, None, :], sw, NEG_INF)
    s_self = _softcap(jnp.einsum(
        "bkgd,bkd->bkg", qg, k_new,
        preferred_element_type=jnp.float32) * sc, softcap)
    # joint softmax across the three groups; s_self is always unmasked so
    # the max is finite even for empty base/window (padding slots)
    m = jnp.maximum(jnp.maximum(jnp.max(sb, axis=-1), jnp.max(sw, axis=-1)),
                    s_self)
    pb = jnp.exp(sb - m[..., None])
    pw = jnp.exp(sw - m[..., None])
    p_self = jnp.exp(s_self - m)
    denom = jnp.sum(pb, axis=-1) + jnp.sum(pw, axis=-1) + p_self
    # base rows past base_lens sit in the bucket's stale tail (recycled
    # pages), window rows past win_lens are last window's leftovers: zero
    # them so non-finite stale values can't ride 0 * NaN through the
    # masked probabilities (K is safe — the score where() discards it)
    v_base = jnp.where(base_mask[None, :, :, None], v_base, 0)
    v_win = jnp.where(win_mask[None, :, :, None], v_win, 0)
    out = jnp.einsum("bkgs,kbsd->bkgd", pb.astype(v_base.dtype), v_base,
                     preferred_element_type=jnp.float32)
    out = out + jnp.einsum("bkgs,kbsd->bkgd", pw.astype(v_win.dtype), v_win,
                           preferred_element_type=jnp.float32)
    out = out + p_self[..., None] * v_new.astype(jnp.float32)[:, :, None, :]
    out = out / denom[..., None]
    return out.reshape(b, h, hd).astype(q.dtype)


@jax.named_scope("attention")
def decode_attention_deferred(
    q: jax.Array,            # [B, H, hd] — one query token per sequence
    k_cache: jax.Array,      # [Hkv, P, ps, hd]
    v_cache: Optional[jax.Array],  # None (with v_new): a one-leaf cache
    k_new: jax.Array,        # [B, Hkv, hd] — this step's kv (NOT in cache)
    v_new: Optional[jax.Array],
    page_table: jax.Array,   # [B, Pb] int32
    prefix_lens: jax.Array,  # [B] int32 — valid kv BEFORE this token
    softcap: float = 0.0,
    window: Optional[jax.Array] = None,  # scalar int32 sliding width
    q_scale: float = 0.0,
    k_scale: Optional[jax.Array] = None,  # [Hkv, P, ps] f32 — int8 cache
    v_scale: Optional[jax.Array] = None,
    layer: Optional[jax.Array] = None,  # caches/scales are [L, ...] stacks
) -> jax.Array:
    """Decode attention with the current token's kv appended in registers.

    The deferred-write decode design: the cache stays READ-ONLY during the
    layer scan (so XLA never copies it through scan outputs — the copy was
    ~8 ms/step on a 1B model, the round-2 perf gap) and the current token's
    kv contributes via an explicit self-term; the engine scatters all
    layers' new kv into the cache in ONE in-place update per step.
    Returns [B, H, hd].
    """
    b, h, hd = q.shape
    hkv = k_cache.shape[0 if layer is None else 1]
    g = h // hkv

    # int8 cache: dequantized at the gather boundary to q.dtype — the
    # dequantized operand is the same width the bf16 path reads
    k = gather_values(k_cache, k_scale, page_table, q.dtype, layer)
    if v_cache is None:
        v, v_new = k, k_new
    else:
        v = gather_values(v_cache, v_scale, page_table, q.dtype, layer)
    lk = k.shape[2]

    sc = _scale(hd, q_scale)
    qg = q.reshape(b, hkv, g, hd)
    # dots stay in the cache dtype (bf16 on TPU: native MXU passes and half
    # the HBM read traffic of an f32 upcast) with f32 accumulation
    scores = _softcap(jnp.einsum(
        "bkgd,kbsd->bkgs", qg, k,
        preferred_element_type=jnp.float32) * sc, softcap)
    kv_pos = jnp.arange(lk, dtype=jnp.int32)[None, :]     # [1, Lk]
    valid = kv_pos < prefix_lens[:, None]                 # [B, Lk]
    if window is not None:
        # the query's absolute position is prefix_lens
        valid = valid & (prefix_lens[:, None] - kv_pos < window)
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    s_self = _softcap(jnp.einsum(
        "bkgd,bkd->bkg", qg, k_new,
        preferred_element_type=jnp.float32) * sc, softcap)

    m = jnp.maximum(jnp.max(scores, axis=-1), s_self)     # [B, Hkv, G]
    p = jnp.exp(scores - m[..., None])                    # [B, Hkv, G, Lk]
    p_self = jnp.exp(s_self - m)                          # [B, Hkv, G]
    denom = jnp.sum(p, axis=-1) + p_self
    # rows past prefix_lens hold recycled-page leftovers: zero them so a
    # stale non-finite value can't ride 0 * NaN through the masked probs
    v = jnp.where(valid[None, :, :, None], v, 0)
    out = jnp.einsum("bkgs,kbsd->bkgd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    out = out + p_self[..., None] * v_new.astype(jnp.float32)[:, :, None, :]
    out = out / denom[..., None]
    return out.reshape(b, h, hd).astype(q.dtype)


# tokens one scatter call of write_kv_rows writes. On a v5e a scattered
# row costs ~67 ns whether it lands or is dropped (my chip run, PR 26), so
# a [32, 16] mixed step that wrote all its 512 token slots would spend 9 ms
# on 47 real tokens; blocks of valid rows cost what the step really writes.
KV_WRITE_BLOCK = 32


@jax.named_scope("kv.write")
def stored_kv_rows(k_new: jax.Array, v_new: Optional[jax.Array],
                   quant: bool) -> tuple:
    """New K/V rows [..., hd] as the pool stores them, a tuple in
    kv_quant.cache_keys order: (k, v), or on an int8 pool (k, v, k_scale,
    v_scale) — capture-time quantization, each row against its own max
    inside the jitted step, no dequantized shadow copy. A one-leaf cache
    (`v_new` None; never quantized: the engine refuses that) stores (k,)."""
    if v_new is None:
        return (k_new,)
    if not quant:
        return k_new, v_new
    kq, ks = quantize_rows(k_new)
    vq, vs = quantize_rows(v_new)
    return kq, vq, ks, vs


class KvWritePlan(NamedTuple):
    """Which token slots a step writes, valid ones first (kv_write_plan)."""
    write_idx: jax.Array            # [N] flat slot page*ps + offset; <0 skip
    order: Optional[jax.Array]      # [ceil(N/block)*block] valid rows first
    n_valid: Optional[jax.Array]    # scalar int32


def kv_write_plan(write_idx: jax.Array) -> KvWritePlan:
    """Layer-independent half of write_kv_rows: computed once a program,
    outside the layer scan. Steps of at most one block write every row in
    one scatter and need no order."""
    write_idx = jnp.asarray(write_idx).reshape(-1)
    n = write_idx.shape[0]
    if n <= KV_WRITE_BLOCK:
        return KvWritePlan(write_idx, None, None)
    valid = write_idx >= 0
    order = jnp.argsort(jnp.logical_not(valid), stable=True)
    order = jnp.pad(order, (0, -n % KV_WRITE_BLOCK))
    return KvWritePlan(write_idx, order,
                       jnp.sum(valid, dtype=jnp.int32))


# a step's flat token rows are rounded up to the MXU's 128 rows. Up to a
# few hundred rows a v5e's projections and MLP are bound by their weights
# (Mistral-7B: 436 MB a layer against 0.44 GFLOP a row), so a wider flat
# step costs what a narrow one does, and a narrower width takes fewer
# steps: at 64 rows a fifth of the [32, 16] steps of a full closed loop
# held more (three prefill rows), and ran the grid (PERF.md section 6,
# PR 32)
COMPACT_TILE = 128


def compact_step(write_idx) -> Optional[tuple]:
    """Whether a `[rows, chunk]` step's token-wise layers run over its
    real tokens instead of its grid: None where the shape alone says no,
    else (width, fits). `width` is STATIC, the next multiple of
    COMPACT_TILE that holds a full row axis of decode tokens beside two
    whole chunks (128 for [32, 16] and [16, 32], 256 for [8, 64]), and a
    shape whose grid is no larger gets None. `fits`: the real tokens
    (`write_idx` >= 0) number at most `width`. THE predicate, for the
    program (a traced `write_idx`: `fits` picks the branch of
    models/llama.forward's `cond`s) and for the host's accounting (a
    NumPy plan: `fits` is a bool) alike."""
    rows, chunk = write_idx.shape
    width = -(-(rows + 2 * chunk) // COMPACT_TILE) * COMPACT_TILE
    if width >= rows * chunk:
        return None
    return width, (write_idx >= 0).sum() <= width


class CompactIndex(NamedTuple):
    """A step's real tokens as the first `width` of its rows * chunk token
    rows, in the grid's row-major order, and the way back
    (compact_index)."""
    cells: jax.Array    # [width] grid cell b * chunk + t of flat row j
    live: jax.Array     # [width] bool: row j holds a real token
    slot: jax.Array     # [rows * chunk] flat row of a cell; 0 where none
    plan: KvWritePlan   # the KV writes of the token rows, flat ones first


@jax.named_scope("step.compact")
def compact_index(plan: KvWritePlan, width: int) -> CompactIndex:
    """The compaction index of a step whose grid is larger than `width`
    (compact_step), from the grid's own write plan: its `order` already
    lists the real cells first. Computed once a program, outside the
    layer scan. Rows past the real tokens name cell 0 and write nothing;
    a step with more real tokens than `width` must not use it."""
    valid = plan.write_idx >= 0
    live = jnp.arange(width) < plan.n_valid
    cells = jnp.where(live, plan.order[:width], 0).astype(jnp.int32)
    slot = jnp.where(valid, jnp.cumsum(valid, dtype=jnp.int32) - 1, 0)
    # the real rows lead already: the order is the identity
    flat = KvWritePlan(
        jnp.pad(jnp.where(live, plan.write_idx[cells], -1),
                (0, valid.shape[0] - width), constant_values=-1),
        jnp.arange(plan.order.shape[0], dtype=plan.order.dtype),
        plan.n_valid)
    return CompactIndex(cells, live, slot, flat)


@jax.named_scope("kv.write")
def write_kv_rows(
    pools: tuple,        # stacked leaves [L, Hkv, P, ps, hd] / scales [L, Hkv, P, ps]
    rows: tuple,         # a leaf each: [Lw, N, Hkv, hd] / [Lw, N, Hkv]
    #                      (as stored: rows of f heads are [.., Hkv/f, f*hd])
    plan: KvWritePlan,
    layers: jax.Array,   # [Lw] int32: the layer rows[:, i] belong to
) -> tuple:
    """Scatter new KV rows into the stacked pool leaves, in place.

    Every (layer, kv head, page, slot) is its own scatter index and a row
    of `hd` values (or one scale) the window: the one formulation that
    XLA:TPU applies to the pool's stored layout. With the kv-head axis a
    window dimension (`.at[:, slot].set` on a [Hkv, P*ps, hd] view, as
    write_kv_pages does) it re-lays the operand out so that Hkv sits next
    to hd: a copy of the whole leaf there and back, 2 x 2.1 GB a leaf
    for Mistral-7B-16 at 1024 pages (PERF.md section 6, PR 26). The
    operand keeps its five axes, so a pool sharded over kv heads (`tp`)
    is written shard by shard with no collective. The window is a whole
    lane tile only where the pool's row is 128 wide: a 64-wide row rests
    pages-minor and this scatter re-laid BOTH leaves out, whole, once a
    layer, which is why 64-wide heads are stored two to a row (PERF.md
    section 6, PR 51).

    Rows are written a block of KV_WRITE_BLOCK valid tokens at a time in
    a loop whose trip count follows `plan.n_valid`: padding rows and
    padding tokens (`write_idx` < 0) cost nothing."""
    _, hkv, p, ps = pools[0].shape[:4]
    head = jnp.arange(hkv, dtype=jnp.int32)[None, None, :]
    layer = layers.astype(jnp.int32)[:, None, None]

    def scatter(leaves, blocks, idx):
        # idx [n] -> index arrays [Lw, n, Hkv]; page p is out of range,
        # which mode="drop" skips
        page = jnp.where(idx >= 0, idx // ps, p)[None, :, None]
        slot = (idx % ps)[None, :, None]
        return tuple(
            leaf.at[layer, head, page, slot].set(
                blk.astype(leaf.dtype), mode="drop")
            for leaf, blk in zip(leaves, blocks))

    if plan.order is None:
        return scatter(pools, rows, plan.write_idx)

    def body(i, leaves):
        at = i * KV_WRITE_BLOCK
        take = jax.lax.dynamic_slice_in_dim(plan.order, at, KV_WRITE_BLOCK)
        live = at + jnp.arange(KV_WRITE_BLOCK) < plan.n_valid
        idx = jnp.where(live, plan.write_idx[take], -1)
        return scatter(leaves, tuple(r[:, take] for r in rows), idx)

    n_blocks = (plan.n_valid + KV_WRITE_BLOCK - 1) // KV_WRITE_BLOCK
    return jax.lax.fori_loop(0, n_blocks, body, tuple(pools))


def write_kv_pages(
    k_cache: jax.Array,   # [Hkv, P, ps, hd]
    v_cache: jax.Array,
    k_new: jax.Array,     # [B, Tq, Hkv, hd]
    v_new: jax.Array,
    write_idx: jax.Array,  # [B, Tq] int32 flat indices into P*ps; <0 = skip
) -> tuple[jax.Array, jax.Array]:
    """Scatter new KV entries into the paged cache at flat token slots."""
    hkv, p, ps, hd = k_cache.shape
    flat_k = k_cache.reshape(hkv, p * ps, hd)
    flat_v = v_cache.reshape(hkv, p * ps, hd)
    idx = write_idx.reshape(-1)
    keep = idx >= 0
    # Out-of-range (negative) indices are dropped by scatter mode "drop".
    safe_idx = jnp.where(keep, idx, p * ps)
    kn = k_new.reshape(-1, hkv, hd).swapaxes(0, 1).astype(flat_k.dtype)
    vn = v_new.reshape(-1, hkv, hd).swapaxes(0, 1).astype(flat_v.dtype)
    flat_k = flat_k.at[:, safe_idx].set(kn, mode="drop")
    flat_v = flat_v.at[:, safe_idx].set(vn, mode="drop")
    return (flat_k.reshape(hkv, p, ps, hd), flat_v.reshape(hkv, p, ps, hd))


def write_kv_pages_quant(
    k_cache: jax.Array,    # [Hkv, P, ps, hd] int8
    v_cache: jax.Array,
    k_scale: jax.Array,    # [Hkv, P, ps] f32 per-row scales
    v_scale: jax.Array,
    k_new: jax.Array,      # [B, Tq, Hkv, hd] full-precision new rows
    v_new: jax.Array,
    write_idx: jax.Array,  # [B, Tq] int32 flat indices into P*ps; <0 = skip
) -> tuple:
    """Capture-time KV quantization (ops/kv_quant.py codec): each new row
    quantizes against its own max and scatters int8 values + f32 scale at
    the same flat token slot — the quantized twin of write_kv_pages."""
    hkv, p, ps, hd = k_cache.shape
    kq, ks = quantize_rows(k_new)           # [B, Tq, Hkv, hd] / [B, Tq, Hkv]
    vq, vs = quantize_rows(v_new)
    flat_k = k_cache.reshape(hkv, p * ps, hd)
    flat_v = v_cache.reshape(hkv, p * ps, hd)
    flat_ks = k_scale.reshape(hkv, p * ps)
    flat_vs = v_scale.reshape(hkv, p * ps)
    idx = write_idx.reshape(-1)
    keep = idx >= 0
    safe_idx = jnp.where(keep, idx, p * ps)
    kn = kq.reshape(-1, hkv, hd).swapaxes(0, 1)
    vn = vq.reshape(-1, hkv, hd).swapaxes(0, 1)
    ksn = ks.reshape(-1, hkv).swapaxes(0, 1)
    vsn = vs.reshape(-1, hkv).swapaxes(0, 1)
    flat_k = flat_k.at[:, safe_idx].set(kn, mode="drop")
    flat_v = flat_v.at[:, safe_idx].set(vn, mode="drop")
    flat_ks = flat_ks.at[:, safe_idx].set(ksn, mode="drop")
    flat_vs = flat_vs.at[:, safe_idx].set(vsn, mode="drop")
    return (flat_k.reshape(hkv, p, ps, hd), flat_v.reshape(hkv, p, ps, hd),
            flat_ks.reshape(hkv, p, ps), flat_vs.reshape(hkv, p, ps))


def dense_causal_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, positions: jax.Array,
    softcap: float = 0.0,
    window: Optional[jax.Array] = None,
    q_scale: float = 0.0,
) -> jax.Array:
    """Plain causal attention (no paging); [B, T, H, hd] each. Test oracle."""
    b, t, h, hd = q.shape
    hkv = k.shape[2]
    g = h // hkv
    qg = q.reshape(b, t, hkv, g, hd)
    scores = _softcap(jnp.einsum(
        "btkgd,bskd->bkgts", qg.astype(jnp.float32), k.astype(jnp.float32)
    ) * _scale(hd, q_scale), softcap)
    mask = positions[:, None, :] <= positions[:, :, None]  # [B, Tq, Tk]
    if window is not None:
        mask = mask & (positions[:, :, None] - positions[:, None, :] < window)
    scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgts,bskd->btkgd", probs, v.astype(jnp.float32))
    return out.reshape(b, t, h, hd).astype(q.dtype)
