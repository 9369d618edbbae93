"""Pallas TPU decode attention: ONE ragged paged-attention kernel.

The XLA fallback (ops/attention.py) materializes the gathered KV prefix
([B, Pb*ps, Hkv, hd]) in HBM every step — a 2x-3x traffic amplification on
the decode hot loop. This kernel instead streams each sequence's pages
HBM -> VMEM with double-buffered async DMA and accumulates flash-attention
style, so the only HBM traffic is the KV bytes themselves (the role of the
GPU engines' paged-attention kernels behind the reference, e.g. vLLM's; the
reference's own native kernel is the block-copy CUDA kernel,
lib/llm/src/kernels/block_copy.cu:40-200).

There is exactly ONE production kernel (`_ragged_decode_kernel`, built by
the one `pl.pallas_call` in `ragged_decode_attention` — dynalint R23 keeps
it that way). It is ragged over the batch: grid (s,), one program per
sequence row, each row's page walk driven by its own per-row length from
`AttnMetadata` (the `MixedPlan` row vocabulary: plain single-token rows,
packed multi-query rows, prefix-window rows all reduce to "attend `lens[s]`
tokens of row s's pages"). The kernel always returns the UNNORMALIZED flash
state (acc, m, l); consumers pick the mode:

- prefix rows (`decode_paged_attention_prefix`): `lens` counts valid kv
  BEFORE the current token; fold the token itself with
  `combine_self_attention` (the deferred-write decode hot path);
- plain/packed rows (`decode_paged_attention`): `lens` is INCLUSIVE of the
  current token (already scattered into the pages); normalize by l outside.

The historical three-kernel split (`_decode_kernel` direct hd>=128,
`_decode_kernel_packed` hd<128, `_decode_kernel_prefix`) survives only as
test oracles in ops/paged_attention_oracle.py.

Layout contract: caches are [L, Hkv, P, ps, hd] so one (layer, head, page)
slice is a contiguous [ps, hd] block — the DMA-friendly layout (same reason
the reference keeps per-layer block tensors, lib/llm/src/kv/layer.rs:100-616).
The layer index is a scalar-prefetch arg so callers never materialize a
per-layer slice copy; per-layer [Hkv, P, ps, hd] callers pass a free
`cache[None]` view with layer 0.

head_dim < 128 (llama3-1b has hd=64): an HBM slice whose minor dim is hd
would violate Mosaic's 128-lane tiling ("Slice shape along dimension 3 must
be aligned to tiling (128)"). The kernel therefore views each [ps, hd] page
as [ps/pack, pack*hd] rows (pack = 128//hd; a free row-major reshape done
outside the kernel), so every DMA is lane-aligned. Row r of a packed block
holds tokens r*pack .. r*pack+pack-1; scores come from `pack` lane-shifted
copies of q dotted against the packed block, and the flash accumulator is
kept packed [G, pack*hd] (each hd-lane segment accumulates its residue
class), folded to [G, hd] by a reshape+sum outside the kernel. hd >= 128 is
the same code at pack = 1: one q copy, a full-lane mask, rows = ps — the
packed machinery degenerates to the direct layout, which is what lets one
kernel cover every geometry `kernel_supported` admits.
"""
# dynalint: hot-path — every op here runs inside jitted decode/prefill programs;
# host syncs (.item(), device_get, float()) are dynalint R6 findings
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P


NEG_INF = -1e30


def kernel_supported(head_dim: int, page_size: int) -> bool:
    """Whether the compiled (non-interpret) kernel has a lane-aligned path
    for this geometry: hd a multiple of 128 (pack=1 direct DMA) or hd < 128
    with 128 % hd == 0 and ps % (128//hd) == 0 (packed DMA). The engine
    refuses decode_kernel="on" otherwise instead of dying at Mosaic
    compile. Lanes are the only constraint: page blocks shorter than the
    dtype's sublane tile (16 rows bf16, 32 rows int8) compile and agree
    with the gather path on a v5e — chip_smoke.py's kernel phase runs the
    8-row block (page 16 x hd 64) beside the two registry geometries, bf16
    and int8, with libtpu 0.0.34."""
    if head_dim >= 128:
        return head_dim % 128 == 0
    return 128 % head_dim == 0 and page_size % (128 // head_dim) == 0


def _kernel_pack(head_dim: int, page_size: int) -> int:
    """Lane-packing factor for a geometry: 128//hd when the packed layout is
    lane-exact, else 1 (direct [ps, hd] rows — the interpret-mode fallback
    for unsupported geometries, and the hd >= 128 production layout)."""
    if head_dim < 128 and kernel_supported(head_dim, page_size):
        return 128 // head_dim
    return 1


def _ragged_decode_kernel(ps: int, hkv: int, g: int, hd: int, pack: int,
                          quant: bool, pt_ref, lens_ref, layer_ref,
                          q_ref, k_hbm, v_hbm, *rest):
    """THE decode attention kernel: one program per SEQUENCE (grid (s,)).

    Ragged: each program walks its own row's pages (dynamic trip count
    ceil(lens[s]/ps)), prefetching page i+1 while computing page i, with
    all kv heads batched per program — one [Hkv, rows, W] DMA per page
    instead of Hkv small ones, and 8x fewer program launches than the
    historical (s, hkv) grid (whose per-program overhead exceeded the XLA
    gather path's whole cost on a 1B model; round-2 verdict: decode was
    host- and overhead-bound).

    The cache stays WHOLE ([L, Hkv, P, rows, W]) with the layer index a
    scalar-prefetch arg, so the caller never materializes a per-layer
    slice copy. The kernel attends the first lens[s] tokens of the row's
    pages and returns the UNNORMALIZED flash state (acc, m, l); whether
    that span is a prefix (combine the current token outside) or the full
    inclusive window (normalize by l outside) is the caller's contract —
    the kernel itself is mode-free.

    quant (int8 pages): per-head scale blocks [1, Hkv, Pb*pack, rows]
    (this layer's scales, page-table-gathered outside) fold into the
    score/probability rows — a row's scale is constant over the hd
    contraction, so (q . k_int8) * s_k == q . (k_int8 * s_k), and p * s_v
    moves V's scale into the probability operand of the accumulator dot.
    The page DMA itself stays int8: half the HBM traffic of a bf16 read.
    """
    if quant:
        sk_ref, sv_ref, o_ref, m_ref, l_ref, k_buf, v_buf, sems = rest
    else:
        o_ref, m_ref, l_ref, k_buf, v_buf, sems = rest
        sk_ref = sv_ref = None
    s = pl.program_id(0)
    w = pack * hd
    rows = ps // pack
    length = lens_ref[s]
    lyr = layer_ref[0]
    # clamped page count: padding slots (length 0) still DMA page 0 safely.
    # NOTE their outputs are NOT zeros: fully-masked scores are a finite
    # NEG_INF, so m stays NEG_INF but p = exp(sc - m) = 1 — l/acc pick up
    # page-0 garbage. Correctness relies on the consumer scaling by
    # exp(m - m') (combine_self_attention) which underflows to exactly 0,
    # or on the plain wrapper clamping lens >= 1; do NOT normalize by l
    # here or skip the combine for empty prefixes.
    n_pages = jnp.maximum(pl.cdiv(length, ps), 1)

    # per-head unrolled compute (a batched dot_general over the head dim
    # lowered to something ~4x slower in Mosaic; plain 2-D dots per head
    # are the proven codegen)
    qs = [q_ref[0, j].astype(jnp.float32) * (hd ** -0.5)
          for j in range(hkv)]                           # each [G, hd]
    zeros = jnp.zeros((g, hd), jnp.float32)
    # pack lane-shifted q copies: segment pk holds q in lanes
    # [pk*hd, (pk+1)*hd); at pack=1 this is just [[q]] — the direct layout
    q_shifts = [
        [jnp.concatenate([zeros] * pk + [qs[j]] + [zeros] * (pack - 1 - pk),
                         axis=-1) for pk in range(pack)]
        for j in range(hkv)
    ]                                                    # [Hkv][pack][G, W]
    lane = jax.lax.broadcasted_iota(jnp.int32, (g, w), 1)
    lane_masks = [(lane // hd) == pk for pk in range(pack)]

    def dma(i, slot, hbm, buf, kv):
        return pltpu.make_async_copy(
            hbm.at[lyr, :, pt_ref[s, i]], buf.at[slot], sems.at[slot, kv])

    dma(0, 0, k_hbm, k_buf, 0).start()
    dma(0, 0, v_hbm, v_buf, 1).start()

    def body(i, carry):
        ms, ls, accs = carry     # tuples per head: [G,1], [G,1], [G,W]
        slot = jax.lax.rem(i, 2)
        nxt = jax.lax.rem(i + 1, 2)

        @pl.when(i + 1 < n_pages)
        def _():
            dma(i + 1, nxt, k_hbm, k_buf, 0).start()
            dma(i + 1, nxt, v_hbm, v_buf, 1).start()

        dma(i, slot, k_hbm, k_buf, 0).wait()
        dma(i, slot, v_hbm, v_buf, 1).wait()

        # zero K AND V lanes of tokens past the valid span (recycled-page
        # tails hold arbitrary, possibly non-finite values): the packed
        # score dot contracts over ALL 128 lanes, so a non-finite K lane
        # in a NEIGHBOURING token's segment NaNs a VALID token's score
        # through the zero-padded q_shifts (0 * NaN), and p == 0 on
        # masked rows does not survive a non-finite V in the accumulator
        # dot (ADVICE r5 medium; the round-5 page-poisoning class)
        vrow = jax.lax.broadcasted_iota(jnp.int32, (rows, w), 0)
        vlane = jax.lax.broadcasted_iota(jnp.int32, (rows, w), 1)
        vpos = i * ps + vrow * pack + vlane // hd
        tail_ok = vpos < length

        row = jax.lax.broadcasted_iota(jnp.int32, (1, rows), 1)
        ms_n, ls_n, accs_n = [], [], []
        for j in range(hkv):
            k = k_buf[slot, j].astype(jnp.float32)       # [rows, W]
            v = v_buf[slot, j].astype(jnp.float32)
            k = jnp.where(tail_ok, k, 0.0)
            v = jnp.where(tail_ok, v, 0.0)
            scores, live = [], []
            for pk in range(pack):
                sc = jax.lax.dot_general(
                    q_shifts[j][pk], k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)  # [G, rows]
                if quant:
                    sc = sc * sk_ref[0, j, pl.ds(i * pack + pk, 1)]
                live.append(i * ps + row * pack + pk < length)
                scores.append(jnp.where(live[pk], sc, NEG_INF))
            m_new = ms[j]
            for sc in scores:
                m_new = jnp.maximum(m_new,
                                    jnp.max(sc, axis=-1, keepdims=True))
            alpha = jnp.exp(ms[j] - m_new)
            l_new = alpha * ls[j]
            acc_new = accs[j] * alpha
            for pk in range(pack):
                p = jnp.exp(scores[pk] - m_new)          # [G, rows]
                l_new = l_new + jnp.sum(p, axis=-1, keepdims=True)
                # V dequant fold; a stale slot's scale is as arbitrary
                # as its values (p == 0 there does not survive a NaN)
                pv = (p * jnp.where(
                    live[pk], sv_ref[0, j, pl.ds(i * pack + pk, 1)], 0.0)
                    if quant else p)
                contrib = jax.lax.dot_general(
                    pv, v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)  # [G, W]
                acc_new = acc_new + jnp.where(lane_masks[pk], contrib, 0.0)
            ms_n.append(m_new)
            ls_n.append(l_new)
            accs_n.append(acc_new)
        return tuple(ms_n), tuple(ls_n), tuple(accs_n)

    m0 = tuple(jnp.full((g, 1), NEG_INF, jnp.float32) for _ in range(hkv))
    l0 = tuple(jnp.zeros((g, 1), jnp.float32) for _ in range(hkv))
    acc0 = tuple(jnp.zeros((g, w), jnp.float32) for _ in range(hkv))
    ms, ls, accs = jax.lax.fori_loop(0, n_pages, body, (m0, l0, acc0))
    for j in range(hkv):
        o_ref[0, j] = accs[j]
        m_ref[0, j] = jnp.broadcast_to(ms[j], (g, w))
        l_ref[0, j] = jnp.broadcast_to(ls[j], (g, w))


def ragged_decode_attention(
    q: jax.Array,            # [S, H, hd] — one query token per sequence
    k_cache: jax.Array,      # [L, Hkv, P, ps, hd] (whole stack, all layers)
    v_cache: jax.Array,
    layer: jax.Array,        # [1] int32 — which layer's pages to read
    page_table: jax.Array,   # [S, Pb] int32
    lens: jax.Array,         # [S] int32 — valid tokens in row s's pages
    *,
    interpret: bool = False,
    k_scale: Optional[jax.Array] = None,  # [L, Hkv, P, ps] f32 (int8 cache)
    v_scale: Optional[jax.Array] = None,
):
    """THE unified dispatcher: builds the one production `pl.pallas_call`
    (dynalint R23 fences any other decode-attention pallas_call site).

    Returns the unnormalized flash state (acc [S,H,hd] f32, m [S,H,1],
    l [S,H,1]) of each row over the first lens[s] tokens of its pages.
    Prefix consumers fold the current token via combine_self_attention;
    inclusive consumers (decode_paged_attention) normalize by l.

    With k_scale/v_scale (int8 cache), this layer's scales are gathered by
    the page table OUTSIDE the kernel (an [S, Hkv, Pb, ps] f32 gather —
    1/hd of the KV bytes) and folded into the in-kernel score/prob rows;
    the page DMA itself stays int8, which is the point: half the HBM
    traffic of the bf16 read."""
    s, h, hd = q.shape
    nl, hkv, p, ps, _ = k_cache.shape
    g = h // hkv
    pack = _kernel_pack(hd, ps)
    w = pack * hd
    rows = ps // pack
    quant = k_scale is not None
    k_pk = k_cache.reshape(nl, hkv, p, rows, w)     # free row-major bitcast
    v_pk = v_cache.reshape(nl, hkv, p, rows, w)
    qg = q.reshape(s, hkv, g, hd)
    pb = page_table.shape[1]

    in_specs = [
        pl.BlockSpec((1, hkv, g, hd), lambda i, *_: (i, 0, 0, 0)),
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    args = (page_table, lens, layer, qg, k_pk, v_pk)
    if quant:
        def scale_blocks(scale):
            # this layer's scales, gathered to [S, Hkv, Pb*pack, rows]:
            # token (r*pack + pk) of page i lands at [i*pack + pk, r],
            # matching the packed value layout's lane segments
            sl = jnp.take(scale, layer[0], axis=0)          # [Hkv, P, ps]
            sg = jnp.take(sl, page_table.reshape(-1),
                          axis=1).reshape(hkv, s, pb, ps)
            return (sg.transpose(1, 0, 2, 3)
                    .reshape(s, hkv, pb, rows, pack)
                    .transpose(0, 1, 2, 4, 3)
                    .reshape(s, hkv, pb * pack, rows))
        in_specs += [
            pl.BlockSpec((1, hkv, pb * pack, rows),
                         lambda i, *_: (i, 0, 0, 0)),
            pl.BlockSpec((1, hkv, pb * pack, rows),
                         lambda i, *_: (i, 0, 0, 0)),
        ]
        args = args + (scale_blocks(k_scale), scale_blocks(v_scale))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(s,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, hkv, g, w), lambda i, *_: (i, 0, 0, 0)),
            pl.BlockSpec((1, hkv, g, w), lambda i, *_: (i, 0, 0, 0)),
            pl.BlockSpec((1, hkv, g, w), lambda i, *_: (i, 0, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, hkv, rows, w), k_cache.dtype),
            pltpu.VMEM((2, hkv, rows, w), v_cache.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    shape = jax.ShapeDtypeStruct((s, hkv, g, w), jnp.float32)
    acc, m, l = pl.pallas_call(
        functools.partial(_ragged_decode_kernel, ps, hkv, g, hd, pack,
                          quant),
        out_shape=[shape, shape, shape],
        grid_spec=grid_spec,
        interpret=interpret,
    )(*args)
    acc = acc.reshape(s, hkv, g, pack, hd).sum(axis=3).reshape(s, h, hd)
    return acc, m[..., :1].reshape(s, h, 1), l[..., :1].reshape(s, h, 1)


@jax.named_scope("attention")
def decode_paged_attention_prefix(
    q: jax.Array,            # [S, H, hd] — one query token per sequence
    k_cache: jax.Array,      # [L, Hkv, P, ps, hd] (whole stack, all layers)
    v_cache: jax.Array,
    layer: jax.Array,        # [1] int32 — which layer's pages to read
    page_table: jax.Array,   # [S, Pb] int32
    prefix_lens: jax.Array,  # [S] int32 — valid kv BEFORE this token
    *,
    interpret: bool = False,
    k_scale: Optional[jax.Array] = None,  # [L, Hkv, P, ps] f32 (int8 cache)
    v_scale: Optional[jax.Array] = None,
):
    """Prefix-mode view of the ragged kernel: lens counts valid kv BEFORE
    the current token, so the engine can defer all cache writes to one
    in-place scatter per step. Returns the unnormalized state (acc, m, l);
    fold the current token via combine_self_attention."""
    return ragged_decode_attention(
        q, k_cache, v_cache, layer, page_table, prefix_lens,
        interpret=interpret, k_scale=k_scale, v_scale=v_scale)


@jax.named_scope("attention")
def combine_self_attention(q, k_new, v_new, acc, m, l):
    """Fold the current token's kv into the prefix flash state.

    q [S, H, hd]; k_new/v_new [S, Hkv, hd]; acc [S, H, hd] f32 UNNORMALIZED;
    m/l [S, H, 1]. Returns normalized attention [S, H, hd] in q.dtype.
    Safe for empty prefixes (m = NEG_INF, l = 0): the result is exactly the
    new token's value row — decode attention is causal, so the current
    token always attends at least to itself.
    """
    s, h, hd = q.shape
    hkv = k_new.shape[1]
    g = h // hkv
    f32 = jnp.float32
    kn = jnp.repeat(k_new, g, axis=1).astype(f32)        # [S, H, hd]
    vn = jnp.repeat(v_new, g, axis=1).astype(f32)
    s_self = jnp.sum(q.astype(f32) * kn, axis=-1, keepdims=True) \
        * (hd ** -0.5)                                   # [S, H, 1]
    m2 = jnp.maximum(m, s_self)
    a = jnp.exp(m - m2)
    b = jnp.exp(s_self - m2)
    out = (acc * a + vn * b) / (l * a + b)
    return out.astype(q.dtype)


@jax.named_scope("attention")
def decode_paged_attention_prefix_sharded(
    q, k_cache, v_cache, layer, page_table, prefix_lens, mesh,
    *, interpret: bool = False, k_scale=None, v_scale=None,
):
    """shard_map the ragged kernel (prefix mode) over the "tp" axis (heads
    sharded); int8 caches shard the scale stacks' kv-head axis the same
    way."""
    in_specs = (P(None, "tp", None), P(None, "tp", None, None, None),
                P(None, "tp", None, None, None), P(None),
                P(None, None), P(None))
    out_specs = (P(None, "tp", None), P(None, "tp", None),
                 P(None, "tp", None))
    if k_scale is not None:
        in_specs = in_specs + (P(None, "tp", None, None),
                               P(None, "tp", None, None))

        def body(q, kc, vc, lyr, pt, lens, ks, vs):
            return decode_paged_attention_prefix(
                q, kc, vc, lyr, pt, lens, interpret=interpret,
                k_scale=ks, v_scale=vs)
        f = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                          out_specs=out_specs, check_vma=False)
        return f(q, k_cache, v_cache, layer, page_table, prefix_lens,
                 k_scale, v_scale)

    def body(q, kc, vc, lyr, pt, lens):
        return decode_paged_attention_prefix(q, kc, vc, lyr, pt, lens,
                                             interpret=interpret)
    f = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                      out_specs=out_specs, check_vma=False)
    return f(q, k_cache, v_cache, layer, page_table, prefix_lens)


@functools.partial(jax.jit, static_argnames=("interpret",))
def decode_paged_attention(
    q: jax.Array,            # [S, H, hd] — one query token per sequence
    k_cache: jax.Array,      # [Hkv, P, ps, hd]
    v_cache: jax.Array,      # [Hkv, P, ps, hd]
    page_table: jax.Array,   # [S, Pb] int32
    kv_lens: jax.Array,      # [S] int32 (>= 1 per active slot)
    *,
    interpret: bool = False,
    k_scale: Optional[jax.Array] = None,  # [Hkv, P, ps] f32 (int8 cache)
    v_scale: Optional[jax.Array] = None,
    layer: Optional[jax.Array] = None,    # [1] int32: caches are [L, ...]
) -> jax.Array:
    """Inclusive-mode view of the ragged kernel: returns [S, H, hd]
    attention of each decode token over its pages, kv_lens INCLUSIVE of
    the current token (already scattered into the pages).

    A per-layer [Hkv, P, ps, hd] cache rides as a free `cache[None]`
    single-layer view with layer index 0; with `layer` the caches (and
    scales) are the stacked leaves, which the kernel indexes itself. The
    kernel's unnormalized (acc, m, l) is normalized here (the historical
    in-kernel `acc / l`).

    With k_scale/v_scale (int8 cache) the scales are gathered by the page
    table outside the kernel and folded into the in-kernel score/prob
    rows; the page DMA stays int8."""
    # padded decode slots carry kv_len 0; clamp so the page-0 warm-up DMA
    # and the 1/l normalization stay well-defined (their output is ignored)
    kv_lens = jnp.maximum(kv_lens, 1)
    if layer is None:
        layer = jnp.zeros((1,), jnp.int32)
        k_cache, v_cache = k_cache[None], v_cache[None]
        if k_scale is not None:
            k_scale, v_scale = k_scale[None], v_scale[None]
    acc, _, l = ragged_decode_attention(
        q, k_cache, v_cache, layer, page_table, kv_lens,
        interpret=interpret, k_scale=k_scale, v_scale=v_scale)
    return (acc / l).astype(q.dtype)


def decode_paged_attention_sharded(
    q: jax.Array,            # [S, H, hd] — H sharded over "tp"
    k_cache: jax.Array,      # [Hkv, P, ps, hd] — Hkv sharded over "tp"
    v_cache: jax.Array,
    page_table: jax.Array,   # [S, Pb] replicated
    kv_lens: jax.Array,      # [S] replicated
    mesh: Mesh,
    *,
    interpret: bool = False,
    k_scale: Optional[jax.Array] = None,  # [Hkv, P, ps] f32 (int8 cache)
    v_scale: Optional[jax.Array] = None,
    layer: Optional[jax.Array] = None,    # [1] int32: caches are [L, ...]
) -> jax.Array:
    """Multi-chip inclusive-mode kernel: shard_map over the "tp" mesh axis.

    pallas_call cannot be auto-partitioned by jit, so each tp shard runs the
    kernel on its own H/tp query heads against its Hkv/tp kv heads (the GQA
    group ratio G = H/Hkv is per-shard invariant because param_shardings
    split both over tp). page_table/kv_lens are replicated; every other mesh
    axis (dp/sp/ep) is replicated too — decode batch stays whole per shard.
    The head-parallel split mirrors how the reference's engines run their
    paged-attention kernels under --tensor-parallel-size (SURVEY.md §2.9).
    """
    head_spec = P(None, "tp", None)
    stack = () if layer is None else (None,)     # the stacked leaves' L axis
    cache_spec = P(*stack, "tp", None, None, None)
    scale_spec = P(*stack, "tp", None, None)
    args = (q, k_cache, v_cache, page_table, kv_lens)
    in_specs = (head_spec, cache_spec, cache_spec, P(None, None), P(None))
    if layer is not None:
        args, in_specs = args + (layer,), in_specs + (P(None),)
    if k_scale is not None:
        args, in_specs = (args + (k_scale, v_scale),
                          in_specs + (scale_spec, scale_spec))

    def local(q, k_cache, v_cache, page_table, kv_lens, *rest):
        rest = list(rest)
        lyr = rest.pop(0) if layer is not None else None
        ks, vs = rest if rest else (None, None)
        return decode_paged_attention(
            q, k_cache, v_cache, page_table, kv_lens, interpret=interpret,
            k_scale=ks, v_scale=vs, layer=lyr)

    # pallas_call output has no varying-mesh-axis annotation, hence
    # check_vma=False
    f = jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                      out_specs=head_spec, check_vma=False)
    return f(*args)
