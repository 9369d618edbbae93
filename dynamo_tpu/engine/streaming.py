"""Decode-time KV streaming beyond HBM: the tiered window-pool pipeline.

A context larger than the HBM page budget cannot keep all of its KV pages
resident, so a streamed sequence holds only a small working set in HBM
(`stream_resident_pages`, with the first `stream_hot_pages` logical pages
protected as the hot prefix) and attends over everything else by staging
cold pages from the offload hierarchy (HostKvPool DRAM / DiskKvPool NVMe)
through a double-buffered *window pool*: two pinned staging halves of
`stream_pages` page slots each, filled by async `jax.device_put` legs
issued one segment AHEAD of the consuming dispatch, so the tier copy for
segment j+1 overlaps the attention partial for segment j (prefetch hit);
a segment that was never prefetched is staged synchronously at consume
time (prefetch late — a stall the hit/late gauges make visible).

Exactness: attention over the full context factors into partial-softmax
flash states — (acc unnormalized, m row max, l row denominator) — one
partial per KV source (resident pages, each streamed segment, the causal
self chunk), merged by the standard flash rule
    m' = max(m1, m2);  l' = l1*e1 + l2*e2;  acc' = acc1*e1 + acc2*e2
with e_i = exp(m_i - m'). K is stored post-RoPE, so a page attends
identically wherever it is staged — page order never changes the merged
softmax, which is why a streamed step is token-identical to an
oversized-HBM oracle (docs/PERF.md §3h has the full argument).

The per-layer host loop is the FlexGen-shaped schedule this layout
forces: layer ℓ+1's queries depend on layer ℓ's COMPLETE attention over
every segment, so segments iterate innermost and the staged unit is one
layer's slice of a page, not a whole page. One decode step therefore
moves each cold page's bytes host→device exactly once.

Integrity: every cold-page fetch goes through `HostKvPool.pin` — the
traveling-checksum verify gate — so rot quarantines at the fetch
boundary and never reaches the device cache; a quarantined page is
recomputed from its token span against the surviving history (only the
victim page — the rest of the stream is untouched) and re-put under its
unchanged chained hash.

Spill policy: a per-logical-page attention-mass EWMA accumulated from
the layer-0 flash (m, l) row statistics. The stats ride the step's
single end-of-step device_get bundle (the R13 deferred-recorder
discipline — no extra host syncs), and the victim is the
lowest-mass sealed resident page outside the hot prefix.
"""
from __future__ import annotations

import dataclasses
import functools
import threading
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.engine.config import ModelConfig
from dynamo_tpu.engine.kv_cache import SequenceState, page_hash
from dynamo_tpu.engine.sampler import sample_logits
from dynamo_tpu.models import llama
from dynamo_tpu.ops.attention import NEG_INF, _scale, write_kv_pages, \
    write_kv_pages_quant
from dynamo_tpu.ops.kv_quant import dequantize_rows, quantize_rows


# -- stats --------------------------------------------------------------------

class StreamStats:
    """Process-global streamed-decode counters -> llm_kv_stream_* gauges.

    Folded into BOTH /metrics surfaces (frontend/service.py and
    observability/exporter.py) at render time; per-step deltas also ride
    the StepLedger samples (stream_hit/late/spilled/stalls columns)."""

    FIELDS = (
        "window_pool_pages",     # staging slots per half (config)
        "window_pool_used",      # slots filled by the last staged segment
        "prefetch_issued",       # async segment stagings issued ahead
        "prefetch_hit",          # segments consumed from a prior prefetch
        "prefetch_late",         # segments staged synchronously at consume
        "pages_spilled",         # resident pages spilled to the host tier
        "pages_promoted",        # cold pages onboarded back into HBM
        "pages_quarantined",     # cold pages failing the pin verify gate
        "pages_recomputed",      # quarantined pages rebuilt from tokens
        "stall_steps",           # steps with >= 1 late segment
        "stream_steps",          # streamed prefill-chunk + decode steps
        "stream_seqs",           # sequences admitted to the streamed path
    )

    def __init__(self):
        self._mu = threading.Lock()
        for f in self.FIELDS:
            setattr(self, f, 0)

    def snapshot(self) -> Dict[str, float]:
        with self._mu:
            return {f: float(getattr(self, f)) for f in self.FIELDS}


STREAM_STATS = StreamStats()


# -- flash-partial math (jitted units) ---------------------------------------

def _merge_partial(acc1, m1, l1, acc2, m2, l2):
    """Merge two partial-softmax states; shapes acc [T, Hkv, G, hd] f32,
    m/l [T, Hkv, G]. The all-masked state (m = NEG_INF, l = 0) merges as
    a no-op: its exp factor underflows to 0 against any finite m."""
    m = jnp.maximum(m1, m2)
    e1 = jnp.exp(m1 - m)
    e2 = jnp.exp(m2 - m)
    return acc1 * e1[..., None] + acc2 * e2[..., None], m, l1 * e1 + l2 * e2


def _pages_partial(q, kp, vp, lens, scale, with_stats):
    """Partial attention of q [T, H, hd] against a stack of KV pages
    kp/vp [Hkv, N, ps, hd] whose every valid row strictly precedes every
    query row (no causal mask — only the per-page length mask). Returns
    (acc, m, l) plus, when with_stats, per-page flash stats (pm [N],
    pl [N]) feeding the attention-mass EWMA."""
    t, h, hd = q.shape
    hkv, n, ps, _ = kp.shape
    g = h // hkv
    qg = q.reshape(t, hkv, g, hd).astype(jnp.float32)
    kf = kp.astype(jnp.float32)
    vf = vp.astype(jnp.float32)
    scores = jnp.einsum("tkgd,knsd->tkgns", qg, kf) * scale
    valid = jnp.arange(ps, dtype=jnp.int32)[None, :] < lens[:, None]  # [N,ps]
    scores = jnp.where(valid[None, None, None], scores, NEG_INF)
    m = jnp.max(scores, axis=(3, 4))                       # [T, Hkv, G]
    # the where (not bare exp) guards the all-masked page set: with
    # m == NEG_INF, exp(NEG_INF - NEG_INF) would be 1, not 0
    p = jnp.where(valid[None, None, None],
                  jnp.exp(scores - m[..., None, None]), 0.0)
    l = jnp.sum(p, axis=(3, 4))
    # stale rows past lens may hold non-finite recycled bytes; p is 0
    # there but IEEE 0 * NaN is NaN — zero V explicitly (ops/attention)
    vz = jnp.where(valid[None, :, :, None], vf, 0.0)
    acc = jnp.einsum("tkgns,knsd->tkgd", p, vz)
    if not with_stats:
        return acc, m, l
    pm = jnp.max(scores, axis=(0, 1, 2, 4))                # [N]
    pp = jnp.where(valid[None, None, None],
                   jnp.exp(scores - pm[None, None, None, :, None]), 0.0)
    pl = jnp.sum(pp, axis=(0, 1, 2, 4))                    # [N]
    return acc, m, l, pm, pl


def _causal_partial(q, k, v, scale):
    """Partial state of the chunk's own causal self-attention; q [T, H,
    hd], k/v [T, Hkv, hd]. Padding rows sit at the chunk tail, so the
    j <= i mask alone keeps them out of every real row's softmax."""
    t, h, hd = q.shape
    hkv = k.shape[1]
    g = h // hkv
    qg = q.reshape(t, hkv, g, hd).astype(jnp.float32)
    scores = jnp.einsum("tkgd,skd->tkgs", qg, k.astype(jnp.float32)) * scale
    idx = jnp.arange(t, dtype=jnp.int32)
    mask = idx[None, :] <= idx[:, None]                    # [Tq, Tk]
    scores = jnp.where(mask[:, None, None, :], scores, NEG_INF)
    m = jnp.max(scores, axis=-1)                           # [T, Hkv, G]
    p = jnp.where(mask[:, None, None, :],
                  jnp.exp(scores - m[..., None]), 0.0)
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("tkgs,skd->tkgd", p, v.astype(jnp.float32))
    return acc, m, l


def _lp_at(layers, lid):
    """Slice one layer's params out of the stacked tree with a traced
    layer id — one compilation covers every layer, no per-layer weight
    copies held on host."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, lid, 0, keepdims=False),
        layers)


def _stream_layer_start(cfg: ModelConfig, with_stats: bool, params, lid,
                        x, positions, ck, cv, ksc, vsc, page_table,
                        page_lens):
    """Per-layer front half: models/llama.layer_front on the chunk as a
    batch of one, then the resident-pages partial merged with the causal
    self-chunk partial. x [T, D]; returns
    (q, k_new, v_new, acc, m, l[, pm, pl])."""
    lp = _lp_at(params["layers"], lid)
    q, k, v = (a[0] for a in llama.layer_front(
        x[None], lp, cfg, positions[None],
        (cfg.num_heads, cfg.num_kv_heads)))
    sc = _scale(cfg.head_dim, cfg.query_scale)
    # resident partial: gather this layer's resident pages; int8 caches
    # dequantize at the gather boundary  # dynalint: kv-codec
    ckl = jax.lax.dynamic_index_in_dim(ck, lid, 0, keepdims=False)
    cvl = jax.lax.dynamic_index_in_dim(cv, lid, 0, keepdims=False)
    kp = jnp.take(ckl, page_table, axis=1)     # [Hkv, R, ps, hd]
    vp = jnp.take(cvl, page_table, axis=1)
    if ksc is not None:
        kssl = jax.lax.dynamic_index_in_dim(ksc, lid, 0, keepdims=False)
        vssl = jax.lax.dynamic_index_in_dim(vsc, lid, 0, keepdims=False)
        # dynalint: kv-codec — scale rows gathered next to the values
        kp = dequantize_rows(kp, jnp.take(kssl, page_table, axis=1), q.dtype)
        vp = dequantize_rows(vp, jnp.take(vssl, page_table, axis=1), q.dtype)
    res = _pages_partial(q, kp, vp, page_lens, sc, with_stats)
    acc_s, m_s, l_s = _causal_partial(q, k, v, sc)
    acc, m, l = _merge_partial(res[0], res[1], res[2], acc_s, m_s, l_s)
    out = (q, k, v, acc, m, l)
    if with_stats:
        out = out + (res[3], res[4])
    return out


def _stream_seg_merge(cfg: ModelConfig, with_stats: bool, q, kp, vp, ksc,
                      vsc, lens, acc, m, l):
    """Merge one staged window-pool segment (the double-buffer fill:
    kp/vp [Hkv, W, ps, hd], int8 staged verbatim with scale leaves
    riding alongside) into the running flash state."""
    if ksc is not None:
        # dynalint: kv-codec — staged int8 pages dequantize at consume
        kp = dequantize_rows(kp, ksc, q.dtype)
        vp = dequantize_rows(vp, vsc, q.dtype)
    sc = _scale(cfg.head_dim, cfg.query_scale)
    seg = _pages_partial(q, kp, vp, lens, sc, with_stats)
    acc, m, l = _merge_partial(acc, m, l, seg[0], seg[1], seg[2])
    if with_stats:
        return acc, m, l, seg[3], seg[4]
    return acc, m, l


def _stream_layer_finish(cfg: ModelConfig, params, lid, x, acc, l):
    """Per-layer back half: normalize the merged flash state, then
    models/llama.layer_back. Returns the next layer's x [T, D].

    A streamed chunk evaluates every expert (`moe_impl` "dense"), whatever
    the configuration dispatches elsewhere: exact at any chunk size, where
    the capacity form's drops depend on the chunk's rows, and no validity
    mask reaches this program to keep padding rows out of a capacity."""
    lp = _lp_at(params["layers"], lid)
    mcfg = dataclasses.replace(cfg, moe_impl="dense")
    attn = (acc / l[..., None]).astype(x.dtype)
    x, _ = llama.layer_back(
        x[None], attn[None], lp, cfg,
        lambda xn, lp: llama._mlp_block(xn, lp, mcfg, None, None))
    return x[0]


def _stream_embed(cfg: ModelConfig, params, tokens):
    # ids validated at admission; streamed decode feeds committed sampler
    # outputs only  # dynalint: disable-next-line=R1
    x = jnp.take(params["embed"], tokens, axis=0)
    return llama.scale_embeds(x, cfg)


def _stream_final(cfg: ModelConfig, params, x_last):
    """final norm + LM head on the last real chunk row; [D] -> [1, V]."""
    return llama.lm_logits(x_last[None], params["final_norm"],
                           llama.lm_head(params, cfg), cfg)


def _stream_scatter(quant: bool, cache_leaves, k_news, v_news, write_idx):
    """Scatter the chunk's new KV rows for ALL layers into the paged
    cache in one dispatch; k_news/v_news [L, T, Hkv, hd], write_idx [T]
    flat slot indices (<0 = padding). Capture-time quantization runs the
    same write_kv_pages_quant codec as the normal engine step, so a
    streamed page's bytes are identical to the oracle's."""
    wi = write_idx[None]
    if quant:
        ck, cv, ks, vs = cache_leaves

        def body(_, xs):
            ckl, cvl, ksl, vsl, kn, vn = xs
            # dynalint: kv-codec — the one capture-time quantize site
            return _, write_kv_pages_quant(ckl, cvl, ksl, vsl, kn[None],
                                           vn[None], wi)
        _, out = jax.lax.scan(body, None, (ck, cv, ks, vs, k_news, v_news))
        return out
    ck, cv = cache_leaves

    def body(_, xs):
        ckl, cvl, kn, vn = xs
        # dynalint: kv-codec — unquantized scatter, model-dtype rows
        return _, write_kv_pages(ckl, cvl, kn[None], vn[None], wi)
    _, out = jax.lax.scan(body, None, (ck, cv, k_news, v_news))
    return out


def _quant_page_rows(k_rows, v_rows):
    """Quantize one recomputed page's rows ([T, Hkv, hd] full precision)
    with the identical per-row codec the capture path uses, so a
    recomputed page re-puts byte-identical payloads."""
    # dynalint: kv-codec — recompute-path twin of write_kv_pages_quant
    kq, ks = quantize_rows(k_rows)
    vq, vs = quantize_rows(v_rows)
    return kq, ks, vq, vs


# -- window pool --------------------------------------------------------------

class WindowPool:
    """Two pinned HBM staging halves for streamed cold-KV segments.

    `prefetch(key, ...)` assembles the segment's per-layer page slices
    into fresh host arrays and issues the async device_put immediately —
    the H2D copy overlaps whatever the device is computing. `take(key,
    ...)` returns the staged arrays: from a half whose key matches (a
    prefetch HIT — the double buffer hid the tier latency) or, when no
    half holds the key, by staging synchronously (a prefetch LATE — the
    step serialized behind the tier). Keys carry the segment's page
    hashes, so a stale prefetch against a changed cold set can never be
    consumed."""

    def __init__(self, slots: int, hkv: int, ps: int, hd: int,
                 np_dtype, quant: bool):
        self.slots = slots
        self._shape = (hkv, slots, ps, hd)
        self._sshape = (hkv, slots, ps)
        self._dtype = np_dtype
        self._quant = quant
        self._half: List[Optional[tuple]] = [None, None]
        self._next = 0
        STREAM_STATS.window_pool_pages = slots

    def _assemble(self, views: List[tuple], lid: int):
        """Stack layer `lid`'s slice of each cold page view into one
        segment buffer and issue the (async) device put. The np.stack
        copies out of the pinned slab views, so the views are not read
        after this returns."""
        k = np.zeros(self._shape, self._dtype)
        v = np.zeros(self._shape, self._dtype)
        lens = np.zeros((self.slots,), np.int32)
        ks = vs = None
        if self._quant:
            ks = np.zeros(self._sshape, np.float32)
            vs = np.zeros(self._sshape, np.float32)
        for i, pv in enumerate(views):
            k[:, i] = pv[0][lid]
            v[:, i] = pv[1][lid]
            lens[i] = self._shape[2]
            if self._quant:
                # dynalint: kv-codec — int8 pages + scale leaves staged
                # verbatim; dequantization happens at kernel consume
                ks[:, i] = pv[2][lid]
                vs[:, i] = pv[3][lid]
        dev = (jax.device_put(k), jax.device_put(v),
               jax.device_put(ks) if self._quant else None,
               jax.device_put(vs) if self._quant else None,
               jax.device_put(lens))
        STREAM_STATS.window_pool_used = len(views)
        return dev

    def prefetch(self, key, views: List[tuple], lid: int) -> None:
        """Fill the idle half ahead of consume — the double-buffer fill
        leg. Halves are keyed by the segment's chained page hashes, so
        a stale prefetch against a changed cold set can never be
        consumed; re-prefetching a key already staged is a no-op."""
        if any(h is not None and h[0] == key for h in self._half):
            return
        half = self._next
        self._next ^= 1
        self._half[half] = (key, self._assemble(views, lid))
        STREAM_STATS.prefetch_issued += 1

    def take(self, key, views: List[tuple], lid: int):
        """Claim the staged segment; returns (arrays, hit: bool). A
        half whose hash-tuple key matches is a prefetch hit (the double
        buffer hid the tier copy); otherwise stage synchronously — a
        prefetch late, never a stale consume (keys can't collide across
        cold-set changes)."""
        for h in self._half:
            if h is not None and h[0] == key:
                STREAM_STATS.prefetch_hit += 1
                return h[1], True
        half = self._next
        self._next ^= 1
        arrs = self._assemble(views, lid)
        self._half[half] = (key, arrs)
        STREAM_STATS.prefetch_late += 1
        return arrs, False

    def invalidate(self) -> None:
        self._half = [None, None]


# -- spill policy -------------------------------------------------------------

class StreamPolicy:
    """Per-logical-page attention-mass EWMA victim selection.

    Masses are normalized flash denominators — page p's share of the
    merged softmax mass, l_p * exp(m_p - M) / Σ — observed once per
    streamed step from the layer-0 statistics. New pages start at 1.0
    (maximum mass) so a freshly sealed page is never the victim before
    any evidence accumulates; the victim is the lowest-EWMA sealed
    resident page outside the protected hot prefix, ties broken toward
    the OLDEST logical page (middle-of-context spills before the recent
    tail)."""

    def __init__(self, hot_pages: int, beta: float = 0.8):
        self.hot_pages = hot_pages
        self.beta = beta

    def observe(self, ewma: List[float], logicals: List[int],
                pm: np.ndarray, pl: np.ndarray) -> None:
        """Fold one step's per-page flash stats (pm: row maxes, pl: local
        denominators, aligned with `logicals`) into the EWMA list."""
        if not logicals:
            return
        pm = np.asarray(pm, np.float64)
        pl = np.asarray(pl, np.float64)
        big = float(np.max(pm))
        mass = pl * np.exp(np.clip(pm - big, -60.0, 0.0))
        total = float(np.sum(mass))
        if total <= 0.0:
            return
        mass = mass / total
        for i, lg in enumerate(logicals):
            if lg < len(ewma):
                ewma[lg] = self.beta * ewma[lg] + (1 - self.beta) * mass[i]

    def victim(self, ewma: List[float],
               candidates: List[int]) -> Optional[int]:
        """Lowest-EWMA candidate logical page outside the hot prefix."""
        eligible = [lg for lg in candidates if lg >= self.hot_pages]
        if not eligible:
            eligible = list(candidates)   # a full hot prefix must still spill
        if not eligible:
            return None
        return min(eligible, key=lambda lg: (ewma[lg], lg))


# -- per-sequence record ------------------------------------------------------

@dataclasses.dataclass
class StreamSeq:
    seq: SequenceState
    hashes: List[int] = dataclasses.field(default_factory=list)
    resident: Dict[int, int] = dataclasses.field(default_factory=dict)
    ewma: List[float] = dataclasses.field(default_factory=list)
    n_kv: int = 0                 # tokens with committed KV
    tail_logical: int = -1        # unsealed page's logical index (-1 none)

    @property
    def sealed_pages(self) -> int:
        return len(self.hashes)

    def cold_logicals(self) -> List[int]:
        return [i for i in range(self.sealed_pages) if i not in self.resident]


class StreamQuarantineError(RuntimeError):
    """A cold page failed the pin verify gate and recompute could not
    restore it (nested rot / missing history)."""


# -- the decoder --------------------------------------------------------------

class StreamingDecoder:
    """Owns streamed sequences end to end: chunked streamed prefill,
    one-token streamed decode steps, residency/spill bookkeeping, and
    the rot -> quarantine -> recompute-the-victim-page repair path.

    Scheduling contract: the scheduler hands one StreamPlan per streamed
    step (engine.step routes it here); everything this class touches on
    the device is the engine's own paged cache, so preempt/migrate reuse
    the existing offload substrate unchanged."""

    def __init__(self, engine):
        self.engine = engine
        cfg = engine.model_cfg
        ecfg = engine.cfg
        self.cfg = cfg
        self.ecfg = ecfg
        self.quant = bool(cfg.kv_quant)
        self.ps = ecfg.page_size
        self.window = ecfg.stream_pages
        self.resident_budget = max(2, ecfg.stream_resident_pages)
        self.policy = StreamPolicy(ecfg.stream_hot_pages)
        np_dtype = (np.dtype(np.int8) if self.quant
                    else jnp.empty((), cfg.dtype).dtype)
        self.pool = WindowPool(self.window, cfg.num_kv_heads, self.ps,
                               cfg.head_dim, np_dtype, self.quant)
        self._seqs: Dict[str, StreamSeq] = {}
        # resident page-table bucket: budget + 1 (the unsealed tail)
        self._rb = self.resident_budget + 1
        eos = tuple(sorted(engine.eos_token_ids))
        # jitted program set: {start, seg} x {stats, no-stats} x {T in
        # (1, ps)} resolve lazily by shape; finish/embed/final/scatter are
        # shape-stable. lid is traced, so one compile covers all layers.
        self._fn_start = {
            ws: jax.jit(functools.partial(_stream_layer_start, cfg, ws))
            for ws in (False, True)}
        self._fn_seg = {
            ws: jax.jit(functools.partial(_stream_seg_merge, cfg, ws))
            for ws in (False, True)}
        self._fn_finish = jax.jit(
            functools.partial(_stream_layer_finish, cfg))
        self._fn_embed = jax.jit(functools.partial(_stream_embed, cfg))
        self._fn_final = jax.jit(functools.partial(_stream_final, cfg))
        self._fn_scatter = jax.jit(
            functools.partial(_stream_scatter, self.quant),
            donate_argnums=(0,))
        self._fn_quant_page = jax.jit(_quant_page_rows)

        def _samp(greedy):
            def run(logits, temp, top_k, top_p, seeds, counters, min_toks):
                return sample_logits(logits, eos, temp, top_k, top_p,
                                     seeds, counters, min_toks,
                                     greedy=greedy)[0]
            return jax.jit(run)
        self._fn_sample = {g: _samp(g) for g in (False, True)}

    # -- lifecycle -----------------------------------------------------------

    def admit(self, seq: SequenceState) -> StreamSeq:
        ss = StreamSeq(seq=seq)
        self._seqs[seq.request_id] = ss
        STREAM_STATS.stream_seqs += 1
        return ss

    def release(self, seq: SequenceState) -> None:
        ss = self._seqs.pop(seq.request_id, None)
        if ss is None:
            return
        alloc = self.engine.scheduler.allocator
        for pid in ss.resident.values():
            alloc.free(pid)
        ss.resident.clear()

    def record(self, seq: SequenceState) -> Optional[StreamSeq]:
        return self._seqs.get(seq.request_id)

    # -- residency helpers ---------------------------------------------------

    def _alloc_page(self) -> int:
        """Allocate one device page, flushing any eviction-triggered
        offloads BEFORE anything can overwrite the evicted bytes (the
        engine's _process_offloads discipline, run mid-step here)."""
        pid = self.engine.scheduler.allocator.allocate()
        self.engine._process_offloads()
        return pid

    def _spill_victims(self, ss: StreamSeq) -> None:
        """Spill lowest-attention-mass sealed resident pages until the
        sequence fits its resident budget. The page rides the existing
        checksummed offload leg (extract -> CopyStream -> HostKvPool put
        with a capture checksum) and the pid returns to the allocator —
        the double-buffered prefetch path re-stages it on demand."""
        sealed = [lg for lg in ss.resident if lg != ss.tail_logical]
        while len(ss.resident) > self.resident_budget and sealed:
            victim = self.policy.victim(ss.ewma, sealed)
            if victim is None:
                return
            sealed.remove(victim)
            pid = ss.resident.pop(victim)
            h = ss.hashes[victim]
            eng = self.engine
            if eng.host_pool is not None and h not in eng.host_pool:
                eng._pending_offloads.append((pid, h))
                eng._process_offloads()
            eng.scheduler.allocator.free(pid)
            STREAM_STATS.pages_spilled += 1

    def _pin_cold(self, ss: StreamSeq, logicals: List[int]) -> dict:
        """Pin + fetch every cold page for this step — the verify-on-
        fetch gate. Rot quarantines the entry; the victim page (and only
        it) is recomputed from its token span and re-put under its
        unchanged chained hash, then the pin retries. Returns
        {logical: slab views} (valid until the matching _unpin_cold)."""
        hp = self.engine.host_pool
        cs = self.engine._copy_stream
        hashes = [ss.hashes[lg] for lg in logicals]
        if cs is not None:
            cs.settle(hashes)   # in-flight spills must land before reads
        views: dict = {}
        for lg, h in zip(logicals, hashes):
            if not hp.pin(h):
                STREAM_STATS.pages_quarantined += 1
                self._recompute_page(ss, lg)
                if not hp.pin(h):
                    raise StreamQuarantineError(
                        f"page {lg} (hash {h:#x}) unrecoverable after "
                        "recompute")
            views[lg] = hp.get(h)
        return views

    def _unpin_cold(self, ss: StreamSeq, logicals: List[int]) -> None:
        hp = self.engine.host_pool
        for lg in logicals:
            hp.unpin(ss.hashes[lg])

    def _recompute_page(self, ss: StreamSeq, logical: int) -> None:
        """Rebuild ONE quarantined page from its token span against the
        surviving history [0, logical*ps) and re-put it: the chained
        hash depends only on token content, so the key is unchanged and
        every later page's hash stays valid."""
        ps = self.ps
        toks = ss.seq.all_tokens[logical * ps:(logical + 1) * ps]
        k_rows, v_rows = self._forward_chunk(
            ss, toks, logical * ps, history_pages=logical,
            append=False, collect_kv=True)
        # [L, T, Hkv, hd] -> the tier's [L, Hkv, ps, hd] page layout
        if self.quant:
            kq, ksc, vq, vsc = jax.device_get(
                self._fn_quant_page(k_rows, v_rows))
            self.engine.host_pool.put(
                ss.hashes[logical],
                np.ascontiguousarray(kq.transpose(0, 2, 1, 3)),
                np.ascontiguousarray(vq.transpose(0, 2, 1, 3)),
                np.ascontiguousarray(ksc.transpose(0, 2, 1)),
                np.ascontiguousarray(vsc.transpose(0, 2, 1)))
        else:
            kn, vn = jax.device_get((k_rows, v_rows))
            self.engine.host_pool.put(
                ss.hashes[logical],
                np.ascontiguousarray(kn.transpose(0, 2, 1, 3)),
                np.ascontiguousarray(vn.transpose(0, 2, 1, 3)))
        STREAM_STATS.pages_recomputed += 1

    # -- the streamed forward pass -------------------------------------------

    def _resident_tables(self, ss: StreamSeq, history_pages: int,
                         hist_len: int):
        """Static-width resident page table + per-page valid lengths for
        attention over history [0, hist_len)."""
        table = np.zeros((self._rb,), np.int32)
        lens = np.zeros((self._rb,), np.int32)
        i = 0
        for lg in sorted(ss.resident):
            if lg >= history_pages and lg != ss.tail_logical:
                continue
            pid = ss.resident[lg]
            if lg == ss.tail_logical:
                valid = hist_len - lg * self.ps
                if valid <= 0:
                    continue
                table[i], lens[i] = pid, valid
            else:
                if lg * self.ps >= hist_len:
                    continue
                table[i], lens[i] = pid, min(self.ps,
                                             hist_len - lg * self.ps)
            i += 1
        return jnp.asarray(table), jnp.asarray(lens)

    def _segments(self, ss: StreamSeq, history_pages: int) -> List[list]:
        cold = [lg for lg in ss.cold_logicals() if lg < history_pages]
        return [cold[i:i + self.window]
                for i in range(0, len(cold), self.window)]

    def _forward_chunk(self, ss: StreamSeq, tokens: List[int], start: int,
                       history_pages: int, append: bool,
                       collect_kv: bool = False):
        """One streamed forward pass over `tokens` (positions start..)
        attending history [0, history_pages * ps) + hist tail + itself.

        The per-layer host loop: layer ℓ's resident+self partial is one
        dispatch (_stream_layer_start), each cold segment merges via the
        window pool's double buffer with segment (ℓ, j+1) prefetched
        while (ℓ, j) computes, and _stream_layer_finish closes the
        layer. Layer-0 per-page flash stats feed the EWMA policy and
        ride the single end-of-step device_get.

        Returns logits [1, V] (append mode) or the chunk's new KV rows
        [L, T, Hkv, hd] pairs (collect_kv, for recompute)."""
        eng = self.engine
        cfg = self.cfg
        ps = self.ps
        t_real = len(tokens)
        t_pad = 1 if t_real == 1 else ps
        toks = np.zeros((t_pad,), np.int32)
        toks[:t_real] = tokens
        # the attended history is exactly [0, start): every committed
        # position before this chunk (recompute passes start = the
        # victim page's base, so later pages never leak into its KV)
        hist_len = start
        positions = np.arange(start, start + t_pad, dtype=np.int32)
        segs = self._segments(ss, history_pages)
        pin_logicals = sorted({lg for seg in segs for lg in seg})
        views = self._pin_cold(ss, pin_logicals)
        stats: list = []
        late = 0
        try:
            x = self._fn_embed(eng.params, jnp.asarray(toks))
            table, lens = self._resident_tables(ss, history_pages,
                                                hist_len)
            cache = eng.cache
            ksc = cache.get("k_scale")
            vsc = cache.get("v_scale")
            k_news: list = []
            v_news: list = []
            nl = cfg.num_layers
            # segment (0, 0) of this step was prefetched at the end of
            # the previous one; re-issue here only if the cold set moved
            if segs:
                with eng.phases.phase("prefetch"):
                    self.pool.prefetch(self._seg_key(ss, 0, segs[0]),
                                       [views[lg] for lg in segs[0]], 0)
            for lid in range(nl):
                lid_t = jnp.int32(lid)
                want_stats = lid == 0
                out = self._fn_start[want_stats](
                    eng.params, lid_t, x, jnp.asarray(positions),
                    cache["k"], cache["v"], ksc, vsc, table, lens)
                q, k_new, v_new, acc, m, l = out[:6]
                if want_stats:
                    stats.append(("resident", None, out[6], out[7]))
                for j, seg in enumerate(segs):
                    key = self._seg_key(ss, lid, seg)
                    arrs, hit = self.pool.take(key,
                                               [views[lg] for lg in seg],
                                               lid)
                    late += 0 if hit else 1
                    sk, sv, sks, svs, slens = arrs
                    sout = self._fn_seg[want_stats](
                        q, sk, sv, sks, svs, slens, acc, m, l)
                    acc, m, l = sout[:3]
                    if want_stats:
                        stats.append(("seg", seg, sout[3], sout[4]))
                    # double buffer: issue the NEXT segment's H2D while
                    # this segment's partial runs on device
                    with eng.phases.phase("prefetch"):
                        if j + 1 < len(segs):
                            nseg = segs[j + 1]
                            self.pool.prefetch(
                                self._seg_key(ss, lid, nseg),
                                [views[lg] for lg in nseg], lid)
                        elif lid + 1 < nl:
                            self.pool.prefetch(
                                self._seg_key(ss, lid + 1, segs[0]),
                                [views[lg] for lg in segs[0]], lid + 1)
                x = self._fn_finish(eng.params, lid_t, x, acc, l)
                k_news.append(k_new)
                v_news.append(v_new)
            k_stack = jnp.stack(k_news)
            v_stack = jnp.stack(v_news)
            if collect_kv:
                return k_stack[:, :t_real], v_stack[:, :t_real]
            if append:
                write_idx = self._write_indices(ss, start, t_real, t_pad)
                leaves = ((cache["k"], cache["v"], ksc, vsc)
                          if self.quant else (cache["k"], cache["v"]))
                new_leaves = self._fn_scatter(leaves, k_stack, v_stack,
                                              jnp.asarray(write_idx))
                keys = (("k", "v", "k_scale", "v_scale") if self.quant
                        else ("k", "v"))
                eng.cache = dict(zip(keys, new_leaves))
            logits = self._fn_final(eng.params, x[t_real - 1])
            return logits
        finally:
            self._unpin_cold(ss, pin_logicals)
            self._fold_stats(ss, segs, stats, late)

    def _seg_key(self, ss: StreamSeq, lid: int, seg: List[int]) -> tuple:
        return (lid, tuple(ss.hashes[lg] for lg in seg))

    def _write_indices(self, ss: StreamSeq, start: int, t_real: int,
                       t_pad: int) -> np.ndarray:
        """Flat cache slot per chunk token (<0 = padding), allocating and
        registering tail pages as the chunk crosses page boundaries."""
        ps = self.ps
        idx = np.full((t_pad,), -1, np.int32)
        for i in range(t_real):
            pos = start + i
            lg = pos // ps
            if lg not in ss.resident:
                ss.resident[lg] = self._alloc_page()
                ss.tail_logical = lg
                if lg >= len(ss.ewma):
                    ss.ewma.append(1.0)
            idx[i] = ss.resident[lg] * ps + pos % ps
        return idx

    def _fold_stats(self, ss: StreamSeq, segs: List[list], stats: list,
                    late: int) -> None:
        """End-of-step host fold of the layer-0 flash stats into the
        EWMA (the one device_get these small arrays ride)."""
        if late:
            STREAM_STATS.stall_steps += 1
        if not stats:
            return
        fetched = jax.device_get([(s[2], s[3]) for s in stats])
        logicals: List[int] = []
        pm_all: List[float] = []
        pl_all: List[float] = []
        res_logicals = sorted(
            lg for lg in ss.resident
            if lg != ss.tail_logical and lg < len(ss.ewma))
        for (kind, seg, _, _), (pm, pl) in zip(stats, fetched):
            lgs = res_logicals if kind == "resident" else seg
            for i, lg in enumerate(lgs):
                if i < len(pm):
                    logicals.append(lg)
                    pm_all.append(float(pm[i]))
                    pl_all.append(float(pl[i]))
        self.policy.observe(ss.ewma, logicals, np.asarray(pm_all),
                            np.asarray(pl_all))

    # -- step entry points ---------------------------------------------------

    def _seal_chunk(self, ss: StreamSeq, upto: int) -> None:
        """Seal every full page below `upto`, chaining hashes, then
        spill down to the resident budget."""
        ps = self.ps
        alloc = self.engine.scheduler.allocator
        toks = ss.seq.all_tokens
        while (ss.sealed_pages + 1) * ps <= upto:
            lg = ss.sealed_pages
            parent = ss.hashes[-1] if ss.hashes else 0
            page_toks = toks[lg * ps:(lg + 1) * ps]
            pid = ss.resident[lg]
            alloc.seal(pid, parent, page_toks)
            ss.hashes.append(page_hash(parent, page_toks))
            if ss.tail_logical == lg:
                ss.tail_logical = -1
        self._spill_victims(ss)

    def step(self, seq: SequenceState):
        """One streamed step: a prefill chunk (no event) or one decoded
        token. Returns (token or None, finished_prefill: bool)."""
        ss = self._seqs.get(seq.request_id)
        if ss is None:
            ss = self.admit(seq)
        STREAM_STATS.stream_steps += 1
        n_prompt = len(seq.prompt)
        if ss.n_kv < n_prompt:
            start = ss.n_kv
            chunk = min(self.ps - start % self.ps, n_prompt - start)
            toks = seq.all_tokens[start:start + chunk]
            logits = self._forward_chunk(ss, toks, start,
                                         history_pages=start // self.ps,
                                         append=True)
            ss.n_kv += chunk
            seq.num_cached = seq.num_computed = ss.n_kv
            self._seal_chunk(ss, ss.n_kv)
            if ss.n_kv < n_prompt:
                return None, False
            if seq.output:
                # resume/migration replay crossed the prompt boundary:
                # the first token was emitted before the preempt — keep
                # rebuilding silently
                return None, True
            return self._sample(ss, logits), True
        start = ss.n_kv
        total = len(seq.all_tokens)
        if start < total - 1:
            # replay after preempt/migration: KV coverage is behind the
            # committed token stream (the unsealed tail was dropped).
            # Rebuild it chunk-at-a-time WITHOUT sampling — these tokens
            # were already emitted; re-sampling here would duplicate them
            chunk = min(self.ps - start % self.ps, total - 1 - start)
            self._forward_chunk(ss, seq.all_tokens[start:start + chunk],
                                start, history_pages=start // self.ps,
                                append=True)
            ss.n_kv += chunk
            seq.num_cached = seq.num_computed = ss.n_kv
            self._seal_chunk(ss, ss.n_kv)
            return None, False
        # decode: feed the last committed token, append its KV, sample
        tok_in = seq.all_tokens[start]
        logits = self._forward_chunk(ss, [tok_in], start,
                                     history_pages=start // self.ps,
                                     append=True)
        ss.n_kv += 1
        seq.num_cached = seq.num_computed = ss.n_kv
        self._seal_chunk(ss, ss.n_kv)
        return self._sample(ss, logits), False

    def _sample(self, ss: StreamSeq, logits) -> int:
        """The identical sampler tail the decode window uses — same
        (seed, counter) keys, so streamed greedy AND seeded-sampled
        outputs are token-for-token the oracle's."""
        seq = ss.seq
        p = self.engine.scheduler.params[seq.request_id]
        greedy = p.temperature <= 0.0
        tok = self._fn_sample[greedy](
            logits,
            jnp.asarray([p.temperature], jnp.float32),
            jnp.asarray([p.top_k], jnp.int32),
            jnp.asarray([p.top_p], jnp.float32),
            jnp.asarray([p.seed & 0x7FFFFFFF], jnp.int32),
            jnp.asarray([len(seq.output)], jnp.int32),
            jnp.asarray([p.min_tokens], jnp.int32))
        return int(tok[0])

    # -- preempt / resume / migration ----------------------------------------

    def preempt(self, seq: SequenceState) -> None:
        """Spill every sealed resident page to the host tier and drop the
        unsealed tail (its tokens recompute on resume) — the streamed
        twin of _evict_to_waiting, except nothing re-queues: the next
        StreamPlan step resumes from sealed coverage."""
        ss = self._seqs.get(seq.request_id)
        if ss is None:
            return
        eng = self.engine
        alloc = eng.scheduler.allocator
        for lg in sorted(ss.resident):
            pid = ss.resident.pop(lg)
            if lg < ss.sealed_pages:
                h = ss.hashes[lg]
                if eng.host_pool is not None and h not in eng.host_pool:
                    eng._pending_offloads.append((pid, h))
                    eng._process_offloads()
                STREAM_STATS.pages_spilled += 1
            alloc.free(pid)
        ss.tail_logical = -1
        ss.n_kv = ss.sealed_pages * self.ps
        seq.num_cached = seq.num_computed = ss.n_kv
        self.pool.invalidate()

    def resume_hot_prefix(self, ss: StreamSeq) -> None:
        """Re-onboard the protected hot-prefix pages into HBM (promotion
        counterpart of the spill leg); cold middle pages stay streamed."""
        hp = self.engine.host_pool
        n = min(self.policy.hot_pages, ss.sealed_pages)
        for lg in range(n):
            if lg in ss.resident:
                continue
            h = ss.hashes[lg]
            if not hp.pin(h):
                STREAM_STATS.pages_quarantined += 1
                self._recompute_page(ss, lg)
                if not hp.pin(h):
                    raise StreamQuarantineError(
                        f"hot page {lg} unrecoverable")
            try:
                pv = hp.get(h)
                pid = self._alloc_page()
                self._inject_host_page(pid, pv)
                ss.resident[lg] = pid
                STREAM_STATS.pages_promoted += 1
            finally:
                hp.unpin(h)
        self._spill_victims(ss)

    def _inject_host_page(self, pid: int, pv: tuple) -> None:
        """One host page -> one device page via the engine's page
        scatter (leaves stacked to the inject layout)."""
        eng = self.engine
        k = np.ascontiguousarray(pv[0][:, :, None])
        v = np.ascontiguousarray(pv[1][:, :, None])
        if self.quant:
            eng.inject_pages([pid], jnp.asarray(k), jnp.asarray(v),
                             jnp.asarray(np.ascontiguousarray(
                                 pv[2][:, :, None])),
                             jnp.asarray(np.ascontiguousarray(
                                 pv[3][:, :, None])))
        else:
            eng.inject_pages([pid], jnp.asarray(k), jnp.asarray(v))

    def export_seq(self, seq: SequenceState) -> dict:
        """Serializable streamed-sequence state for migration / the
        disagg handoff: pages stay content-addressed in the tiers, so
        the record is just tokens + hashes + policy state. Call
        preempt() first so every sealed page is tier-resident."""
        ss = self._seqs[seq.request_id]
        return {
            "request_id": seq.request_id,
            "prompt": list(seq.prompt),
            "output": list(seq.output),
            "hashes": list(ss.hashes),
            "ewma": list(ss.ewma),
            "n_kv": ss.n_kv,
        }

    def import_seq(self, seq: SequenceState, record: dict) -> StreamSeq:
        """Register a migrated streamed sequence; its pages must already
        be present in this engine's tiers (the caller moves them —
        engine/kv_pool or a host-pool copy)."""
        ss = self.admit(seq)
        ss.hashes = list(record["hashes"])
        ss.ewma = list(record["ewma"])
        ss.n_kv = int(record["n_kv"])
        seq.num_cached = seq.num_computed = ss.n_kv
        return ss
