"""KV-cache int8 page codec: quantize at capture, dequantize at the read.

Weight int8 already pays 1.5x decode throughput (BENCH_SELF_r05_int8);
this module applies the same lever to the OTHER half of decode HBM
traffic and to every place KV bytes sit or travel: with
``kv_quant="int8"`` the paged cache stores K/V as int8 with per-row f32
scales, and that representation — not a dequantized copy — is what the
offload tiers slab, the disagg transfer frames ship, and the integrity
checksums cover. Halving bytes-per-page ~doubles HBM page slots per
chip at a fixed budget and halves transfer bytes per disagg handoff
(the KV-management survey's highest-leverage capacity lever, PAPERS.md).

Scheme: symmetric per-row int8. Each written KV row — one (layer, kv
head, token) vector of head_dim values — quantizes independently:
``s = max|x| / 127`` (f32), ``q = round(x / s)`` in [-127, 127]. The
scale array mirrors the cache layout minus the head_dim axis
(``[L, Hkv, P, ps]`` next to ``[L, Hkv, P, ps, hd]``), so every
page-indexed operation (extract, inject, offload, transfer) moves the
scales with axis-2 page ids exactly like the values. Per-row (rather
than per-page) granularity is what makes capture-time quantization a
pure scatter inside the jitted step: a per-page max would need a
read-modify-write of already-written rows' scales (stale rows quantized
under the old max would dequantize wrong), while per-row scales are
written once, by the same write_idx scatter as the values.

Dequantization sites (the only places quantized bytes become values):
- the XLA gather fallback (ops/attention.py): dequantize right after
  the page gather, before any score math;
- the ragged Pallas decode kernel (ops/paged_attention.py): int8 pages
  DMA HBM->VMEM and the scales fold into the score/probability rows —
  ``(q . k_int8) * s_k`` equals ``q . (k_int8 * s_k)`` because a row's
  scale is constant over the contraction, so the kernel never
  materializes a dequantized page;
- the decode window's base gather (engine/engine.py): the per-window
  read-only base buffer is dequantized once per window.

Exactness: ``kv_quant=""`` engines never touch this module's arrays —
every call site branches at trace time — so the default path stays
bit-identical. ``kv_quant="int8"`` is gated by a committed parity
harness (greedy-match rate + bounded logit drift, tests/test_kv_quant.py
+ tools/tpu_parity_quick.py), not by hope.

Every read or write of ``cache["k"]``/``cache["v"]`` outside this
module's helpers must carry a ``# dynalint: kv-codec`` annotation
(rule R11, docs/ANALYSIS.md): raw int8 bytes treated as values is the
exact bug class this module exists to make impossible.
"""
# dynalint: hot-path — every op here runs inside jitted decode/prefill
# programs; host syncs (.item(), device_get, float()) are dynalint R6 findings
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

KV_QMAX = 127.0
# scale floor: an all-zero row (blank page, padding) quantizes to q=0,
# s=floor and dequantizes to exactly 0
KV_SCALE_EPS = 1e-12

# cache-dict keys added by the int8 representation, in checksum order
SCALE_KEYS = ("k_scale", "v_scale")


def validate_mode(mode: str) -> str:
    if mode not in ("", "int8"):
        raise ValueError(f"unknown kv_quant mode {mode!r} "
                         "(supported: '', 'int8')")
    return mode


def is_quantized_cache(cache: Dict[str, jax.Array]) -> bool:
    """Whether a cache dict carries the int8+scales representation."""
    return "k_scale" in cache


def cache_keys(quant: bool) -> tuple:
    """Cache-dict keys in canonical order (values first, then scales):
    the ONE ordering extract/inject/offload/transfer/checksums share."""
    return ("k", "v", "k_scale", "v_scale") if quant else ("k", "v")


def leaf_page_bytes(num_layers: int, heads: int, page_size: int, width: int,
                    dtype_itemsize: int, quant: bool) -> int:
    """Bytes one page of ONE cache leaf [L, heads, P, ps, width] occupies
    in HBM (its scales with it when quantized)."""
    rows = num_layers * heads * page_size
    if quant:
        return rows * width + rows * 4          # int8 values + f32 scales
    return rows * width * dtype_itemsize


def page_bytes(num_layers: int, num_kv_heads: int, page_size: int,
               head_dim: int, dtype_itemsize: int, quant: bool) -> int:
    """Bytes one KV page occupies in HBM (K + V + scales when quantized):
    the bench capacity phase's definition; the /metrics llm_kv_page_bytes
    gauge sums `leaf_page_bytes` over the leaves the cache really has
    (`ModelConfig.kv_cache_leaves`)."""
    return 2 * leaf_page_bytes(num_layers, num_kv_heads, page_size, head_dim,
                               dtype_itemsize, quant)


def quantize_rows(x: jax.Array) -> tuple:
    """x [..., hd] -> (q int8 [..., hd], s f32 [...]): symmetric per-row.

    The per-row max runs in f32 regardless of x's dtype so bf16 inputs
    quantize against their true magnitude, not a rounded one."""
    xf = x.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1) / KV_QMAX, KV_SCALE_EPS)
    q = jnp.clip(jnp.round(xf / s[..., None]), -KV_QMAX, KV_QMAX)
    return q.astype(jnp.int8), s


def dequantize_rows(q: jax.Array, s: jax.Array, dtype) -> jax.Array:
    """(q int8 [..., hd], s f32 [...]) -> values [..., hd] in `dtype`."""
    return (q.astype(jnp.float32) * s[..., None]).astype(dtype)
