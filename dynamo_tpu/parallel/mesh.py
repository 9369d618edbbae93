"""Device mesh construction and axis conventions.

Axis names used across the framework:
- "dp": data parallel (replicate model, shard batch) — the reference's DP is
  worker replicas balanced by the router (reference:
  lib/runtime/src/component/client.rs:181-244); within one engine dp shards
  the decode batch.
- "tp": tensor parallel over ICI (reference delegates to engines via
  --tensor-parallel-size; first-class here).
- "pp": pipeline stages (reference: vLLM-only, vllm_inc.py:38).
- "ep": expert parallel for MoE (absent in the reference; required for the
  Mixtral config, SURVEY.md §2.9).
- "sp": sequence parallel / ring attention for long context (absent in the
  reference; SURVEY.md §2.9).
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

AXES = ("dp", "pp", "ep", "sp", "tp")


def make_mesh(
    dp: int = 1, tp: int = 1, pp: int = 1, ep: int = 1, sp: int = 1,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build a Mesh with the framework's canonical axis order.

    "tp" is innermost so tensor-parallel collectives ride the fastest ICI
    links; "dp" is outermost so replicas can span DCN.
    """
    devices = list(devices if devices is not None else jax.devices())
    n = dp * tp * pp * ep * sp
    if n > len(devices):
        raise ValueError(f"need {n} devices, have {len(devices)}")
    arr = np.array(devices[:n]).reshape(dp, pp, ep, sp, tp)
    return Mesh(arr, AXES)


def single_device_mesh(device: Optional[jax.Device] = None) -> Mesh:
    devices = [device] if device is not None else jax.devices()[:1]
    return make_mesh(devices=devices)


def kv_shard_layout(num_layers: int, num_kv_heads: int, tp: int = 1,
                    pp: int = 1, n_streams: int = 0) -> list:
    """Slice plan for sharded parallel KV transfer (disagg data plane).

    Returns one entry per transfer stream, each a tuple of
    ``(axis, start, count)`` slices over the paged-cache leaf layout
    ([L, Hkv, P, ps, hd] values; [L, Hkv, P, ps] kv_quant scales —
    axes 0 and 1 are shared, so one plan slices both): the KV sharding
    spec of this mesh (models/llama.cache_sharding: heads over "tp";
    models/pp.pp_cache_sharding: layers over "pp" too) cut into the
    per-shard blocks that land on distinct device groups. A sender
    that ships each slice on its own stream to the host owning that
    shard is the multi-NIC parallel placement the disagg data plane
    needs — no stream ever carries bytes two hosts both store.

    `n_streams` (non-pp only) overrides the natural tp count, further
    subdividing (or merging) the kv-head axis — the CPU-validation
    knob for A/Bing stream counts independent of mesh shape; it must
    divide num_kv_heads. 0/1 natural slicing; the degenerate 1-stream
    plan is a single full-cache slice (the legacy single-stream wire
    format stays byte-identical in that case)."""
    if pp > 1:
        if n_streams:
            raise ValueError("n_streams override requires pp == 1 "
                             "(pp slices the layer axis per stage)")
        if num_layers % pp or num_kv_heads % tp:
            raise ValueError(
                f"kv shard layout needs pp|L and tp|Hkv, got L={num_layers} "
                f"pp={pp} Hkv={num_kv_heads} tp={tp}")
        lc, hc = num_layers // pp, num_kv_heads // tp
        return [((0, s * lc, lc), (1, h * hc, hc))
                for s in range(pp) for h in range(tp)]
    n = n_streams or tp
    if n <= 1:
        return [((1, 0, num_kv_heads),)]
    if num_kv_heads % n:
        raise ValueError(
            f"{n} transfer streams must divide num_kv_heads "
            f"({num_kv_heads})")
    hc = num_kv_heads // n
    return [((1, h * hc, hc),) for h in range(n)]
