"""Ring attention: sequence-parallel exact attention over the `sp` mesh axis.

The reference has NO sequence/context parallelism — it caps context length
and leans on paged KV + disaggregated prefill (SURVEY.md §2.9/§5
"Long-context"). This is the TPU-native fill for that gap: shard the
sequence over the `sp` axis, keep Q resident, and rotate K/V blocks around
the ring with `ppermute` (XLA overlaps the collective with compute over
ICI), flash-combining partial results so the attention is exact at any
length. Blockwise-parallel-transformer-style accumulation; memory per chip
is O(T / sp).

Causality is enforced with absolute positions, so the same code handles
interior blocks, the diagonal, and fully-masked pairs (which contribute
zero via the running-max trick).
"""
# dynalint: hot-path — every op here runs inside jitted decode/prefill programs;
# host syncs (.item(), device_get, float()) are dynalint R6 findings
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30


def _flash_update(q, k, v, qpos, kpos, m, l, acc, scale):
    """One block's contribution. q:[B,Tq,Hkv,G,hd] k/v:[B,Tk,Hkv,hd]."""
    scores = jnp.einsum(
        "btkgd,bskd->bkgts", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale                                            # [B,Hkv,G,Tq,Tk]
    mask = (kpos[:, None, :] <= qpos[:, :, None]) & (kpos >= 0)[:, None, :]
    scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(scores, axis=-1, keepdims=True))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(scores - m_new)
    # fully-masked blocks: m_new stays NEG_INF, p = exp(0) would pollute —
    # zero those rows explicitly
    p = jnp.where(m_new > NEG_INF / 2, p, 0.0)
    l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
    acc_new = acc * alpha + jnp.einsum(
        "bkgts,bskd->bkgtd", p, v.astype(jnp.float32))
    return m_new, l_new, acc_new


def _ring_local(axis: str, n: int, scale: float, q, k, v, qpos, kpos):
    """Per-shard body: local q stays, k/v/kpos rotate n times."""
    b, tq, h, hd = q.shape
    hkv = k.shape[2]
    g = h // hkv
    qg = q.reshape(b, tq, hkv, g, hd)
    scale = scale or hd ** -0.5
    perm = [(i, (i + 1) % n) for i in range(n)]

    # mark the fresh accumulators as device-varying over the ring axis so
    # the fori_loop carry types stay consistent (shard_map VMA tracking)
    def varying(x):
        return jax.lax.pcast(x, (axis,), to="varying")

    m = varying(jnp.full((b, hkv, g, tq, 1), NEG_INF, jnp.float32))
    l = varying(jnp.zeros((b, hkv, g, tq, 1), jnp.float32))
    acc = varying(jnp.zeros((b, hkv, g, tq, hd), jnp.float32))

    def step(i, carry):
        k_c, v_c, kpos_c, m, l, acc = carry
        m, l, acc = _flash_update(qg, k_c, v_c, qpos, kpos_c, m, l, acc,
                                  scale)
        # rotate for the next step (the last rotation is redundant but keeps
        # the loop body uniform; XLA overlaps it with the epilogue)
        k_c = jax.lax.ppermute(k_c, axis, perm)
        v_c = jax.lax.ppermute(v_c, axis, perm)
        kpos_c = jax.lax.ppermute(kpos_c, axis, perm)
        return k_c, v_c, kpos_c, m, l, acc

    _, _, _, m, l, acc = jax.lax.fori_loop(
        0, n, step, (k, v, kpos, m, l, acc))
    out = acc / jnp.maximum(l, 1e-30)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, tq, h, hd).astype(q.dtype)


def ring_attention(
    q: jax.Array,           # [B, T, H, hd], sharded over T on `axis`
    k: jax.Array,           # [B, T, Hkv, hd]
    v: jax.Array,           # [B, T, Hkv, hd]
    q_positions: jax.Array,  # [B, T] int32; -1 = padding
    kv_positions: jax.Array,  # [B, T] int32; -1 = padding
    mesh: Mesh,
    axis: str = "sp",
    scale: float = 0.0,     # 0 = hd ** -0.5 (rows of several heads name it)
) -> jax.Array:
    """Exact causal attention with the sequence sharded over `axis`."""
    n = mesh.shape[axis]
    seq = P(None, axis, None, None)
    pos = P(None, axis)
    f = jax.shard_map(
        functools.partial(_ring_local, axis, n, scale),
        mesh=mesh,
        in_specs=(seq, seq, seq, pos, pos),
        out_specs=seq,
    )
    return f(q, k, v, q_positions, kv_positions)
