"""Mixture-of-experts dispatch for expert parallelism.

The reference has NO expert parallelism (SURVEY.md §2.9 — engines may do it
internally); for the Mixtral-class configs we need a first-class EP path.
TPU-idiomatic capacity-based dispatch (GShard/Switch style): top-k routing
builds dense dispatch/combine tensors, tokens are gathered per expert into a
fixed-capacity buffer ([B, E, C, D] — static shapes, XLA-friendly), expert
FFNs run as one batched einsum with the expert axis sharded over the "ep"
mesh axis (XLA inserts the all-to-alls), and outputs scatter back with
routing weights. Tokens over a full expert's capacity are dropped (standard
GShard semantics); capacity_factor trades waste for drop rate.

The dense-compute alternative (models/llama._moe_mlp: every expert evaluates
every token, mask-combined) is exact but does E/k times the FLOPs — fine for
tiny test models, wasteful for Mixtral (8/2 = 4x). Dispatch is the serving
default.
"""
# dynalint: hot-path — every op here runs inside jitted decode/prefill programs;
# host syncs (.item(), device_get, float()) are dynalint R6 findings
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from dynamo_tpu.ops.quant import is_quantized, qspec, wmat


def moe_dispatch_mlp(x: jax.Array, lp, cfg, capacity_factor: float = 2.0,
                     return_dropped: bool = False, valid=None):
    """Top-k routed expert MLP with fixed-capacity dispatch.

    x: [B, T, D]; lp holds router [D, E] and stacked expert weights
    w_gate/w_up [E, D, F], w_down [E, F, D]. Returns [B, T, D], or
    ([B, T, D], (dropped, routed)) with return_dropped — the number of
    (token, expert) assignments dropped over capacity and the total
    routed, so the engine can surface the drop rate instead of degrading
    silently (GShard-style capacity dropping is invisible in the output).

    valid: optional [B, T] bool/0-1 mask of real (non-padding) positions.
    Padded positions all share one hidden state, so unmasked they would
    pile onto the same experts — consuming capacity real tokens need and
    polluting the drop counters. Masked tokens route nowhere.

    The three legs carry `jax.named_scope`s (moe.dispatch, moe.experts,
    moe.combine): trace-time metadata for whoever reads a profile.
    """
    b, t, d = x.shape
    e, k = cfg.num_experts, cfg.num_experts_per_tok
    f32 = jnp.float32

    with jax.named_scope("moe.dispatch"):
        logits = jnp.einsum("btd,de->bte", x.astype(f32),
                            lp["router"].astype(f32))
        weights, idx = jax.lax.top_k(logits, k)          # [B, T, k]
        weights = jax.nn.softmax(weights, axis=-1)

        # flatten (token, choice) pairs in token-major order so earlier
        # tokens win capacity ties deterministically
        sel = jax.nn.one_hot(idx, e, dtype=f32)          # [B, T, k, E]
        if valid is not None:
            sel = sel * valid.astype(f32)[:, :, None, None]
        sel_flat = sel.reshape(b, t * k, e)
        pos = jnp.cumsum(sel_flat, axis=1) - 1.0     # position within expert
        cap = max(int(t * k / e * capacity_factor), 1)
        keep = (pos < cap) * sel_flat                    # [B, S, E]
        pos_oh = jax.nn.one_hot(pos.astype(jnp.int32), cap, dtype=f32)
        dispatch = keep[..., None] * pos_oh              # [B, S, E, C]

        w_flat = jnp.broadcast_to(weights[..., None], (b, t, k, 1)
                                  ).reshape(b, t * k, 1)
        combine = dispatch * w_flat[..., None]           # [B, S, E, C]

        x_rep = jnp.repeat(x, k, axis=1)             # [B, S, D] (token-major)
        xin = jnp.einsum("bsec,bsd->becd", dispatch, x_rep.astype(f32)
                         ).astype(x.dtype)               # [B, E, C, D]

    with jax.named_scope("moe.experts"):
        gate = jnp.einsum("becd,edf->becf", xin,
                          wmat(lp["w_gate"], x.dtype))
        up = jnp.einsum("becd,edf->becf", xin, wmat(lp["w_up"], x.dtype))
        act = jax.nn.silu(gate.astype(f32)).astype(x.dtype) * up
        y = jnp.einsum("becf,efd->becd", act,
                       wmat(lp["w_down"], x.dtype))  # [B, E, C, D]

    with jax.named_scope("moe.combine"):
        out = jnp.einsum("bsec,becd->bsd", combine, y.astype(f32))
        out = out.reshape(b, t, k, d).sum(axis=2).astype(x.dtype)
    if return_dropped:
        routed = jnp.sum(sel_flat)
        dropped = routed - jnp.sum(keep)
        return out, (dropped, routed)
    return out


def _route(x, router, e, k, capacity_factor, valid):
    """Shared routing: top-k selection, capacity positions, weights.

    Returns (keep [B,S,E], pos_oh would be too big — positions [B,S,E],
    weights_flat [B,S,1], cap) where S = T*k token-major flat choices.
    All tensors are O(B·S·E) — NO capacity dim, so it is cheap to compute
    replicated on every ep shard.
    """
    b, t, d = x.shape
    f32 = jnp.float32
    logits = jnp.einsum("btd,de->bte", x.astype(f32), router.astype(f32))
    weights, idx = jax.lax.top_k(logits, k)
    weights = jax.nn.softmax(weights, axis=-1)
    sel = jax.nn.one_hot(idx, e, dtype=f32)
    if valid is not None:
        sel = sel * valid.astype(f32)[:, :, None, None]
    sel_flat = sel.reshape(b, t * k, e)
    pos = jnp.cumsum(sel_flat, axis=1) - 1.0
    cap = max(int(t * k / e * capacity_factor), 1)
    keep = (pos < cap) * sel_flat
    w_flat = jnp.broadcast_to(weights[..., None],
                              (b, t, k, 1)).reshape(b, t * k, 1)
    return sel_flat, keep, pos, w_flat, cap


def moe_dispatch_mlp_sharded(x, lp, cfg, mesh, capacity_factor: float = 2.0,
                             return_dropped: bool = False, valid=None):
    """Expert-parallel dispatch with O(E/ep) per-shard memory.

    The dense moe_dispatch_mlp materializes [B, S, E, C] dispatch/combine
    tensors per chip; under jit auto-sharding XLA does not reliably shard
    their E axis, so Mixtral-class configs would allocate all-expert
    capacity buffers everywhere (VERDICT r2 next #7). Here shard_map over
    the "ep" axis makes the per-shard shapes explicit: routing (no C dim)
    is computed replicated, each shard builds dispatch/combine only for its
    OWN E/ep experts, runs their FFNs, and the combine psums partial
    outputs over "ep" (+ "tp" for the FFN-dim shards). This is the
    replicated-token EP pattern — the decode batch is small and whole per
    shard (engine invariant), so a psum is the right collective; a ragged
    all-to-all only pays when tokens themselves are sharded.
    """
    e, k = cfg.num_experts, cfg.num_experts_per_tok
    ep = mesh.shape.get("ep", 1)
    f32 = jnp.float32
    b, t, d = x.shape

    def body(x, router, w_gate, w_up, w_down, valid_arr):
        # runs per (dp, ep, tp) shard: x is the dp-local batch, w_* leading
        # dim is E/ep, last dim F/tp
        bl, tl, dl = x.shape
        with jax.named_scope("moe.dispatch"):
            sel_flat, keep, pos, w_flat, cap = _route(
                x, router, e, k, capacity_factor, valid_arr)
            ei = jax.lax.axis_index("ep")
            e_loc = e // ep
            # slice MY experts' columns out of the replicated routing
            # tensors
            keep_l = jax.lax.dynamic_slice_in_dim(keep, ei * e_loc, e_loc, 2)
            pos_l = jax.lax.dynamic_slice_in_dim(pos, ei * e_loc, e_loc, 2)
            pos_oh = jax.nn.one_hot(pos_l.astype(jnp.int32), cap, dtype=f32)
            dispatch = keep_l[..., None] * pos_oh      # [B, S, E/ep, C]
            combine = dispatch * w_flat[..., None]
            x_rep = jnp.repeat(x, k, axis=1)
            xin = jnp.einsum("bsec,bsd->becd", dispatch,
                             x_rep.astype(f32)).astype(x.dtype)
        with jax.named_scope("moe.experts"):
            gate = jnp.einsum("becd,edf->becf", xin, wmat(w_gate, x.dtype))
            up = jnp.einsum("becd,edf->becf", xin, wmat(w_up, x.dtype))
            act = jax.nn.silu(gate.astype(f32)).astype(x.dtype) * up
            y = jnp.einsum("becf,efd->becd", act, wmat(w_down, x.dtype))
        with jax.named_scope("moe.combine"):
            out = jnp.einsum("bsec,becd->bsd", combine, y.astype(f32))
            out = jax.lax.psum(out, ("ep", "tp"))
            out = out.reshape(bl, tl, k, dl).sum(axis=2).astype(x.dtype)
        routed = jax.lax.psum(jnp.sum(sel_flat), "dp")
        dropped = routed - jax.lax.psum(jnp.sum(keep), "dp")
        return out, dropped, routed

    valid_in = valid if valid is not None else jnp.ones((b, t), bool)

    def wspec(spec, w):
        # int8-quantized expert tensor: qspec is the shared scale-spec
        # rule (ops/quant.py)
        return qspec(spec) if is_quantized(w) else spec

    specs = dict(
        mesh=mesh,
        # batch rides "dp" (whole per shard when dp=1), experts ride "ep",
        # FFN dim rides "tp" — matching llama.param_shardings
        in_specs=(P("dp"), P(),
                  wspec(P("ep", None, "tp"), lp["w_gate"]),
                  wspec(P("ep", None, "tp"), lp["w_up"]),
                  wspec(P("ep", "tp", None), lp["w_down"]), P("dp")),
        out_specs=(P("dp"), P(), P()),
    )
    f = jax.shard_map(body, check_vma=False, **specs)
    out, dropped, routed = f(x, lp["router"], lp["w_gate"], lp["w_up"],
                             lp["w_down"], valid_in)
    if return_dropped:
        return out, (dropped, routed)
    return out
