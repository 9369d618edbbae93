"""Mixture-of-experts dispatch.

Two forms. `moe_dropless_mlp` (one device): the step's tokens are
flattened, their (token, expert) assignments sorted by expert, and ONE
grouped matmul a projection runs over the sorted rows, group sizes from a
bincount: nothing is dropped, no [.., E, C] one-hot exists, and each
touched expert's weights are read once a call. It is what many small
experts need (OLMoE: 64 of width 1024, 8 a token), where a capacity per
expert either drops assignments or computes every expert for every row.
The capacity form below it is the older GShard dispatch, kept for the
multi-device `--tp` / `--ep` meshes (`moe_dispatch_mlp_sharded`) and, for
now, for up to eight wide experts (Mixtral: 8 of 14336, 2 a token;
`ModelConfig.moe_dropless` and PERF.md section 6, PR 27 have the paired
runs that decided it, and the 3.4 % of assignments it drops there).

Capacity dispatch for expert parallelism:

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

# Tiles (rows, contraction, columns) of the grouped-matmul kernel. Sorted
# rows are padded to a multiple of the row tile, and a group that straddles
# a row-tile edge is one more visit of that expert's weights; the other two
# are caps (a smaller dimension is one tile). PERF.md section 6, PR 27 has
# the sweep on the chip that chose them.
GMM_TILING = (128, 2048, 1024)


def grouped_matmul_impl() -> str:
    """"gmm": the grouped-matmul Pallas kernel that ships with jax
    (megablox), on a TPU. "ragged_dot": `jax.lax.ragged_dot`, which every
    backend lowers, elsewhere. ("gmm-interpret" runs the kernel's code in
    the Pallas interpreter: what a CPU test asks for.)"""
    return "gmm" if jax.default_backend() == "tpu" else "ragged_dot"


def group_limited(pick, n_group: int, topk_group: int):
    """DeepSeek-V3's group-limited pick. pick [..., E]: the (biased)
    scores the experts are picked on; the experts lie in `n_group` equal
    groups in order. A group's score is the sum of its two largest; the
    `topk_group` best groups stay, every other group's experts become
    -inf, so the k experts come from the groups that stay. A tie between
    groups goes to the lower group."""
    with jax.named_scope("moe.route.groups"):
        lead, e = pick.shape[:-1], pick.shape[-1]
        grouped = pick.reshape(lead + (n_group, e // n_group))
        score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
        _, kept = jax.lax.top_k(score, topk_group)            # [..., g]
        mask = jnp.any(kept[..., None] == jnp.arange(n_group), axis=-2)
        return jnp.where(mask[..., None], grouped, -jnp.inf
                         ).reshape(lead + (e,))


def route_topk(x, router, k: int, renorm: bool, scoring: str = "softmax",
               bias=None, scale: float = 1.0, n_group: int = 1,
               topk_group: int = 1, renorm_eps: float = 1e-20):
    """Router: x [..., D], router [D, E] -> (weights [..., k] float32,
    expert ids [..., k]). The scores are a float32 softmax (or, `scoring`
    "sigmoid", independent sigmoids) over ALL experts at the highest
    matmul precision (a TPU's default rounds float32 operands to
    bfloat16, and a near-tie between the k-th and the next expert then
    flips). `renorm` rescales the k kept weights to sum to one (Mixtral;
    on the plain softmax router computed as the softmax over the k
    chosen logits, the same numbers); without it they stay as they are
    (OLMoE, `norm_topk_prob: false`); the general form divides by their
    sum plus `renorm_eps`, the family's published constant (DeepSeek-V3's
    1e-20, LFM2's 1e-6). `bias` [E] (DeepSeek-V3's
    `e_score_correction_bias`) is added to the scores to PICK the k
    experts and is no part of their weights; `scale` multiplies the
    weights last. `n_group` > 1: the pick is group-limited
    (`group_limited`). A tie goes to the lower expert id."""
    f32 = jnp.float32
    logits = jnp.einsum("...d,de->...e", x.astype(f32), router.astype(f32),
                        precision=jax.lax.Precision.HIGHEST)
    if scoring == "softmax" and bias is None and scale == 1.0 \
            and n_group == 1:
        if renorm:
            weights, idx = jax.lax.top_k(logits, k)
            return jax.nn.softmax(weights, axis=-1), idx
        return jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
    if scoring not in ("softmax", "sigmoid"):
        raise ValueError(f"unknown router scoring {scoring!r}")
    scores = (jax.nn.sigmoid(logits) if scoring == "sigmoid"
              else jax.nn.softmax(logits, axis=-1))
    pick = scores if bias is None else scores + bias.astype(f32)
    if n_group > 1:
        pick = group_limited(pick, n_group, topk_group)
    _, idx = jax.lax.top_k(pick, k)
    weights = jnp.take_along_axis(scores, idx, axis=-1)
    if renorm:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                             + renorm_eps)
    return weights * scale, idx


def route(x, lp, cfg):
    """`route_topk` with a layer's router leaves and the model's router
    settings (engine/config.py ModelConfig)."""
    return route_topk(x, lp["router"], cfg.num_experts_per_tok,
                      cfg.norm_topk_prob, cfg.moe_scoring,
                      lp.get("router_bias"), cfg.moe_routed_scale,
                      cfg.moe_n_group, cfg.moe_topk_group,
                      cfg.moe_renorm_eps)


def moe_stats(routed, dropped, expert_rows, experts_hit):
    """What a layer's dispatch reports, as float32 scalars the layer scan
    stacks and the engine sums into `llm_engine_moe_*_total`."""
    f32 = jnp.float32
    return {"moe_routed": jnp.asarray(routed, f32),
            "moe_dropped": jnp.asarray(dropped, f32),
            "moe_expert_rows": jnp.asarray(expert_rows, f32),
            "moe_experts_hit": jnp.asarray(experts_hit, f32),
            "moe_layer_calls": jnp.ones((), f32)}


def _capacity(t: int, k: int, e: int, capacity_factor: float) -> int:
    """Slots an expert has for one batch row's t tokens (capacity form)."""
    return max(int(t * k / e * capacity_factor), 1)


def _grouped_matmul(lhs, rhs, group_sizes, out_dtype, layer=None):
    """rows [M, K] sorted by group x rhs [G, K, N] -> [M, N]: row r of
    group g is lhs[r] @ rhs[g]; rows past sum(group_sizes) come out zero.
    Returns (out, rows computed). The kernel visits only the (group,
    row-tile) pairs that hold rows, so an untouched expert's weights are
    never read and a touched one's are read once per row tile it spans;
    `ragged_dot` is charged every row it is given.

    With `layer` (a traced index) rhs is the model's STACKED leaf
    [L, G, K, N] and the kernel reads layer `layer`'s experts where they
    lie: the stack is viewed as L*G groups of which only that layer's
    have rows. Slicing the layer out first would copy it (268 MB a leaf
    on OLMoE: a custom call's operand has to exist in memory), which on
    the chip cost more than the matmul itself (PERF.md section 6, PR 27).
    """
    m = lhs.shape[0]
    impl = grouped_matmul_impl()
    if impl == "ragged_dot":
        if layer is not None:
            rhs = jax.lax.dynamic_index_in_dim(rhs, layer, keepdims=False)
        out = jax.lax.ragged_dot(lhs, rhs, group_sizes,
                                 preferred_element_type=out_dtype)
        return out, jnp.asarray(m, jnp.float32)
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm
    tm, tk, tn = GMM_TILING
    sizes = group_sizes
    if layer is not None:
        g = rhs.shape[1]
        rhs = rhs.reshape((rhs.shape[0] * g,) + rhs.shape[2:])
        sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((rhs.shape[0],), group_sizes.dtype), group_sizes,
            (layer * g,))
    out = gmm(lhs, rhs, sizes, preferred_element_type=out_dtype,
              tiling=(tm, min(rhs.shape[1], tk), min(rhs.shape[2], tn)),
              interpret=impl == "gmm-interpret")
    ends = jnp.cumsum(group_sizes)
    # the kernel never writes a row that no group owns: zero those here
    out = jnp.where((jnp.arange(m) < ends[-1])[:, None], out, 0)
    # row tiles visited: a group spans ceil(end / tm) - floor(start / tm)
    starts = ends - group_sizes
    tiles = jnp.where(group_sizes > 0,
                      (ends + tm - 1) // tm - starts // tm, 0)
    return out, (jnp.sum(tiles) * tm).astype(jnp.float32)


def moe_dropless_mlp(x: jax.Array, lp, cfg, valid=None, layer=None):
    """Top-k routed expert MLP that drops nothing. x: [B, T, D]; lp as
    for moe_dispatch_mlp. Returns ([B, T, D], stats) with stats as
    `moe_stats` gives them (`moe_dropped` is 0 by construction).

    layer: a traced layer index; then lp's w_gate / w_up / w_down are the
    model's stacked [L, E, ...] leaves, read in place (`_grouped_matmul`),
    and the router is still this layer's own.

    valid: optional [B, T] mask of real positions; a padding position's
    assignments carry the expert id E, sort behind every real one and
    belong to no group, so no expert computes them.

    A SHARE (`cfg.experts_held` of the router's `cfg.num_experts`, from
    `cfg.expert_first` on): the router and the pick are over all the
    experts, lp's expert leaves hold the share alone, and an assignment
    to an expert that is not held is taken out BEFORE the sort, like a
    padding position's (it costs no row tile) and counted apart
    (`moe_routed_absent`): what the absent experts would add is left
    out, here and in the reference alike; no exchange is built.

    The assignments (S = B*T*k of them, padded up to the kernel's row
    tile) are sorted by expert with a stable argsort, the rows gathered
    in that order, gate / up / down each run as ONE grouped matmul with
    group sizes from a bincount, and the result is gathered back by the
    inverse permutation, weighted in float32 and summed over k.
    """
    b, t, d = x.shape
    # e: the experts whose weights are here, and the id of no expert
    e, k = cfg.local_experts, cfg.num_experts_per_tok
    f32 = jnp.float32
    n = b * t
    xf = x.reshape(n, d)

    with jax.named_scope("moe.route"):
        weights, idx = route(xf, lp, cfg)
        absent = None
        if cfg.experts_held:
            idx = idx - cfg.expert_first
            held = (idx >= 0) & (idx < e)
            if valid is not None:
                held = held | ~valid.reshape(n, 1).astype(bool)
            absent = jnp.sum(~held)
            idx = jnp.where(held, idx, e)
            weights = jnp.where(held, weights, 0.0)
        if valid is not None:
            ok = valid.reshape(n).astype(bool)
            idx = jnp.where(ok[:, None], idx, e)
            weights = jnp.where(ok[:, None], weights, 0.0)

    with jax.named_scope("moe.dispatch"):
        s = n * k
        s_pad = -(-s // GMM_TILING[0]) * GMM_TILING[0]
        flat = idx.reshape(s).astype(jnp.int32)
        if s_pad > s:
            flat = jnp.concatenate(
                [flat, jnp.full((s_pad - s,), e, jnp.int32)])
        order = jnp.argsort(flat, stable=True)           # [S_pad]
        inverse = jnp.zeros((s_pad,), jnp.int32).at[order].set(
            jnp.arange(s_pad, dtype=jnp.int32))
        group_sizes = jnp.bincount(flat, length=e + 1)[:e].astype(jnp.int32)
        # a padding assignment reads row 0: its group is none, so the
        # grouped matmul never multiplies it
        rows = jnp.take(xf, jnp.minimum(order // k, n - 1), axis=0)

    with jax.named_scope("moe.experts"):
        gate, computed = _grouped_matmul(
            rows, wmat(lp["w_gate"], x.dtype), group_sizes, x.dtype, layer)
        up, _ = _grouped_matmul(
            rows, wmat(lp["w_up"], x.dtype), group_sizes, x.dtype, layer)
        act = jax.nn.silu(gate.astype(f32)).astype(x.dtype) * up
        y, _ = _grouped_matmul(
            act, wmat(lp["w_down"], x.dtype), group_sizes, f32, layer)

    with jax.named_scope("moe.combine"):
        back = jnp.take(y, inverse[:s], axis=0).reshape(n, k, d)
        out = jnp.sum(back * weights[..., None], axis=1).astype(x.dtype)
    stats = moe_stats(routed=jnp.sum(group_sizes), dropped=0.0,
                      expert_rows=computed,
                      experts_hit=jnp.sum(group_sizes > 0))
    if absent is not None:
        stats["moe_routed_absent"] = absent.astype(f32)
    return out.reshape(b, t, d), stats


def moe_dispatch_mlp(x: jax.Array, lp, cfg, capacity_factor: float = 2.0,
                     return_dropped: bool = False, valid=None):
    """Top-k routed expert MLP with fixed-capacity dispatch.

    x: [B, T, D]; lp holds router [D, E] and stacked expert weights
    w_gate/w_up [E, D, F], w_down [E, F, D]. Returns [B, T, D], or
    ([B, T, D], `moe_stats`) with return_dropped — among them the number
    of (token, expert) assignments dropped over capacity and the total
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

    with jax.named_scope("moe.route"):
        weights, idx = route(x, lp, cfg)                 # [B, T, k]

    with jax.named_scope("moe.dispatch"):
        # flatten (token, choice) pairs in token-major order so earlier
        # tokens win capacity ties deterministically
        sel = jax.nn.one_hot(idx, e, dtype=f32)          # [B, T, k, E]
        if valid is not None:
            sel = sel * valid.astype(f32)[:, :, None, None]
        sel_flat = sel.reshape(b, t * k, e)
        pos = jnp.cumsum(sel_flat, axis=1) - 1.0     # position within expert
        cap = _capacity(t, k, e, capacity_factor)
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
        return out, moe_stats(
            routed=routed, dropped=routed - jnp.sum(keep),
            expert_rows=b * e * cap,
            experts_hit=jnp.sum(jnp.sum(sel_flat, axis=(0, 1)) > 0))
    return out


def _route(x, router, e, k, capacity_factor, valid, renorm=True):
    """Shared routing: top-k selection, capacity positions, weights.

    Returns (keep [B,S,E], pos_oh would be too big — positions [B,S,E],
    weights_flat [B,S,1], cap) where S = T*k token-major flat choices.
    All tensors are O(B·S·E) — NO capacity dim, so it is cheap to compute
    replicated on every ep shard.
    """
    b, t, d = x.shape
    f32 = jnp.float32
    weights, idx = route_topk(x, router, k, renorm)
    sel = jax.nn.one_hot(idx, e, dtype=f32)
    if valid is not None:
        sel = sel * valid.astype(f32)[:, :, None, None]
    sel_flat = sel.reshape(b, t * k, e)
    pos = jnp.cumsum(sel_flat, axis=1) - 1.0
    cap = _capacity(t, k, e, capacity_factor)
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
                x, router, e, k, capacity_factor, valid_arr,
                cfg.norm_topk_prob)
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
        hit = jnp.sum(jax.lax.psum(jnp.sum(sel_flat, axis=(0, 1)), "dp") > 0)
        return out, dropped, routed, hit

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
        out_specs=(P("dp"), P(), P(), P()),
    )
    f = jax.shard_map(body, check_vma=False, **specs)
    out, dropped, routed, hit = f(x, lp["router"], lp["w_gate"],
                                  lp["w_up"], lp["w_down"], valid_in)
    if return_dropped:
        cap = _capacity(t, k, e, capacity_factor)
        return out, moe_stats(routed=routed, dropped=dropped,
                              expert_rows=b * e * cap, experts_hit=hit)
    return out
