"""Llama-family decoder (also hosts the Mixtral-style MoE MLP variant,
DeepSeek-V3's latent attention, sigmoid router, shared experts and dense
lead: `_mla_front`, `_mlp_block`, `layer_groups`; and afmoe's head-wise
QK-norm, output gate, RoPE-less full layers and dense lead in front of a
window pool's period loop: `qkv_proj`, `_out_gate`, `rope_table`,
`layer_period`; and Falcon-H1's parallel block, a Mamba-2 state-space mixer
beside softmax attention in every layer, with its muP multipliers:
`_ssm_front`, `ssm_decode`, `ssm_mix_rows`, `_times`; and LFM2's gated
short-convolution layers, a kind by `layer_types` that holds a tail and no
pages, served by the period loop: `_conv_front`, `conv_decode`,
`conv_mix_rows`; and Brumby's power-retention layers, a kind that holds
its whole context in a matrix state and no page at all: `_ret_front`,
`ret_decode`, `ret_mix_rows`).

Functional JAX, TPU-first:
- parameters are a pytree of arrays **stacked over layers** and the layer loop
  is a `lax.scan`, so XLA compiles one layer body regardless of depth;
- all matmuls are bf16 on the MXU; softmax/normalization accumulate in f32;
- tensor parallelism is expressed as PartitionSpecs over a named mesh axis
  "tp" (see param_shardings) — XLA inserts the all-reduces over ICI;
- the KV cache is paged ([layers, pages, page_size, kv_heads, head_dim]) and
  attention runs against it in both prefill and decode (ops/attention.py).

Covers the architecture of DeepSeek-R1-Distill-Llama-8B / Llama-3-70B (the
reference's canonical + scale-out configs, reference:
examples/llm/configs/disagg_router.yaml, BASELINE.md) and Mixtral-8x7B when
cfg.num_experts > 0.
"""
# dynalint: hot-path — every op here runs inside jitted decode/prefill programs;
# host syncs (.item(), device_get, float()) are dynalint R6 findings
from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
from typing import Any, Dict, NamedTuple, Optional

import jax
import numpy as np
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from dynamo_tpu.engine.config import ModelConfig
from dynamo_tpu.ops.attention import (
    StepRows, _softcap, attend, attention_rows, attention_rows_pay,
    compact_index, compact_step, decode_attention_deferred,
    decode_attention_split, gather_kv, kv_write_plan, paged_attention,
    step_rows, stored_kv_rows, write_kv_rows,
)
from dynamo_tpu.ops.kv_quant import cache_keys
from dynamo_tpu.ops.linear_attention import BLOCK as KDA_BLOCK
from dynamo_tpu.ops.linear_attention import (
    conv_one_token, conv_with_tail, kda_chunk, kda_step_slots, l2_normalize,
)
from dynamo_tpu.ops.kv_quant import validate_mode as _validate_kv_quant
from dynamo_tpu.ops.power_retention import (
    retention_chunk, retention_step_slots,
)
from dynamo_tpu.ops.state_space import ssd_chunk, ssd_step_slots
from dynamo_tpu.ops.moe import (
    moe_dispatch_mlp, moe_dispatch_mlp_sharded, moe_dropless_mlp, route,
)
from dynamo_tpu.ops.quant import is_quantized, wmat
from dynamo_tpu.ops.paged_attention import (
    combine_self_attention, decode_paged_attention,
    decode_paged_attention_prefix, decode_paged_attention_prefix_sharded,
    decode_paged_attention_sharded,
)

Params = Dict[str, Any]


def _decode_kernel_mode(cfg: ModelConfig) -> Optional[str]:
    """Resolve the decode-attention implementation at trace time.

    Returns "tpu" / "interpret" to use the ragged Pallas kernel (the ONE
    decode-attention kernel, ops/paged_attention.py — per-row page-walk
    lengths cover plain, packed, and prefix-window rows in a single
    program), None for the XLA gather path. On multi-device meshes the
    kernel runs under shard_map over "tp" (auto-sharded jit cannot
    partition a pallas_call).

    "auto" IS the gather path, on every platform: the one on-chip
    comparison (llama3-1b, batch 8, kv~300-600, the kernels the ragged
    one replaced) had the deferred-write gather decode at 7.5 ms/step
    against 34 ms for Pallas — per-(seq, head, page) dots of
    [G<=8, 128] x [rows, 128] are fixed-overhead bound on the MXU, while
    the gather path's single big einsum amortizes. The ragged kernel
    walks the same pages with the same dot shapes and has not been timed
    on a chip (chip_smoke.py's kernel phase checks that it compiles and
    agrees with the gather path, not how fast it is). "on" requests the
    compiled kernel and raises where it cannot serve; "interpret" is the
    CPU test path exercising the kernel code."""
    mode = cfg.decode_kernel
    if mode in ("off", "auto"):
        return None
    if cfg.attn_softcap or cfg.sliding_window or cfg.query_scale:
        # Gemma-2 logit soft-caps / sliding windows live only in the
        # gather paths; the Pallas kernel has no hook for them
        if mode == "on":
            raise ValueError(
                "decode_kernel='on' requested but the model uses "
                "soft-caps/sliding windows/query scaling the Pallas kernel "
                "has no hooks for; use decode_kernel='auto'")
        import logging
        logging.getLogger(__name__).warning(
            "decode_kernel=%r: the model's soft-caps/sliding windows/query "
            "scaling have no hooks in the Pallas kernel; using the XLA "
            "gather path", mode)
        return None
    if mode == "interpret":
        return "interpret"
    return "tpu"


@dataclasses.dataclass
class AttnMetadata:
    """Everything the paged forward pass needs besides tokens.

    All arrays are bucketed to static shapes by the scheduler.
    """

    positions: jax.Array    # [B, Tq] int32 absolute positions
    page_table: jax.Array   # [B, Pb] int32
    kv_lens: jax.Array      # [B] int32 (valid kv length AFTER this step)
    write_idx: jax.Array    # [B, Tq] int32 flat slot indices (<0 = padding)
    # [B] int32: each row's recurrent-state slot (-1 = none); only a model
    # with linear-attention layers has one (ModelConfig.state_leaves)
    state_slots: Optional[jax.Array] = None
    # a model with a window pool (`cfg.window_pool`): each row's table of
    # the pages it holds THERE [B, Wb], the position of that table's first
    # key [B] (a row holds only the pages its step can see, so its table
    # starts at its first held page, not at position 0), and the flat
    # slots its new rows take in the window pool [B, Tq] (>= 0 exactly
    # where `write_idx` is)
    wtable: Optional[jax.Array] = None
    woff: Optional[jax.Array] = None
    wwrite_idx: Optional[jax.Array] = None


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# -- init ---------------------------------------------------------------------

class LayerRun(NamedTuple):
    """A run of consecutive layers of one kind: one stack in `params`,
    one `lax.scan`."""
    key: str          # the stack's key in params
    first: int        # the run's first layer, in the model's order
    count: int
    dense: bool       # a dense MLP (False: experts)
    # attention kind: "mha" | "mla" | "kda" | "swa" | "par" | "conv" | "ret"
    kind: str
    # the run's first layer among the layers that share its STORE: the
    # paged cache's layer axis runs over the "mha" / "mla" layers, the
    # recurrent state's over the "kda" ones, the window pool's over the
    # "swa" ones, and a conv model's state axis over its "conv" ones (==
    # first where all alike). A "par" layer lies on the
    # cache's axis AND on the state's: such a model is one run, in which
    # both are the layer's own index
    store_first: int

    @property
    def is_lead(self) -> bool:
        """A stack of the dense lead in front of a period loop
        (`layer_runs`: a `window_pool` model's, a conv model's)."""
        return self.key.startswith("lead")

    def store_index(self, lid):
        """Layer `lid` of this run -> its index in its store's layer axis
        (`lid` itself where the two axes agree: no arithmetic traced)."""
        if self.store_first == self.first:
            return lid
        return lid - self.first + self.store_first


def layer_runs(cfg: ModelConfig) -> tuple:
    """The model's layers, split once into runs of like layers. One run,
    `params["layers"]`, for every model whose layers are all alike. A
    model with a leading dense MLP before its expert layers
    (`first_dense_layers`) has two stacks: `params["dense_layers"]` and
    then `params["layers"]`, the experts, whose leaves keep their own
    leading axis so the grouped matmul reads them in place (no slice of
    one stack by kind). A hybrid (`linear_group_size`) is split by
    attention kind as well, a stack a run of (attention kind, MLP kind):
    `params["run0"]`, `params["run1"]`, ... in layer order. A model
    whose sliding layers have a pool of their own (`window_pool`) has a
    stack a KIND, `run0` and `run1` in the order the kinds first appear:
    ALL its "swa" layers and ALL its "mha" layers, which do not lie side
    by side in the model (S S S F S S S F ...); `layer_period` says in
    what order the loop takes them. A dense lead in FRONT of such a
    model is no part of those stacks: it has stacks of its own, a run of
    like kinds each (`lead0`, ...), which come first in this tuple, run
    before the loop over periods, and lie FIRST in their kind's store
    (the window pool's layer axis: the lead's "swa" layers, then
    `run0`'s). A model with gated short-convolution layers (`has_conv`)
    is split the same way, a stack a KIND behind its lead's own stacks
    (C C | F C C C F C C C ...: `lead0` the dense conv layers, `run0` all
    "mha" layers, `run1` all "conv" ones), the lead's conv layers FIRST
    on the state's layer axis. init_params,
    param_shardings, forward(), decode_forward() and models/loader.py
    all walk this."""
    lead = cfg.first_dense_layers if cfg.is_moe else 0
    kinds = cfg.layer_kinds()
    own = "ret" if cfg.has_retention else "par" if cfg.has_ssm \
        else "mla" if cfg.is_mla else "mha"
    seen = dict.fromkeys(kinds, 0)

    def like_runs(kinds, prefix):
        """Runs of consecutive layers alike in (attention kind, MLP kind),
        `seen` counting each kind's layers so far (its store's axis)."""
        runs = []
        for i, kind in enumerate(kinds):
            dense = not cfg.is_moe or i < lead
            if runs and (runs[-1].kind, runs[-1].dense) == (kind, dense):
                runs[-1] = runs[-1]._replace(count=runs[-1].count + 1)
            else:
                runs.append(LayerRun(f"{prefix}{len(runs)}", i, 1, dense,
                                     kind, seen[kind]))
            seen[kind] += 1
        return tuple(runs)
    if cfg.window_pool or cfg.has_conv:
        rest = kinds[lead:]
        return like_runs(kinds[:lead], "lead") + tuple(
            LayerRun(f"run{i}", lead + rest.index(kind), rest.count(kind),
                     not cfg.is_moe, kind, seen[kind])
            for i, kind in enumerate(dict.fromkeys(rest)))
    if len(set(kinds)) == 1:
        if not lead:
            return (LayerRun("layers", 0, cfg.num_layers, not cfg.is_moe,
                             own, 0),)
        return (LayerRun("dense_layers", 0, lead, True, own, 0),
                LayerRun("layers", lead, cfg.num_layers - lead, False, own,
                         lead))
    return like_runs(kinds, "run")


class LayerPeriod(NamedTuple):
    """The order in which the loop of a model with a stack a kind (a
    `window_pool` model, a conv model) takes the layers of its kind
    stacks: `count` periods, each the same `parts`, after the
    `lead` runs that stand in front of the loop."""
    count: int
    # ((index in layer_runs, the kind's layers a period, the part's first
    # among them, the part's layers), ...): layer j of a part in period p
    # is layer p * per + offset + j of its run's stack
    parts: tuple
    # how many of layer_runs' first entries are the dense lead's: each a
    # scan of its own BEFORE the scan over periods (0: no lead)
    lead: int = 0


def layer_period(cfg: ModelConfig) -> Optional[LayerPeriod]:
    """None but for a `window_pool` model and a model with conv layers
    (`has_conv`). Its kinds repeat with a period (S S S F, F C C C: the
    shortest prefix whose repetition is the model; the whole model where
    nothing repeats, as behind LFM2's published irregular tail: served
    right, a layer body a part), and ONE compiled loop serves it:
    a scan over periods whose body is a scan a part (models/llama.forward,
    decode_forward). A scan a run of like layers, as the hybrid has,
    would compile six layer bodies for S S S F x 3 where this compiles
    two, and every engine program pays that (PERF.md section 6, PR 38).
    The period is found over the layers AFTER a dense lead
    (`first_dense_layers`), whose runs stand before the loop
    (`LayerPeriod.lead`): one more layer body a run of the lead."""
    if not (cfg.window_pool or cfg.has_conv):
        return None
    runs = layer_runs(cfg)
    lead = sum(run.is_lead for run in runs)
    kinds = cfg.layer_kinds()[sum(run.count for run in runs[:lead]):]
    n = len(kinds)
    if not n:
        raise ValueError(f"{cfg.name}: first_dense_layers leaves no layer "
                         f"behind the lead")
    size = next(p for p in range(1, n + 1)
                if n % p == 0 and kinds == kinds[:p] * (n // p))
    index = {run.kind: i for i, run in enumerate(runs) if i >= lead}
    parts, seen = [], dict.fromkeys(index, 0)
    for i, kind in enumerate(kinds[:size]):
        if parts and parts[-1][0] == index[kind] and i > 0 \
                and kinds[i - 1] == kind:
            parts[-1][3] += 1
        else:
            parts.append([index[kind], kinds[:size].count(kind),
                          seen[kind], 1])
        seen[kind] += 1
    return LayerPeriod(n // size, tuple(tuple(part) for part in parts),
                       lead)


def layer_groups(cfg: ModelConfig) -> tuple:
    """`layer_runs` as ((key in params, first layer, count, dense MLP?),
    ...), for the callers that know one attention kind."""
    return tuple(run[:4] for run in layer_runs(cfg))


def _group_rows(whole: bool, first: int, count: int):
    """A per-layer array's rows of one layer group: all of them, untouched,
    where the model has one group (the older models' programs stay as
    they were traced), else the group's slice."""
    return (lambda a: a) if whole else (lambda a: a[first:first + count])


def _mlp_width(cfg: ModelConfig, dense: bool) -> int:
    """A dense MLP's width: the leading dense layers of an expert model
    have their own (`dense_intermediate_size`)."""
    return cfg.dense_intermediate_size if dense and cfg.is_moe \
        else cfg.intermediate_size


def _init_layer_stack(keys, cfg: ModelConfig, l: int, dense_mlp: bool,
                      kind: str = "") -> Params:
    """One layer group's leaves, stacked over its `l` layers. `kind`: the
    run's attention kind (`layer_runs`; "" = the model's own)."""
    kind = kind or ("mla" if cfg.is_mla else "mha")
    dt = _dtype(cfg)
    d, hd = cfg.hidden_size, cfg.head_dim
    h, hkv = cfg.num_heads, cfg.num_kv_heads
    f = _mlp_width(cfg, dense_mlp)

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32) * fan_in ** -0.5).astype(dt)

    def near_one(key, shape, sd=0.1):
        # seeded, not ones: a norm weight of one would hide a path that
        # skipped it (or applied it per head with the first head's slice)
        return (1.0 + sd * jax.random.normal(key, shape, jnp.float32)
                ).astype(dt)

    more = jax.random.split(jax.random.fold_in(keys[0], 31), 6)
    if kind == "kda":
        hd = cfg.linear_head_dim
    # a leaf behind a muP multiplier (the parallel block's; 1.0 elsewhere)
    # is drawn that much LARGER, so that the function the seeded weights
    # give is the one they would give with every multiplier at 1: with
    # the published multipliers on fan-in-scaled draws the logits would
    # be 1/128 of a nat apart and no comparison could see the model
    m_gate, m_down = cfg.mlp_multipliers
    layers = {
        "attn_norm": jnp.ones((l, d), dt),
        "wo": dense(keys[3], (l, h * hd, d),
                    h * hd * cfg.attention_out_multiplier ** 2),
        "mlp_norm": jnp.ones((l, d), dt),
    }
    if kind == "kda":
        # every leaf that has a neutral value is drawn away from it, so
        # that a path which skipped one is seen (tests/test_ling.py)
        kk = jax.random.split(jax.random.fold_in(keys[0], 57), 8)
        c = h * hd
        f32 = jnp.float32
        layers.update({
            "kda_wqkv": dense(keys[0], (l, d, 3 * c), d),
            "kda_conv_w": (jax.random.normal(
                kk[0], (l, cfg.linear_conv_size, 3 * c), f32)
                * cfg.linear_conv_size ** -0.5).astype(dt),
            "kda_wf": dense(keys[1], (l, d, c), d),
            "kda_wg": dense(keys[2], (l, d, c), d),
            "kda_wb": dense(kk[1], (l, d, h), d),
            # exp(A_log) in about (0.5, 1.8) and dt_bias around -3: the
            # log decay g = bound * sigmoid(exp(A_log) (x Wf + dt_bias))
            # lies mostly in (-1.3, -0.03), a memory of a few to a few
            # dozen tokens, with a tail of channels near the bound. A
            # state that forgets within a token makes o_t ~ (k_t . q_t)
            # v_t, whose head norm flips sign with k . q: a function no
            # precision can be held to
            "kda_a_log": 0.3 * jax.random.normal(kk[2], (l, h), f32),
            "kda_dt_bias": -3.0 + 0.5 * jax.random.normal(kk[3], (l, c),
                                                          f32),
            "kda_o_norm": near_one(kk[4], (l, hd)),
        })
    elif kind == "conv":
        # the taps drawn at unit output variance, no two alike: a path
        # that skipped or swapped one is seen
        layers.update({
            "wo": dense(keys[3], (l, d, d), d),
            "conv_in": dense(keys[0], (l, d, 3 * d), d),
            "conv_w": (jax.random.normal(
                jax.random.fold_in(keys[0], 73), (l, cfg.conv_l_cache, d),
                jnp.float32) * cfg.conv_l_cache ** -0.5).astype(dt),
        })
    elif cfg.is_mla:
        r, dn, dr = (cfg.kv_lora_rank, cfg.qk_nope_head_dim,
                     cfg.qk_rope_head_dim)
        layers.update({
            "wq": dense(keys[0], (l, d, h * (dn + dr)), d),
            "wkv_a": dense(keys[1], (l, d, r + dr), d),
            "kv_a_norm": near_one(more[0], (l, r)),
            # per head k_nope[dn] | v[hd], as the checkpoint has them
            "wkv_b": dense(keys[2], (l, r, h * (dn + hd)), r),
        })
        if cfg.mla_qk_norm:
            layers.update({"mla_q_norm": near_one(more[5], (l, dn + dr)),
                           "mla_k_norm": near_one(
                               jax.random.fold_in(more[5], 1), (l, dr))})
        if cfg.mla_gate:
            layers["w_attn_gate"] = dense(
                jax.random.fold_in(more[5], 2), (l, d, h), d)
    else:
        layers.update({
            "wq": dense(keys[0], (l, d, h * hd), d),
            "wk": dense(keys[1], (l, d, hkv * hd),
                        d * cfg.key_multiplier ** 2),
            "wv": dense(keys[2], (l, d, hkv * hd), d),
        })
        if kind == "par":
            layers.update(_init_ssm_leaves(
                jax.random.fold_in(keys[0], 91), cfg, l, near_one))
        if kind == "ret":
            # the gate g = sigmoid(xn W_g + b_g) a key-value head: each
            # head's bias drawn so that its memory 1 / (1 - g) is log-
            # uniform over 10 .. 1000 tokens (g 0.9 .. 0.999), the token's
            # own part moving the logit by half a unit: a gate of all ones
            # would hide a path that dropped it, and one that forgot
            # within a few tokens a state older than a chunk
            kg = jax.random.split(jax.random.fold_in(keys[0], 113), 2)
            tau = jnp.exp(jax.random.uniform(
                kg[1], (l, hkv), jnp.float32, math.log(10.0),
                math.log(1000.0)))
            layers.update({
                "ret_wg": (0.5 * jax.random.normal(
                    kg[0], (l, d, hkv), jnp.float32) * d ** -0.5).astype(dt),
                "ret_bg": jnp.log(tau - 1.0),
            })
        if cfg.attn_out_gate:
            layers["w_out_gate"] = dense(jax.random.fold_in(more[5], 5),
                                         (l, d, h * hd), d)
    if cfg.post_norms:
        layers.update({
            "post_attn_norm": jnp.ones((l, d), dt),
            "post_mlp_norm": jnp.ones((l, d), dt),
        })
    if cfg.attn_bias and kind != "conv":
        layers.update({
            "wq_b": jnp.zeros((l, h * hd), dt),
            "wk_b": jnp.zeros((l, hkv * hd), dt),
            "wv_b": jnp.zeros((l, hkv * hd), dt),
        })
    if cfg.qk_norm and kind != "conv":
        # one weight vector for all heads where the norm is a head's
        per_head = cfg.qk_norm == "head"
        layers.update({
            "q_norm": near_one(keys[10], (l, hd if per_head else h * hd)),
            "k_norm": near_one(keys[11],
                               (l, hd if per_head else hkv * hd)),
        })
    if cfg.is_moe and not dense_mlp:
        # the router's width, and the experts held here (a share, or all)
        e, held = cfg.num_experts, cfg.local_experts
        layers.update({
            "router": dense(keys[4], (l, d, e), d),
            "w_gate": dense(keys[5], (l, held, d, f), d),
            "w_up": dense(keys[6], (l, held, d, f), d),
            "w_down": dense(keys[7], (l, held, f, d), f),
        })
        if cfg.moe_router_bias:
            # float32 like the checkpoint's; seeded, so that a router
            # which ignored it (or weighed with it) is seen
            layers["router_bias"] = 0.1 * jax.random.normal(
                more[1], (l, e), jnp.float32)
        if cfg.shared_expert_size:
            fs = cfg.shared_expert_size
            layers.update({
                "ws_gate": dense(more[2], (l, d, fs), d),
                "ws_up": dense(more[3], (l, d, fs), d),
                "ws_down": dense(more[4], (l, fs, d), fs),
            })
    else:
        layers.update({
            "w_gate": dense(keys[5], (l, d, f), d * m_gate ** 2),
            "w_up": dense(keys[6], (l, d, f), d),
            "w_down": dense(keys[7], (l, f, d), f * m_down ** 2),
        })
    if cfg.has_state or cfg.window_pool:
        # a hybrid's block norms too are drawn away from one
        layers["attn_norm"] = near_one(jax.random.fold_in(more[5], 3), (l, d))
        layers["mlp_norm"] = near_one(jax.random.fold_in(more[5], 4), (l, d))
        if cfg.post_norms:
            layers["post_attn_norm"] = near_one(
                jax.random.fold_in(more[5], 6), (l, d))
            layers["post_mlp_norm"] = near_one(
                jax.random.fold_in(more[5], 7), (l, d))
    return layers


def ssm_segments(cfg: ModelConfig) -> np.ndarray:
    """`ssm_multipliers` spread over the columns of the mixer's input
    projection, z | x | B | C | dt: the constant vector the published
    code calls `mup_vector`, float32 on the host."""
    gn = cfg.mamba_n_groups * cfg.mamba_d_state
    return np.repeat(np.asarray(cfg.ssm_multipliers, np.float32),
                     [cfg.mamba_d_ssm, cfg.mamba_d_ssm, gn, gn,
                      cfg.mamba_n_heads])


def _init_ssm_leaves(key, cfg: ModelConfig, l: int, near_one) -> Params:
    """The state-space mixer's leaves of a "par" stack of `l` layers,
    seeded (`_init_layer_stack`). `ssm_in` [D, z | x | B | C | dt]: each
    segment's columns drawn 1 / (ssm_in_multiplier x its entry of
    ssm_multipliers) larger, the dt columns at half of that besides (the
    time step should follow its bias more than the token). A_log = log(1
    .. H) and D = 1 as published. dt_bias: each head's memory 1 / (dt A)
    is drawn log-uniform over 3 .. 300 tokens, dt_bias = softplus^-1 of
    that dt, so the decays span a few to a few hundred tokens: a state
    that forgot within a token would make the mixer a function of the
    current token alone, and one that never forgot would show no decay.
    The convolution's bias and the gated norm's weight are drawn away
    from their neutral values."""
    dt, f32 = _dtype(cfg), jnp.float32
    d, ds, h = cfg.hidden_size, cfg.mamba_d_ssm, cfg.mamba_n_heads
    kk = jax.random.split(key, 6)
    seg = ssm_segments(cfg) * cfg.ssm_in_multiplier
    seg[-h:] *= 2.0
    a = np.arange(1, h + 1, dtype=np.float32)
    tau = jnp.exp(jax.random.uniform(kk[3], (l, h), f32, math.log(3.0),
                                     math.log(300.0)))
    dt0 = 1.0 / (tau * a)
    return {
        "ssm_in": (jax.random.normal(kk[0], (l, d, seg.size), f32)
                   * d ** -0.5 / seg).astype(dt),
        "ssm_conv_w": (jax.random.normal(
            kk[1], (l, cfg.mamba_d_conv, cfg.mamba_conv_dim), f32)
            * cfg.mamba_d_conv ** -0.5).astype(dt),
        "ssm_conv_b": (0.1 * jax.random.normal(
            kk[2], (l, cfg.mamba_conv_dim), f32)).astype(dt),
        "ssm_a_log": jnp.broadcast_to(jnp.log(a), (l, h)),
        "ssm_d": jnp.ones((l, h), f32),
        "ssm_dt_bias": jnp.log(jnp.expm1(dt0)),
        "ssm_norm": near_one(kk[4], (l, ds)),
        "ssm_out": (jax.random.normal(kk[5], (l, ds, d), f32)
                    * ds ** -0.5 / cfg.ssm_out_multiplier).astype(dt),
    }


def init_params(rng: jax.Array, cfg: ModelConfig) -> Params:
    """Random-init parameters (stacked over layers, a stack a layer kind:
    `layer_groups`)."""
    dt = _dtype(cfg)
    d = cfg.hidden_size
    keys = jax.random.split(rng, 12)

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32) * fan_in ** -0.5).astype(dt)

    params: Params = {
        "embed": dense(keys[8], (cfg.vocab_size, d), d),
        "final_norm": jnp.ones((d,), dt),
    }
    for run in layer_runs(cfg):
        params[run.key] = _init_layer_stack(
            keys if run.key == "layers"
            else jax.random.split(jax.random.fold_in(rng, 1 + run.first),
                                  12),
            cfg, run.count, run.dense, run.kind)
    if cfg.has_state or cfg.window_pool:
        params["final_norm"] = (1.0 + 0.1 * jax.random.normal(
            jax.random.fold_in(rng, 77), (d,), jnp.float32)).astype(dt)
    if not cfg.tie_word_embeddings:
        # behind its multiplier, like a "par" layer's leaves
        params["lm_head"] = dense(keys[9], (d, cfg.vocab_size),
                                  d * cfg.lm_head_multiplier ** 2)
    if cfg.vision is not None:
        from dynamo_tpu.models import vision
        params["vision"] = vision.init_params(keys[10], cfg)
    return params


def param_shardings(cfg: ModelConfig) -> Params:
    """PartitionSpecs matching init_params' tree; mesh axes ("dp", "tp").

    Megatron-style TP (reference delegates TP to engines via
    --tensor-parallel-size, reference: launch/dynamo-run/src/lib.rs +
    engines/sglang/worker.rs:285-320; here it is first-class): attention heads
    and MLP hidden dim shard over "tp"; XLA inserts the psum after wo/w_down.
    MoE experts shard over "tp" as well (expert-parallel uses the same axis
    until the dedicated "ep" mesh is used — see models/moe notes).
    """
    out: Params = {
        "embed": P(None, None),
        "final_norm": P(None),
    }
    for run in layer_runs(cfg):
        out[run.key] = _layer_stack_shardings(cfg, run.dense, run.kind)
    if not cfg.tie_word_embeddings:
        out["lm_head"] = P(None, "tp")
    if cfg.vision is not None:
        from dynamo_tpu.models import vision
        out["vision"] = vision.param_shardings(cfg)
    return out


def _layer_stack_shardings(cfg: ModelConfig, dense_mlp: bool,
                           kind: str = "") -> Params:
    """PartitionSpecs of one layer group: `_init_layer_stack`'s tree."""
    layers = {
        "attn_norm": P(None, None),
        "wq": P(None, None, "tp"),
        "wo": P(None, "tp", None),
        "mlp_norm": P(None, None),
    }
    if kind == "kda":
        # no mesh serves a recurrent state yet
        # (engine/config.refuse_unserved): everything replicated
        del layers["wq"]
        layers["wo"] = P(None, None, None)
        layers.update({name: P(None, None, None) for name in (
            "kda_wqkv", "kda_conv_w", "kda_wf", "kda_wg", "kda_wb")})
        layers.update({name: P(None, None) for name in (
            "kda_a_log", "kda_dt_bias", "kda_o_norm")})
    elif kind == "conv":
        # no mesh serves a recurrent state: everything replicated
        del layers["wq"]
        layers.update({"wo": P(None, None, None),
                       "conv_in": P(None, None, None),
                       "conv_w": P(None, None, None)})
    elif cfg.is_mla:
        # the latent projection and its norm are shared by every head;
        # no mesh serves this model yet (engine/config.refuse_unserved)
        layers.update({"wkv_a": P(None, None, None),
                       "kv_a_norm": P(None, None),
                       "wkv_b": P(None, None, "tp")})
        if cfg.mla_qk_norm:
            layers.update({"mla_q_norm": P(None, None),
                           "mla_k_norm": P(None, None)})
        if cfg.mla_gate:
            layers["w_attn_gate"] = P(None, None, None)
    else:
        layers.update({"wk": P(None, None, "tp"), "wv": P(None, None, "tp")})
        if kind == "par":
            # no mesh serves a recurrent state: the mixer is replicated
            layers.update({name: P(None, None, None) for name in (
                "ssm_in", "ssm_conv_w", "ssm_out")})
            layers.update({name: P(None, None) for name in (
                "ssm_conv_b", "ssm_a_log", "ssm_d", "ssm_dt_bias",
                "ssm_norm")})
        if kind == "ret":
            # no mesh serves a recurrent state: the gate is replicated
            layers.update({"ret_wg": P(None, None, None),
                           "ret_bg": P(None, None)})
        if cfg.attn_out_gate:
            layers["w_out_gate"] = P(None, None, "tp")
    if cfg.post_norms:
        layers.update({
            "post_attn_norm": P(None, None),
            "post_mlp_norm": P(None, None),
        })
    if cfg.attn_bias and kind != "conv":
        layers.update({
            "wq_b": P(None, "tp"),
            "wk_b": P(None, "tp"),
            "wv_b": P(None, "tp"),
        })
    if cfg.qk_norm and kind != "conv":
        # a head's norm has one weight vector, whole on every shard
        spec = P(None, None) if cfg.qk_norm == "head" else P(None, "tp")
        layers.update({"q_norm": spec, "k_norm": spec})
    if cfg.is_moe and not dense_mlp:
        # experts shard over "ep", each expert's FFN dim over "tp"; on
        # meshes without those axes (size 1) the specs are no-ops
        layers.update({
            "router": P(None, None, None),
            "w_gate": P(None, "ep", None, "tp"),
            "w_up": P(None, "ep", None, "tp"),
            "w_down": P(None, "ep", "tp", None),
        })
        if cfg.moe_router_bias:
            layers["router_bias"] = P(None, None)
        if cfg.shared_expert_size:
            layers.update({"ws_gate": P(None, None, "tp"),
                           "ws_up": P(None, None, "tp"),
                           "ws_down": P(None, "tp", None)})
    else:
        layers.update({
            "w_gate": P(None, None, "tp"),
            "w_up": P(None, None, "tp"),
            "w_down": P(None, "tp", None),
        })
    return layers


def cache_sharding(cfg: ModelConfig) -> P:
    """KV cache [L, rows, P, ps, width] (`cfg.kv_cache_leaves`: a row is
    one kv head's hd values, or f adjacent heads'): shard the rows over
    tp, whole rows a shard (engine/config.kv_heads_per_row).

    Head-major so one (head, page) slice is a contiguous [ps, hd] block —
    the decode kernel's DMA unit (ops/paged_attention.py)."""
    del cfg
    return P(None, "tp", None, None, None)


def cache_scale_sharding(cfg: ModelConfig) -> P:
    """KV scale arrays [L, Hkv, P, ps]: kv heads over tp, like the values."""
    del cfg
    return P(None, "tp", None, None)


def cache_shardings(cfg: ModelConfig) -> Dict[str, P]:
    """Per-leaf PartitionSpecs matching init_cache's dict layout."""
    out = {name: cache_sharding(cfg) for name in
           (*cfg.kv_cache_leaves(), *cfg.window_cache_leaves())}
    if _validate_kv_quant(cfg.kv_quant):
        out.update({f"{name}_scale": cache_scale_sharding(cfg)
                    for name in cfg.kv_cache_leaves()})
    return out


def init_cache(cfg: ModelConfig, num_pages: int, page_size: int,
               window_pages: int = 0) -> Dict[str, jax.Array]:
    """The paged pool as it is stored, a leaf per entry of
    `cfg.kv_cache_leaves()`: [L, rows, pages, page_size, width], a row
    `cfg.kv_row_heads` adjacent kv heads of head_dim each (one, unless
    an engine resolved otherwise); and, for a model with a window pool,
    a leaf per entry of `cfg.window_cache_leaves()` over ITS layers and
    `window_pages` pages. No leaf at all for a model none of whose layers
    holds a page (power retention): its context is `init_state`'s."""
    shapes = {name: (cfg.num_cache_layers, heads, num_pages, page_size,
                     width)
              for name, (heads, width) in cfg.kv_cache_leaves().items()}
    shapes.update({
        name: (cfg.num_window_layers, heads, window_pages, page_size, width)
        for name, (heads, width) in cfg.window_cache_leaves().items()})
    if _validate_kv_quant(cfg.kv_quant):
        # int8 pages + per-row f32 scales (ops/kv_quant.py): the scale
        # array shares the page axis (2) with the values, so every
        # page-indexed move (extract/inject/offload/transfer) carries
        # the scales with the same ids
        out = {name: jnp.zeros(shape, jnp.int8)
               for name, shape in shapes.items()}
        out.update({f"{name}_scale": jnp.zeros(shape[:-1], jnp.float32)
                    for name, shape in shapes.items()})
        return out
    return {name: jnp.zeros(shape, _dtype(cfg))
            for name, shape in shapes.items()}


def init_state(cfg: ModelConfig, slots: int) -> Dict[str, jax.Array]:
    """The per-sequence recurrent state, a leaf per entry of
    `cfg.state_leaves()`: [state layers, slots + 1, ...], zeros. It lives
    in the engine's cache dict beside the paged pool. The last slot is no
    sequence's: it is the SCRATCH slot that the slot-addressed update
    points its dead rows at (`ops/linear_attention.kda_step_slots`);
    `slots` is what the scheduler hands out."""
    return {name: jnp.zeros((cfg.num_state_layers, slots + 1) + shape,
                            jnp.dtype(dtype))
            for name, (shape, dtype) in cfg.state_leaves().items()}


# -- forward ------------------------------------------------------------------

def rms_norm(x: jax.Array, w: jax.Array, eps: float,
             plus_one: bool = False) -> jax.Array:
    xf = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    if plus_one:
        # Gemma stores the norm weight as a delta from 1 and applies it in
        # f32 before the downcast (HF GemmaRMSNorm)
        return (xf * scale * (1.0 + w.astype(jnp.float32))).astype(x.dtype)
    return (xf * scale).astype(x.dtype) * w


def mlp_activation(gate: jax.Array, cfg: ModelConfig) -> jax.Array:
    """GLU gate activation in f32: SiLU (llama) or tanh-GELU (Gemma)."""
    gf = gate.astype(jnp.float32)
    a = (jax.nn.gelu(gf, approximate=True) if cfg.mlp_act == "gelu_tanh"
         else jax.nn.silu(gf))
    return a.astype(gate.dtype)


def scale_embeds(x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Gemma multiplies embedding outputs by sqrt(hidden) (in x.dtype)."""
    if cfg.embed_scale:
        return x * jnp.asarray(cfg.embed_scale, x.dtype)
    return x


def yarn_inv_freq(p, dim: int) -> np.ndarray:
    """YaRN's frequency table [dim / 2] for one layer kind (`p`:
    engine/config.RopeParams), float32 on the host, as `transformers`
    computes it: f_i = theta ** (2i / dim); the extrapolated 1 / f_i and
    the interpolated 1 / (factor f_i) blend by a linear ramp over i
    between low = floor(c(beta_fast)) and high = ceil(c(beta_slow)),
    c(r) = dim ln(L0 / (2 pi r)) / (2 ln theta), clipped to [0, dim - 1]:
    dimensions that turn more than beta_fast times within the original
    context L0 keep their frequency, those that turn less than beta_slow
    times are stretched by `factor`."""
    f32 = np.float32
    pos_freqs = f32(p.theta) ** (np.arange(0, dim, 2, dtype=f32) / f32(dim))
    extrapolated = f32(1.0) / pos_freqs
    interpolated = f32(1.0) / (f32(p.factor) * pos_freqs)

    def correction(turns):
        return dim * math.log(p.original_max_position
                              / (turns * 2 * math.pi)) \
            / (2 * math.log(p.theta))
    low = max(math.floor(correction(p.beta_fast)), 0)
    high = min(math.ceil(correction(p.beta_slow)), dim - 1)
    if low == high:
        high += 0.001   # transformers' guard against a zero-width ramp
    ramp = np.clip((np.arange(dim // 2, dtype=f32) - f32(low))
                   / f32(high - low), 0, 1).astype(f32)
    return (interpolated * ramp + extrapolated * (f32(1.0) - ramp)
            ).astype(f32)


def _lead_scope(run: LayerRun):
    """`layers.lead` around the scan of the dense lead in front of a
    period loop (`layer_runs`), whose layer body is its own beside the bodies of the
    period loop; no scope around any other run."""
    return jax.named_scope("layers.lead") if run.is_lead \
        else contextlib.nullcontext()


def _full_scope(cfg: ModelConfig, kind: str = ""):
    """`attention.full` around a full layer's attention where the model
    also has window layers (`attention.window`); `attention` inside
    `block.parallel` where a state-space mixer stands beside it (`kind`
    "par"); no scope elsewhere."""
    if kind == "par":
        return jax.named_scope("block.parallel/attention")
    return jax.named_scope("attention.full") if cfg.window_pool \
        else contextlib.nullcontext()


def _times(x: jax.Array, m: float) -> jax.Array:
    """x times a muP multiplier of the configuration, the product formed
    in float32 and rounded once to x's dtype, as the published code's
    tensor-times-scalar does; 1.0 traces nothing."""
    if m == 1.0:
        return x
    return (x.astype(jnp.float32) * m).astype(x.dtype)


def rope_table(cfg: ModelConfig, kind: str = "") -> tuple:
    """(theta, frequency table or None, cos / sin scale) of one layer
    kind's RoPE, built on the host from the config: what `apply_rope`
    takes after x and the positions. `kind` "swa": `cfg.rope_sliding`;
    any other: `cfg.rope_full`. A kind without parameters of its own, or
    with plain ones, is (theta, None, 1.0): `apply_rope` then computes
    1 / theta ** (2i / d) inline, the one expression every program had
    before RoPE went by kind, so those programs are the same programs.
    None for a kind WITHOUT a positional embedding (`rope_type` "none"):
    the caller then traces nothing for it (no multiply by cos 0 = 1)."""
    p = cfg.rope_sliding if kind == "swa" else cfg.rope_full
    if p is None:
        return cfg.rope_theta, None, 1.0
    if p.rope_type == "default":
        return p.theta, None, 1.0
    if p.rope_type == "none":
        return None
    if p.rope_type != "yarn":
        raise ValueError(f"{cfg.name}: rope_type {p.rope_type!r} is not "
                         f"modelled (default, none, yarn)")
    # Python numbers of the config throughout: nothing here is traced
    scale = p.attention_factor or 0.1 * math.log(p.factor) + 1.0
    return p.theta, yarn_inv_freq(p, cfg.head_dim), scale


@jax.named_scope("attention.rope")
def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               inv_freq: Optional[np.ndarray] = None,
               scale: float = 1.0) -> jax.Array:
    """x: [B, T, H, hd]; positions: [B, T]. `inv_freq` [hd / 2] (a host
    float32 table, `rope_table`) replaces the plain 1 / theta ** (2i /
    hd); `scale` multiplies cos and sin (YaRN's attention factor)."""
    hd = x.shape[-1]
    if inv_freq is None:
        freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, jnp.float32) / hd))
    else:
        freqs = jnp.asarray(inv_freq, jnp.float32)                     # [hd/2]
    angles = positions[..., None].astype(jnp.float32) * freqs          # [B, T, hd/2]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


@jax.named_scope("moe")
def _moe_mlp(x: jax.Array, lp: Params, cfg: ModelConfig) -> jax.Array:
    """Dense-compute MoE (top-k routing, all experts evaluated then masked).

    TPU-friendly for moderate expert counts: one big batched einsum over the
    expert axis keeps the MXU busy and avoids dynamic shapes. A ragged
    all-to-all EP dispatch over a dedicated "ep" axis is the scale-out path
    (parallel/expert.py).
    """
    b, t, d = x.shape
    e, k = cfg.num_experts, cfg.num_experts_per_tok
    weights, idx = route(x, lp, cfg)                           # [B, T, k]
    one_hot = jax.nn.one_hot(idx, e, dtype=jnp.float32)        # [B, T, k, E]
    combine = jnp.einsum("btk,btke->bte", weights, one_hot)    # [B, T, E]
    if cfg.experts_held:      # a share: the absent experts add nothing
        combine = combine[..., cfg.expert_first:
                          cfg.expert_first + cfg.experts_held]

    gate = jnp.einsum("btd,edf->betf", x, wmat(lp["w_gate"], x.dtype))
    up = jnp.einsum("btd,edf->betf", x, wmat(lp["w_up"], x.dtype))
    act = mlp_activation(gate, cfg) * up
    down = jnp.einsum("betf,efd->betd", act,
                      wmat(lp["w_down"], x.dtype))             # [B, E, T, D]
    return jnp.einsum("betd,bte->btd", down.astype(jnp.float32), combine).astype(x.dtype)


def _dense_mlp(x: jax.Array, lp: Params, cfg: ModelConfig,
               leaves: tuple = ("w_gate", "w_up", "w_down")) -> jax.Array:
    w_gate, w_up, w_down = (wmat(lp[name], x.dtype) for name in leaves)
    m_gate, m_down = cfg.mlp_multipliers   # the gate's before the SiLU
    gate = _times(jnp.einsum("btd,df->btf", x, w_gate), m_gate)
    up = jnp.einsum("btd,df->btf", x, w_up)
    act = mlp_activation(gate, cfg) * up
    return _times(jnp.einsum("btf,fd->btd", act, w_down), m_down)


@jax.named_scope("attention.qkv")
def qkv_proj(xn: jax.Array, lp: Params, cfg: ModelConfig):
    """x -> (q [B, T, H*hd], k, v [B, T, Hkv*hd]), before the split into
    heads and RoPE. With `cfg.qk_norm` True (OLMoE) q and k each pass an
    RMSNorm over the WHOLE projection, all heads together, with its own
    weight vector; with "head" (afmoe) over EACH head's `head_dim`
    values, one weight vector shared by the heads. What reaches the
    cache is the normed (and, where the kind rotates, rotated) k."""
    q = jnp.einsum("btd,de->bte", xn, wmat(lp["wq"], xn.dtype))
    k = _times(jnp.einsum("btd,de->bte", xn, wmat(lp["wk"], xn.dtype)),
               cfg.key_multiplier)
    v = jnp.einsum("btd,de->bte", xn, wmat(lp["wv"], xn.dtype))
    if cfg.attn_bias:
        q, k, v = q + lp["wq_b"], k + lp["wk_b"], v + lp["wv_b"]
    if cfg.qk_norm == "head":
        with jax.named_scope("attention.head_qk_norm"):
            by_head = q.shape[:2] + (-1, cfg.head_dim)
            q = rms_norm(q.reshape(by_head), lp["q_norm"],
                         cfg.rms_norm_eps).reshape(q.shape)
            k = rms_norm(k.reshape(by_head), lp["k_norm"],
                         cfg.rms_norm_eps).reshape(k.shape)
    elif cfg.qk_norm:
        with jax.named_scope("attention.qk_norm"):
            q = rms_norm(q, lp["q_norm"], cfg.rms_norm_eps)
            k = rms_norm(k, lp["k_norm"], cfg.rms_norm_eps)
    return q, k, v


EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


def _use_dropless(cfg: ModelConfig, mesh) -> bool:
    return (cfg.is_moe and cfg.moe_impl == "dispatch" and cfg.moe_dropless
            and (mesh is None or mesh.size == 1))


def split_expert_stacks(layers: Params, cfg: ModelConfig, mesh):
    """(the leaves the layer scan slices, the expert stacks it does not).
    On the dropless path the stacked [L, E, ...] expert leaves stay out of
    the scan's xs and are handed to the layer whole with its index: the
    grouped-matmul kernel reads a layer's experts where they lie, where a
    slice of the stack would be copied before every call. Quantized
    leaves are dequantized a layer at a time and stay in the scan."""
    if not _use_dropless(cfg, mesh) or is_quantized(layers["w_gate"]):
        return layers, None
    return ({k: v for k, v in layers.items() if k not in EXPERT_LEAVES},
            {k: layers[k] for k in EXPERT_LEAVES})


def _mlp_block(xn: jax.Array, lp: Params, cfg: ModelConfig, mesh,
               token_valid, stacks=None, lid=None, dense: bool = False):
    """The layer's MLP: (out, stats). stats is None except on the MoE
    dispatch paths, where it is ops/moe.py's `moe_stats` dict. `stacks`
    and `lid`: split_expert_stacks' second half and this layer's index
    in them. `dense`: a layer of the group with a dense MLP
    (`layer_groups`), whatever the model's other layers are. An expert
    layer with `shared_expert_size` adds ONE dense SwiGLU of that width,
    evaluated on every token, to the routed experts' output."""
    if dense and cfg.is_moe:
        with jax.named_scope("mlp.dense_lead"):
            return _dense_mlp(xn, lp, cfg), None
    if not cfg.is_moe:
        with jax.named_scope("mlp"):
            return _dense_mlp(xn, lp, cfg), None
    if cfg.moe_impl == "dense":
        out, stats = _moe_mlp(xn, lp, cfg), None
    elif mesh is not None and mesh.shape.get("ep", 1) > 1:
        # explicit O(E/ep) per-shard dispatch (ops/moe.py sharded path)
        out, stats = moe_dispatch_mlp_sharded(
            xn, lp, cfg, mesh, return_dropped=True, valid=token_valid)
    elif _use_dropless(cfg, mesh):
        if stacks is not None:
            out, stats = moe_dropless_mlp(xn, {**lp, **stacks}, cfg,
                                          valid=token_valid, layer=lid)
        else:
            out, stats = moe_dropless_mlp(xn, lp, cfg, valid=token_valid)
    else:
        out, stats = moe_dispatch_mlp(xn, lp, cfg, return_dropped=True,
                                      valid=token_valid)
    if cfg.shared_expert_size:
        with jax.named_scope("moe.shared"):
            out = out + _dense_mlp(xn, lp, cfg,
                                   ("ws_gate", "ws_up", "ws_down"))
    return out, stats


def _sum_stats(stats) -> dict:
    """Per-layer (and per-step) stacks of MoE stats -> one scalar each."""
    return {k: jnp.sum(v) for k, v in (stats or {}).items()}


def _merge_stats(groups: list) -> dict:
    """The layer groups' summed stats -> one dict (a group without
    experts has none)."""
    out: dict = {}
    for stats in groups:
        for k, v in stats.items():
            out[k] = out[k] + v if k in out else v
    return out


# -- the layer ----------------------------------------------------------------
# One transformer layer, cut where streamed decode runs host code between
# the halves. Between them each caller does what is really its own: the
# cache update and the attention. Callers: forward(), decode_forward(),
# models/pp._stage, engine/streaming._stream_layer_start / _finish.

def layer_front(x: jax.Array, lp: Params, cfg: ModelConfig,
                positions: jax.Array, heads: tuple, kind: str = ""):
    """x [B, T, D] -> q [B, T, H, hd], k, v [B, T, Hkv, hd]: attention
    norm, QKV projection (bias, QK-norm), split into heads, RoPE on q and
    k where `kind` has one (`rope_table`). `heads` = (H, Hkv) as the caller holds them: a "tp" shard of a
    manual mesh passes its local counts. Where the pool's rows hold f =
    `cfg.kv_row_heads` > 1 heads, all three come out over ROWS, after the
    norms and RoPE: k, v [B, T, Hkv / f, f * hd] (a view: the heads of a
    row are adjacent) and q [B, T, H, f * hd] (`_query_rows`); every
    caller then hands attention `attn_scale(cfg)` and `layer_back` keeps
    each head's own lanes of the output. Under latent attention
    (`_mla_front`) q is the absorbed query, k the token's ONE cache row
    and v None: the attention ops take the whole row as its values and
    `_mla_out` keeps the latent columns of what they return. A linear
    layer (`kind` "kda", `_kda_front`) hands back what its state update
    takes instead: the convolution's inputs, the decay and beta. A
    parallel block (`kind` "par") takes this path for its attention
    branch (its multipliers on the normed input and on k); its mixer
    reads the same input on its own (`_ssm_front`)."""
    if kind == "kda":
        return _kda_front(x, lp, cfg)
    if cfg.is_mla:
        return _mla_front(x, lp, cfg, positions)
    b, t = x.shape[:2]
    h, hkv = heads
    with jax.named_scope("norm.attn"):
        xn = _times(rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps,
                             cfg.norm_plus_one), cfg.attention_in_multiplier)
    q, k, v = qkv_proj(xn, lp, cfg)
    rope = rope_table(cfg, kind)

    def heads_of(a, n):
        a = a.reshape(b, t, n, cfg.head_dim)
        return a if rope is None else apply_rope(a, positions, *rope)
    q, k, v = heads_of(q, h), heads_of(k, hkv), \
        v.reshape(b, t, hkv, cfg.head_dim)
    f = cfg.kv_row_heads
    if f == 1:
        return q, k, v
    # the pool's rows hold f adjacent KV heads (`kv_cache_leaves`): k and
    # v as stored, a view; each query over its KV head's lanes of the row
    return _query_rows(q, cfg), k.reshape(b, t, hkv // f, -1), \
        v.reshape(b, t, hkv // f, -1)


def _own_lanes(cfg: ModelConfig, h: int) -> np.ndarray:
    """[h, f] bool: which of a pool row's f heads is query head i's own.
    Head i reads KV head i // g (g = H / Hkv), which lies in row
    i // (g f), lanes ((i // g) % f) * hd ..; a "tp" shard's heads start
    on a row's edge (`kv_heads_per_row`), so its local i says the same."""
    f = cfg.kv_row_heads
    return ((np.arange(h) // cfg.q_per_kv) % f)[:, None] == np.arange(f)


def _query_rows(q: jax.Array, cfg: ModelConfig) -> jax.Array:
    """q [B, T, H, hd] -> [B, T, H, f * hd], zero outside its KV head's
    lanes: q . row is then the published score exactly (the row's other
    heads meet zeros), the standard grouping holds over Hkv / f rows of
    g f heads each, and no attention op knows the row is shared."""
    own = _own_lanes(cfg, q.shape[2])[:, :, None]
    return jnp.where(own, q[..., None, :], 0).reshape(
        q.shape[:3] + (-1,))


def _head_values(attn: jax.Array, cfg: ModelConfig) -> jax.Array:
    """The way back: attention's output over pool rows [B, T, H, f * hd]
    (each head weighted the WHOLE row's values) -> [B, T, H, hd], each
    head's own lanes."""
    b, t, h = attn.shape[:3]
    own = _own_lanes(cfg, h)[:, :, None]
    return jnp.sum(jnp.where(
        own, attn.reshape(b, t, h, cfg.kv_row_heads, -1), 0), axis=-2)


def attn_scale(cfg: ModelConfig) -> float:
    """What every attention op is handed as `q_scale`. 0.0 selects
    `width ** -0.5` from the OPERAND's last axis (ops/attention._scale),
    which is head_dim only while a row is one head: where rows are shared
    the scale is named. A latent row's width is neither the width of
    a head's key (qk_nope_head_dim + qk_rope_head_dim) nor, stored in
    whole lane tiles, the model's own: named too."""
    if cfg.is_mla:
        return cfg.query_scale or (
            cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    if cfg.kv_row_heads == 1:
        return cfg.query_scale
    return cfg.query_scale or cfg.head_dim ** -0.5


def _mla_up_proj(lp: Params, cfg: ModelConfig, dtype) -> tuple:
    """`wkv_b` [r, H * (dn + hd)] as (W_UK [r, H, dn], W_UV [r, H, hd])."""
    w = wmat(lp["wkv_b"], dtype).reshape(
        cfg.kv_lora_rank, cfg.num_heads, cfg.qk_nope_head_dim + cfg.head_dim)
    return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]


def _mla_front(x: jax.Array, lp: Params, cfg: ModelConfig,
               positions: jax.Array):
    """Multi-head latent attention in the absorbed form, the front half:
    x [B, T, D] -> (q [B, T, H, w], row [B, T, 1, w], None), w the
    width the pool stores a row in (`cfg.kv_cache_leaves`): r + dr, or
    that in whole lane tiles with zeros in the lanes past r + dr of BOTH
    (engine/config.kv_row_lanes), which adds exact zeros to q . row.

    `row` is what the cache stores for a token, once: the latent
    c = RMSNorm(x Wkv_a[:, :r]; kv_a_norm) and the rotated key part
    k_pe = RoPE(x Wkv_a[:, r:]) that every head shares. The query of
    head h is (q_nope_h W_UK_h^T | RoPE(q_pe_h)), so that q . row =
    q_nope . (c W_UK_h) + q_pe . k_pe: the published scores without ever
    expanding c to per-head keys. The values are c itself (the row's
    first r columns); `_mla_out` applies W_UV after the softmax. RoPE is
    rotate-half over the dr rope columns only: models/loader.py
    de-interleaves those columns of Wq / Wkv_a once at load, which is
    the published code's own first step moved from the activations to
    the weights."""
    b, t = x.shape[:2]
    h, r = cfg.num_heads, cfg.kv_lora_rank
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    with jax.named_scope("norm.attn"):
        xn = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps,
                      cfg.norm_plus_one)
    with jax.named_scope("attention.mla.q"):
        q = jnp.einsum("btd,de->bte", xn, wmat(lp["wq"], xn.dtype)
                       ).reshape(b, t, h, dn + dr)
        if cfg.mla_qk_norm:
            q = rms_norm(q, lp["mla_q_norm"], cfg.rms_norm_eps)
        q_pe = apply_rope(q[..., dn:], positions, cfg.rope_theta)
    with jax.named_scope("attention.mla.latent"):
        ckv = jnp.einsum("btd,de->bte", xn, wmat(lp["wkv_a"], xn.dtype))
        c = rms_norm(ckv[..., :r], lp["kv_a_norm"], cfg.rms_norm_eps)
        k_pe = ckv[:, :, None, r:]
        if cfg.mla_qk_norm:
            k_pe = rms_norm(k_pe, lp["mla_k_norm"], cfg.rms_norm_eps)
        k_pe = apply_rope(k_pe, positions, cfg.rope_theta)
        row = _stored_row(jnp.concatenate([c[:, :, None, :], k_pe],
                                          axis=-1), cfg)
    with jax.named_scope("attention.mla.absorb"):
        w_uk, _ = _mla_up_proj(lp, cfg, xn.dtype)
        q_lat = jnp.einsum("bthn,rhn->bthr", q[..., :dn], w_uk)
        q = _stored_row(jnp.concatenate([q_lat, q_pe], axis=-1), cfg)
    return q, row, None


def _stored_row(a: jax.Array, cfg: ModelConfig) -> jax.Array:
    """[..., r + dr] -> [..., the stored row's width], zeros in the pad."""
    pad = cfg.kv_row_pad
    return jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, pad)]) if pad else a


def _mla_out(attn: jax.Array, lp: Params, cfg: ModelConfig) -> jax.Array:
    """The absorbed form's back half: the softmax-weighted cache rows
    [B, T, H, the stored width], of which the first r columns are o_lat
    (the values of a one-leaf cache are its latent columns; the rope
    columns and a stored row's zero pad are dropped) -> [B, T, H, hd]
    through each head's W_UV."""
    with jax.named_scope("attention.mla.out"):
        _, w_uv = _mla_up_proj(lp, cfg, attn.dtype)
        return jnp.einsum("bthr,rhv->bthv", attn[..., :cfg.kv_lora_rank],
                          w_uv)


def _input_gate(x: jax.Array, lp: Params, cfg: ModelConfig,
                leaf: str) -> jax.Array:
    """sigmoid(x_normed W) in float32, W the leaf `leaf` [D, n]: the gate
    a layer's back half puts on its attention's output, from the block's
    input (the attention norm recomputed: one pass over x)."""
    xn = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps, cfg.norm_plus_one)
    return jax.nn.sigmoid(jnp.einsum(
        "btd,de->bte", xn, wmat(lp[leaf], xn.dtype)).astype(jnp.float32))


def _mla_gate(attn: jax.Array, x: jax.Array, lp: Params,
              cfg: ModelConfig) -> jax.Array:
    """The head-wise output gate: attn [B, T, H, hd] times
    sigmoid(x_normed Wgate)_h."""
    with jax.named_scope("attention.mla.gate"):
        gate = _input_gate(x, lp, cfg, "w_attn_gate")
        return (attn.astype(jnp.float32) * gate[..., None]
                ).astype(attn.dtype)


def _out_gate(attn: jax.Array, x: jax.Array, lp: Params,
              cfg: ModelConfig) -> jax.Array:
    """Softmax attention's element-wise output gate (`attn_out_gate`):
    attn [B, T, H * hd] times sigmoid(x_normed Wg), before `wo`."""
    with jax.named_scope("attention.out_gate"):
        return (attn.astype(jnp.float32)
                * _input_gate(x, lp, cfg, "w_out_gate")).astype(attn.dtype)


def _kda_front(x: jax.Array, lp: Params, cfg: ModelConfig):
    """A linear layer's token-wise front half: x [B, T, D] -> (pre [B, T,
    3 H d] the q | k | v projections BEFORE their convolution, g [B, T,
    H, d] float32 the per-channel log decay in (lower bound, 0), beta
    [B, T, H] float32). The convolution needs a row's neighbours and the
    state update its slot: both are a ROW's (`kda_mix` on the grid,
    `kda_mix_rows` over a split step's rows)."""
    b, t = x.shape[:2]
    h, d = cfg.num_heads, cfg.linear_head_dim
    f32 = jnp.float32
    with jax.named_scope("norm.attn"):
        xn = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps,
                      cfg.norm_plus_one)
    with jax.named_scope("linattn.in_proj"):
        pre = jnp.einsum("btd,de->bte", xn, wmat(lp["kda_wqkv"], xn.dtype))
    with jax.named_scope("linattn.gate"):
        f = jnp.einsum("btd,de->bte", xn, wmat(lp["kda_wf"], xn.dtype)
                       ).astype(f32) + lp["kda_dt_bias"].astype(f32)
        g = cfg.linear_gate_lower_bound * jax.nn.sigmoid(
            jnp.exp(lp["kda_a_log"].astype(f32))[:, None]
            * f.reshape(b, t, h, d))
        beta = jax.nn.sigmoid(jnp.einsum(
            "btd,dh->bth", xn, wmat(lp["kda_wb"], xn.dtype)).astype(f32))
    return pre, g, beta


def _kda_qkv(y: jax.Array, cfg: ModelConfig):
    """The convolution's output [..., 3 H d] float32 -> q, k, v [..., H,
    d]: SiLU, the split, q and k L2-normalised a head, q scaled."""
    h, d = cfg.num_heads, cfg.linear_head_dim
    y = jax.nn.silu(y)
    q, k, v = (a.reshape(a.shape[:-1] + (h, d))
               for a in jnp.split(y, 3, axis=-1))
    return l2_normalize(q) * d ** -0.5, l2_normalize(k), v


# a step of more rows than this is split by what each row holds
# (`kda_mix_splits`, `kda_mix_rows`); up to it, every row takes the
# chunkwise form on the grid (`kda_mix`)
KDA_CHUNK_ROWS = 8
# how many of a split step's chunk rows `kda_mix_rows` takes through the
# chunkwise form at a time: the group's size, not a limit on the rows.
# Chosen on the chip (PERF.md section 6, PR 37)
KDA_GROUP_ROWS = 4


def _slot_index(slots: jax.Array, n_slots: int) -> jax.Array:
    """Row -> state slot, a row without one (-1) sent out of range: its
    read clips, its write drops."""
    return jnp.where(slots < 0, n_slots, slots)


def kda_mix_splits(rows: int, tq: int) -> bool:
    """Whether a [rows, tq] step's linear layers split its rows by what
    each holds (`kda_mix_rows`): the program traces it, the engine's
    counters read it off the plan."""
    return tq > 1 and rows > KDA_CHUNK_ROWS


def kda_mix(state: tuple, lk, slots: jax.Array, lp: Params,
            cfg: ModelConfig, pre, g, beta, valid, fresh):
    """A linear layer's state update for a [B, T] step of at most
    KDA_CHUNK_ROWS rows, on the grid, between `_kda_front` and
    `_kda_out`: the causal convolution over each row's tokens (continued
    from the slot's tail), then the delta rule from the slot's state,
    every row through `kda_chunk`. state: (kda_s [Lk, slots + 1, H, d,
    d], kda_conv [Lk, slots + 1, K - 1, 3 H d]), `lk` this layer's index
    in them; valid [B, T]: real tokens, a prefix of each row; fresh [B]:
    the row starts its sequence (position 0), so it starts from zeros
    whatever the slot held: a reused slot needs no clearing. Each touched
    slot's state is read once and written once; a row without a slot,
    and a row of padding, write nothing. It is also the DEFINITION that
    the tests hold `kda_mix_rows` to, at any number of rows. Returns
    (state, o [B, T, H, d] float32)."""
    kda_s, kda_conv = state
    n_slots = kda_s.shape[1]
    at = _slot_index(slots, n_slots)
    n_valid = jnp.sum(valid, axis=1).astype(jnp.int32)
    keep = ~fresh
    with jax.named_scope("linattn.conv"):
        tail = kda_conv.at[lk, at].get(mode="clip")
        tail = jnp.where(keep[:, None, None], tail, 0)
        y, tail = conv_with_tail(pre, tail, lp["kda_conv_w"], n_valid)
        q, k, v = _kda_qkv(y, cfg)
        kda_conv = kda_conv.at[lk, at].set(tail.astype(kda_conv.dtype),
                                           mode="drop")
    with jax.named_scope("linattn.chunk"):
        m = valid[:, :, None, None]
        q, k, v, g = (jnp.where(m, a, 0.0) for a in (q, k, v, g))
        beta = jnp.where(valid[:, :, None], beta, 0.0)
        s0 = kda_s.at[lk, at].get(mode="clip")
        s0 = jnp.where(keep[:, None, None, None], s0, 0.0)
        o, s1 = kda_chunk(q, k, v, g, beta, s0)
        kda_s = kda_s.at[lk, at].set(s1, mode="drop")
    return (kda_s, kda_conv), o


def _chunk_group(rows: StepRows, j, group: int, long_at, n_slots: int,
                 valid, keep):
    """Group `j` of a split step's chunk rows, `group` rows of
    `rows.order`: (at [group] their slots, out of range for a row that is
    dead here; live [group]; valid_g [group, T] their real tokens; keep_g
    [group] not fresh; cells [group, T] their token rows). Past the last
    row (the last group's fill), and a row of one token or none: read
    clipped, every write dropped."""
    b, tq = valid.shape
    r = rows.order.at[j * group + jnp.arange(group)].get(
        mode="fill", fill_value=b)
    at = long_at.at[r].get(mode="fill", fill_value=-1)
    live = at >= 0
    at = jnp.where(live, at, n_slots)
    valid_g = valid.at[r].get(mode="clip") & live[:, None]
    keep_g = keep.at[r].get(mode="clip")
    cells = rows.start.at[r].get(mode="clip")[:, None] \
        + jnp.arange(tq, dtype=jnp.int32)[None, :]
    return at, live, valid_g, keep_g, cells


def kda_mix_rows(state: tuple, lk, slots: jax.Array, lp: Params,
                 cfg: ModelConfig, x, rows: StepRows, valid, fresh,
                 group: int = KDA_GROUP_ROWS):
    """A linear layer from `_kda_front` to the input of `_kda_out` for a
    step that `kda_mix_splits`, over the step's ROWS by what each holds,
    never over its [B, T] grid. x [B * T, D]: the step's token rows in
    either layout (`step_rows`); state: (kda_s, kda_conv, o [B * T, H,
    d] float32), `o` a scratch the layers share: each real token's row
    is overwritten, no other row is read.

    A row of ONE token (a decode row, a one-token chunk): its token is
    token row `start`; the front half over [B, D], then what a decode
    window's step does (`kda_decode`: the convolution against its
    slot's tail, one tap window a row, `_kda_qkv` over [B, 3 H d], the
    state updated where it rests).
    A row of more (a chunk row): `group` of them at a time, for as many
    groups as the step holds; a group's tokens are T-long runs of token
    rows from `start` on, masked by `valid`, and the front half, the
    convolution, the masks and `kda_chunk` run over [group, T, ...]
    alone; each row's state and tail are gathered and scattered once.
    Each kind is dead to the other; `fresh`, a row without a slot and a
    row of padding as in `kda_mix`, which is what the tests hold this
    to. Returns (state, with o's real rows written)."""
    kda_s, kda_conv, o = state
    tq = valid.shape[1]
    n, n_slots = x.shape[0], kda_s.shape[1]
    keep = ~fresh
    one = rows.n_valid == 1
    w = lp["kda_conv_w"]
    pre, g, beta = _kda_front(
        x.at[rows.start].get(mode="clip")[:, None], lp, cfg)
    (kda_s, kda_conv), o1 = kda_decode(
        (kda_s, kda_conv), lk, slots, lp, cfg, pre[:, 0], g[:, 0],
        beta[:, 0], one, fresh)
    o = o.at[jnp.where(one, rows.start, n)].set(o1, mode="drop")
    long_at = jnp.where(rows.n_valid > 1, _slot_index(slots, n_slots), -1)

    def chunk_group(j, carry):
        o, kda_s, kda_conv = carry
        at, _, valid_g, keep_g, cells = _chunk_group(
            rows, j, group, long_at, n_slots, valid, keep)
        pre, g, beta = _kda_front(x.at[cells].get(mode="clip"), lp, cfg)
        with jax.named_scope("linattn.conv"):
            tail = kda_conv.at[lk, at].get(mode="clip")
            y, tail = conv_with_tail(
                pre, jnp.where(keep_g[:, None, None], tail, 0), w,
                jnp.sum(valid_g, axis=1).astype(jnp.int32))
            q, k, v = _kda_qkv(y, cfg)
            kda_conv = kda_conv.at[lk, at].set(
                tail.astype(kda_conv.dtype), mode="drop")
        with jax.named_scope("linattn.chunk"):
            m = valid_g[:, :, None, None]
            q, k, v, g = (jnp.where(m, a, 0.0) for a in (q, k, v, g))
            beta = jnp.where(valid_g[:, :, None], beta, 0.0)
            s0 = jnp.where(keep_g[:, None, None, None],
                           kda_s.at[lk, at].get(mode="clip"), 0.0)
            o_g, s1 = kda_chunk(q, k, v, g, beta, s0)
            kda_s = kda_s.at[lk, at].set(s1, mode="drop")
        o = o.at[jnp.where(valid_g, cells, n).reshape(-1)].set(
            o_g.reshape((group * tq,) + o_g.shape[2:]), mode="drop")
        return o, kda_s, kda_conv

    o, kda_s, kda_conv = jax.lax.fori_loop(
        0, -(-rows.n_long // group), chunk_group, (o, kda_s, kda_conv))
    return kda_s, kda_conv, o


def kda_decode(state: tuple, lk, slots: jax.Array, lp: Params,
               cfg: ModelConfig, pre, g, beta, valid, fresh=None):
    """`kda_mix` for one token a row (a decode step's rows, a mixed
    step's one-token rows), every row updated where its state rests
    (`kda_step_slots`: no [B, H, d, d] copy of the rows' states exists).
    pre [B, 3 H d], g [B, H, d], beta [B, H]; valid [B]: rows that are
    live (a finished or padding row, or one another form takes, is a
    dead row to the kernel and writes nothing); fresh [B]: the row
    starts its sequence, from zeros whatever its slot held (a decode
    step has none). Returns (state, o [B, H, d] float32)."""
    kda_s, kda_conv = state
    slots = jnp.where(valid, slots, -1)
    at = _slot_index(slots, kda_conv.shape[1])
    with jax.named_scope("linattn.conv"):
        tail = kda_conv.at[lk, at].get(mode="clip")
        if fresh is not None:
            tail = jnp.where(fresh[:, None, None], 0, tail)
        y, tail = conv_one_token(pre, tail, lp["kda_conv_w"])
        q, k, v = _kda_qkv(y, cfg)
        kda_conv = kda_conv.at[lk, at].set(tail, mode="drop")
    with jax.named_scope("linattn.step"):
        o, kda_s = kda_step_slots(kda_s, lk, slots, q, k, v, g, beta, fresh)
    return (kda_s, kda_conv), o


def _kda_out(o: jax.Array, x: jax.Array, lp: Params,
             cfg: ModelConfig) -> jax.Array:
    """A linear layer's back half before `wo`: o [B, T, H, d] float32 ->
    RMSNorm over each head, times sigmoid(x_normed Wg) element-wise, in
    the model's dtype."""
    with jax.named_scope("linattn.out"):
        gate = _input_gate(x, lp, cfg, "kda_wg").reshape(o.shape)
        o = rms_norm(o, lp["kda_o_norm"].astype(jnp.float32),
                     cfg.rms_norm_eps)
        return (o * gate).astype(x.dtype)


# -- the parallel block's state-space mixer -----------------------------------

def mix_splits(cfg: ModelConfig, rows: int, tq: int) -> bool:
    """Whether a [rows, tq] step's state layers work over its rows by what
    each holds: the state-space mixer, the short convolution and power
    retention always (`ssm_mix_rows`, `conv_mix_rows`, `ret_mix_rows`:
    each its mixer's one form), the linear layers where
    `kda_mix_splits`."""
    return cfg.has_ssm or cfg.has_conv or cfg.has_retention \
        or kda_mix_splits(rows, tq)


def _ssm_front(x: jax.Array, lp: Params, cfg: ModelConfig):
    """The mixer's token-wise front half: x [B, T, D] -> (z [B, T, d_ssm]
    the gate, xbc [B, T, d_ssm + 2 G N] x | B | C BEFORE their
    convolution, dt [B, T, H] before its bias and softplus), in the
    model's dtype: the block's norm, the input multiplier, `ssm_in`, and
    the constant vector that multiplies the five segments z | x | B | C |
    dt by `ssm_multipliers`."""
    with jax.named_scope("norm.attn"):
        xn = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps,
                      cfg.norm_plus_one)
    ds, conv = cfg.mamba_d_ssm, cfg.mamba_conv_dim
    with jax.named_scope("block.parallel/ssm.in_proj"):
        p = jnp.einsum("btd,de->bte", _times(xn, cfg.ssm_in_multiplier),
                       wmat(lp["ssm_in"], xn.dtype))
        if any(m != 1.0 for m in cfg.ssm_multipliers):
            p = (p.astype(jnp.float32) * ssm_segments(cfg)).astype(p.dtype)
    return p[..., :ds], p[..., ds:ds + conv], p[..., ds + conv:]


def _ssm_inputs(y: jax.Array, dt: jax.Array, lp: Params, cfg: ModelConfig):
    """The convolution's output y [..., d_ssm + 2 G N] float32 (bias
    added) and the raw dt [..., H] -> what the scan takes, float32: x
    [..., H, P], B, C [..., G, N] after the SiLU, dt = softplus(dt +
    dt_bias), A = -exp(A_log) [H], D [H]."""
    f32 = jnp.float32
    h, g, n = cfg.mamba_n_heads, cfg.mamba_n_groups, cfg.mamba_d_state
    ds = cfg.mamba_d_ssm
    y = jax.nn.silu(y)
    lead = y.shape[:-1]
    return (y[..., :ds].reshape(lead + (h, cfg.mamba_d_head)),
            y[..., ds:ds + g * n].reshape(lead + (g, n)),
            y[..., ds + g * n:].reshape(lead + (g, n)),
            jax.nn.softplus(dt.astype(f32) + lp["ssm_dt_bias"].astype(f32)),
            -jnp.exp(lp["ssm_a_log"].astype(f32)), lp["ssm_d"].astype(f32))


def _ssm_out(y: jax.Array, z: jax.Array, lp: Params,
             cfg: ModelConfig) -> jax.Array:
    """The mixer's back half before `ssm_out`: y [..., H, P] float32, z
    [..., d_ssm] the gate -> the gated grouped RMSNorm, the gate FIRST
    (`mamba_norm_before_gate` false): y * SiLU(z), the variance over each
    of the G groups of d_ssm / G, one weight of d_ssm; in z's dtype."""
    with jax.named_scope("block.parallel/ssm.norm_out"):
        f32 = jnp.float32
        y = y.reshape(z.shape).astype(f32) * jax.nn.silu(z.astype(f32))
        by_group = y.shape[:-1] + (cfg.mamba_n_groups, -1)
        y = y.reshape(by_group)
        y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                              + cfg.rms_norm_eps)
        return (y.reshape(z.shape) * lp["ssm_norm"].astype(f32)
                ).astype(z.dtype)


def ssm_decode(state: tuple, l, slots: jax.Array, lp: Params,
               cfg: ModelConfig, z, xbc, dt, valid, fresh=None):
    """The mixer for one token a row (a decode step's rows, a mixed
    step's one-token rows), between `_ssm_front` and `ssm_out`, every row
    updated where its state rests (`ssd_step_slots`). state: (ssm_s [L,
    slots + 1, H, P, N], ssm_conv [L, slots + 1, K - 1, d_ssm + 2 G N]),
    `l` this layer's index in them; z [B, d_ssm], xbc [B, d_ssm + 2 G N],
    dt [B, H]; valid [B]: rows that are live (a finished or padding row,
    or one another form takes, is a dead row to the kernel and writes
    nothing); fresh [B]: the row starts its sequence, from zeros whatever
    its slot held (a decode step has none). Returns (state, o [B, d_ssm]
    in the model's dtype, past the gated norm)."""
    ssm_s, ssm_conv = state
    slots = jnp.where(valid, slots, -1)
    at = _slot_index(slots, ssm_conv.shape[1])
    with jax.named_scope("block.parallel/ssm.conv"):
        tail = ssm_conv.at[l, at].get(mode="clip")
        if fresh is not None:
            tail = jnp.where(fresh[:, None, None], 0, tail)
        y, tail = conv_one_token(xbc, tail, lp["ssm_conv_w"],
                                 lp["ssm_conv_b"])
        x, b, c, dt, a, d = _ssm_inputs(y, dt, lp, cfg)
        ssm_conv = ssm_conv.at[l, at].set(tail, mode="drop")
    with jax.named_scope("block.parallel/ssm.step"):
        y, ssm_s = ssd_step_slots(ssm_s, l, slots, x, dt, a, b, c, d, fresh)
    return (ssm_s, ssm_conv), _ssm_out(y, z, lp, cfg)


def ssm_mix_rows(state: tuple, l, slots: jax.Array, lp: Params,
                 cfg: ModelConfig, x, rows: StepRows, valid, fresh,
                 group: int = KDA_GROUP_ROWS):
    """The mixer from the block's norm to the input of `ssm_out` for a
    [B, T] step, over the step's ROWS by what each holds, never over its
    grid: `kda_mix_rows` for the state-space scan, and the mixer's ONE
    form for a step (at any number of rows). x [B * T, D]: the step's
    token rows in either layout (`step_rows`); state: (ssm_s, ssm_conv, o
    [B * T, d_ssm] in the model's dtype), `o` a scratch the layers
    share: each real token's row is overwritten, no other row is read.

    A row of ONE token (a decode row, a one-token chunk): its token is
    token row `start`; the front half over [B, D], then what a decode
    window's step does (`ssm_decode`). A row of more (a chunk row):
    `group` of them at a time, for as many groups as the step holds; the
    front half, the convolution (continued from the slot's tail) and
    `ssd_chunk` run over [group, T, ...] alone, padding at dt = 0 (an
    exact no-op on the state); each row's state and tail are gathered
    and scattered once. Each kind is dead to the other. `fresh` [B]: the
    row starts its sequence (position 0), so it starts from zeros
    whatever the slot held: a reused slot needs no clearing. A row
    without a slot, and a row of padding, write nothing. Returns (state,
    with o's real rows written)."""
    ssm_s, ssm_conv, o = state
    tq = valid.shape[1]
    n, n_slots = x.shape[0], ssm_s.shape[1]
    keep = ~fresh
    one = rows.n_valid == 1
    z, xbc, dt = _ssm_front(
        x.at[rows.start].get(mode="clip")[:, None], lp, cfg)
    (ssm_s, ssm_conv), o1 = ssm_decode(
        (ssm_s, ssm_conv), l, slots, lp, cfg, z[:, 0], xbc[:, 0], dt[:, 0],
        one, fresh)
    o = o.at[jnp.where(one, rows.start, n)].set(o1, mode="drop")
    long_at = jnp.where(rows.n_valid > 1, _slot_index(slots, n_slots), -1)

    def chunk_group(j, carry):
        o, ssm_s, ssm_conv = carry
        at, live, valid_g, keep_g, cells = _chunk_group(
            rows, j, group, long_at, n_slots, valid, keep)
        z, xbc, dt = _ssm_front(x.at[cells].get(mode="clip"), lp, cfg)
        with jax.named_scope("block.parallel/ssm.conv"):
            tail = ssm_conv.at[l, at].get(mode="clip")
            y, tail = conv_with_tail(
                xbc, jnp.where(keep_g[:, None, None], tail, 0),
                lp["ssm_conv_w"],
                jnp.sum(valid_g, axis=1).astype(jnp.int32),
                lp["ssm_conv_b"])
            xs, bs, cs, dt, a, d = _ssm_inputs(y, dt, lp, cfg)
            ssm_conv = ssm_conv.at[l, at].set(
                tail.astype(ssm_conv.dtype), mode="drop")
        with jax.named_scope("block.parallel/ssm.chunk"):
            # a row's state by a slice at (layer, slot), never a gather
            # over the leaf: XLA:TPU splits a gather's 256-wide rows into
            # two 128-lane halves by slicing the WHOLE leaf first (1.7
            # GB copied a layer and step, a third of a mixed step: PERF.md
            # section 6, PR 45). A dead row's slot is out of range: the
            # slice clamps to the leaf's last, scratch slot, whose
            # content a dead row (dt = 0) hands back as it found it
            where = [(l, at[i], 0, 0, 0) for i in range(group)]
            s0 = jnp.concatenate([jax.lax.dynamic_slice(
                ssm_s, w, (1, 1) + ssm_s.shape[2:])[0] for w in where])
            s0 = jnp.where((keep_g | ~live)[:, None, None, None], s0, 0.0)
            y_g, s1 = ssd_chunk(
                xs, jnp.where(valid_g[:, :, None], dt, 0.0), a, bs, cs, d,
                s0)
            for i, w in enumerate(where):
                ssm_s = jax.lax.dynamic_update_slice(
                    ssm_s, s1[i][None, None], w)
        o_g = _ssm_out(y_g, z, lp, cfg)
        o = o.at[jnp.where(valid_g, cells, n).reshape(-1)].set(
            o_g.reshape((group * tq,) + o_g.shape[2:]), mode="drop")
        return o, ssm_s, ssm_conv

    o, ssm_s, ssm_conv = jax.lax.fori_loop(
        0, -(-rows.n_long // group), chunk_group, (o, ssm_s, ssm_conv))
    return ssm_s, ssm_conv, o


# -- the gated short convolution ------------------------------------------------

def _conv_front(x: jax.Array, lp: Params, cfg: ModelConfig):
    """A conv layer's token-wise front half: x [..., D] -> (g [..., D] =
    B * u, the convolution's input, whose last rows are the layer's
    state; c [..., D] the gate on its output), in the model's dtype: the
    block's norm, `conv_in` [D, B | C | u]."""
    d = cfg.hidden_size
    with jax.named_scope("norm.attn"):
        xn = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps,
                      cfg.norm_plus_one)
    with jax.named_scope("shortconv.in_proj"):
        p = jnp.einsum("...d,de->...e", xn, wmat(lp["conv_in"], xn.dtype))
    with jax.named_scope("shortconv.gate"):
        return p[..., :d] * p[..., 2 * d:], p[..., d:2 * d]


def _conv_out(y: jax.Array, c: jax.Array) -> jax.Array:
    """The convolution's output y [..., D] float32 times its gate c, in
    the gate's dtype: what `wo` (the layer's `out_proj`) takes."""
    with jax.named_scope("shortconv.gate"):
        return (c.astype(jnp.float32) * y).astype(c.dtype)


def conv_decode(state: tuple, l, slots: jax.Array, lp: Params,
                g, c, valid, fresh=None):
    """The mixer for one token a row (a decode step's rows, a mixed
    step's one-token rows), between `_conv_front` and `wo`. state:
    (conv_tail [Lc, slots + 1, K - 1, D],), `l` this layer's index in it;
    g, c [B, D]; valid [B]: rows that are live (a finished or padding
    row, or one another form takes, reads clipped and writes nothing);
    fresh [B]: the row starts its sequence, from zeros whatever its slot
    held (a decode step has none). Returns (state, o [B, D])."""
    conv_tail, = state
    at = _slot_index(jnp.where(valid, slots, -1), conv_tail.shape[1])
    with jax.named_scope("shortconv.taps"):
        tail = conv_tail.at[l, at].get(mode="clip")
        if fresh is not None:
            tail = jnp.where(fresh[:, None, None], 0, tail)
        y, tail = conv_one_token(g, tail, lp["conv_w"])
        conv_tail = conv_tail.at[l, at].set(tail, mode="drop")
    return (conv_tail,), _conv_out(y, c)


def conv_mix_rows(state: tuple, l, slots: jax.Array, lp: Params,
                  cfg: ModelConfig, x, rows: StepRows, valid, fresh,
                  group: int = KDA_GROUP_ROWS):
    """A conv layer from the block's norm to the input of `wo` for a
    [B, T] step, over the step's ROWS by what each holds, never over its
    grid: `ssm_mix_rows` for a state that is a tail alone, and the
    mixer's ONE form for a step. x [B * T, D]: the step's token rows in
    either layout (`step_rows`); state: (conv_tail, o [B * T, D] in the
    model's dtype), `o` a scratch the layers share: each real token's
    row is overwritten, no other row is read.

    A row of ONE token: its token is token row `start`; the front half
    over [B, D], then what a decode window's step does (`conv_decode`).
    A row of more (a chunk row): `group` of them at a time; the front
    half and the convolution, continued from the slot's tail
    (`conv_with_tail`: a row's `n_valid` real tokens move its tail, so a
    chunk of one real token keeps the older row), run over [group, T,
    ...] alone. Each kind is dead to the other. `fresh` [B]: the row
    starts its sequence (position 0), from zeros whatever the slot held.
    A row without a slot, and a row of padding, write nothing. Returns
    (state, with o's real rows written)."""
    conv_tail, o = state
    tq = valid.shape[1]
    n, n_slots = x.shape[0], conv_tail.shape[1]
    keep = ~fresh
    one = rows.n_valid == 1
    g, c = _conv_front(x.at[rows.start].get(mode="clip"), lp, cfg)
    (conv_tail,), o1 = conv_decode((conv_tail,), l, slots, lp, g, c, one,
                                   fresh)
    o = o.at[jnp.where(one, rows.start, n)].set(o1, mode="drop")
    long_at = jnp.where(rows.n_valid > 1, _slot_index(slots, n_slots), -1)

    def chunk_group(j, carry):
        o, conv_tail = carry
        at, _, valid_g, keep_g, cells = _chunk_group(
            rows, j, group, long_at, n_slots, valid, keep)
        g, c = _conv_front(x.at[cells].get(mode="clip"), lp, cfg)
        with jax.named_scope("shortconv.taps"):
            tail = conv_tail.at[l, at].get(mode="clip")
            y, tail = conv_with_tail(
                g, jnp.where(keep_g[:, None, None], tail, 0), lp["conv_w"],
                jnp.sum(valid_g, axis=1).astype(jnp.int32))
            conv_tail = conv_tail.at[l, at].set(
                tail.astype(conv_tail.dtype), mode="drop")
        o = o.at[jnp.where(valid_g, cells, n).reshape(-1)].set(
            _conv_out(y, c).reshape(group * tq, -1), mode="drop")
        return o, conv_tail

    o, conv_tail = jax.lax.fori_loop(
        0, -(-rows.n_long // group), chunk_group, (o, conv_tail))
    return conv_tail, o


# -- power retention ----------------------------------------------------------

# how many of a step's chunk rows `ret_mix_rows` takes through the
# chunkwise form at a time. One: a row's state is 34 MB a layer at the
# served size, and the group's states are the form's one copy of them
RET_GROUP_ROWS = 1


def _ret_front(x: jax.Array, lp: Params, cfg: ModelConfig,
               positions: jax.Array):
    """A power-retention layer's token-wise front half: x [B, T, D],
    positions [B, T] -> (q [B, T, H, hd], k, v [B, T, Hkv, hd], log_g [B,
    T, Hkv]), float32: the block's norm, the softmax model's own q | k | v
    projections, head norms and RoPE (`qkv_proj`, `rope_table`), and the
    gate log g = log_sigmoid(xn W_g + b_g), in (-inf, 0), a key-value
    head."""
    b, t = x.shape[:2]
    f32 = jnp.float32
    with jax.named_scope("norm.attn"):
        xn = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps,
                      cfg.norm_plus_one)
    q, k, v = qkv_proj(xn, lp, cfg)
    rope = rope_table(cfg, "ret")

    def heads_of(a, n):
        a = a.reshape(b, t, n, cfg.head_dim)
        return a if rope is None else apply_rope(a, positions, *rope)
    q, k = heads_of(q, cfg.num_heads), heads_of(k, cfg.num_kv_heads)
    with jax.named_scope("retention.front"):
        log_g = jax.nn.log_sigmoid(jnp.einsum(
            "btd,dc->btc", xn, wmat(lp["ret_wg"], xn.dtype)).astype(f32)
            + lp["ret_bg"].astype(f32))
        return q.astype(f32), k.astype(f32), \
            v.reshape(b, t, cfg.num_kv_heads, cfg.head_dim).astype(f32), log_g


def ret_decode(state: tuple, l, slots: jax.Array, q, k, v, log_g, valid,
               fresh=None):
    """The mixer for one token a row (a decode step's rows, a mixed
    step's one-token rows), between `_ret_front` and `wo`, every row
    updated where its state rests (`retention_step_slots`: no [B, Hkv,
    hd, F] copy of the rows' states exists). state: (ret_s [L, slots + 1,
    Hkv, hd, F], ret_z [L, slots + 1, Hkv, F]), `l` this layer's index in
    them; q [B, H, hd], k, v [B, Hkv, hd], log_g [B, Hkv]; valid [B]:
    rows that are live (a finished or padding row, or one another form
    takes, is a dead row to the kernel and writes nothing); fresh [B]:
    the row starts its sequence, from zeros whatever its slot held (a
    decode step has none). Returns (state, o [B, H, hd] float32)."""
    ret_s, ret_z = state
    with jax.named_scope("retention.step"):
        o, ret_s, ret_z = retention_step_slots(
            ret_s, ret_z, l, jnp.where(valid, slots, -1), q, k, v, log_g,
            fresh)
    return (ret_s, ret_z), o


def ret_mix_rows(state: tuple, l, slots: jax.Array, lp: Params,
                 cfg: ModelConfig, x, positions, rows: StepRows, valid,
                 fresh, group: int = RET_GROUP_ROWS):
    """A power-retention layer from the block's norm to the input of `wo`
    for a [B, T] step, over the step's ROWS by what each holds, never
    over its grid: `ssm_mix_rows` for a state that is the layer's whole
    context, and the mixer's ONE form for a step. x [B * T, D]: the
    step's token rows in either layout (`step_rows`); positions [B, T]:
    the grid's (a row's real tokens are a prefix of it); state: (ret_s,
    ret_z, o [B * T, H hd] in the model's dtype), `o` a scratch the
    layers share: each real token's row is overwritten, no other row is
    read.

    A row of ONE token: its token is token row `start`, at its row's
    first position; the front half over [B, D], then what a decode
    window's step does (`ret_decode`). A row of more (a chunk row):
    `group` of them at a time; the front half and `retention_chunk` run
    over [group, T, ...] alone, padding at log g = 0 and k = v = 0 (an
    exact no-op on the state); each row's state is sliced out of the
    leaves at (layer, slot) and written back there, never gathered over
    the leaf (`ssm_mix_rows` says why). Each kind is dead to the other.
    `fresh` [B]: the row starts its sequence (position 0), from zeros
    whatever the slot held. A row without a slot, and a row of padding,
    write nothing. Returns (state, with o's real rows written)."""
    ret_s, ret_z, o = state
    tq = valid.shape[1]
    n, n_slots = x.shape[0], ret_s.shape[1]
    keep = ~fresh
    one = rows.n_valid == 1
    q, k, v, log_g = _ret_front(
        x.at[rows.start].get(mode="clip")[:, None], lp, cfg,
        positions[:, :1])
    (ret_s, ret_z), o1 = ret_decode(
        (ret_s, ret_z), l, slots, q[:, 0], k[:, 0], v[:, 0], log_g[:, 0],
        one, fresh)
    o = o.at[jnp.where(one, rows.start, n)].set(
        o1.reshape(o1.shape[0], -1).astype(o.dtype), mode="drop")
    long_at = jnp.where(rows.n_valid > 1, _slot_index(slots, n_slots), -1)

    def chunk_group(j, carry):
        o, ret_s, ret_z = carry
        at, live, valid_g, keep_g, cells = _chunk_group(
            rows, j, group, long_at, n_slots, valid, keep)
        r = rows.order.at[j * group + jnp.arange(group)].get(
            mode="fill", fill_value=0)
        q, k, v, log_g = _ret_front(
            x.at[cells].get(mode="clip"), lp, cfg,
            positions.at[r].get(mode="clip"))
        with jax.named_scope("retention.chunk"):
            m = valid_g[:, :, None, None]
            k, v = jnp.where(m, k, 0.0), jnp.where(m, v, 0.0)
            log_g = jnp.where(valid_g[:, :, None], log_g, 0.0)
            # a dead row's slot is out of range: the slice clamps to the
            # leaves' last, scratch slot, whose content a dead row (an
            # identity update) hands back as it found it
            where = [(l, at[i], 0, 0) for i in range(group)]
            s0 = jnp.concatenate([jax.lax.dynamic_slice(
                ret_s, w + (0,), (1, 1) + ret_s.shape[2:])[0]
                for w in where])
            z0 = jnp.concatenate([jax.lax.dynamic_slice(
                ret_z, w, (1, 1) + ret_z.shape[2:])[0] for w in where])
            start = keep_g | ~live
            o_g, s1, z1 = retention_chunk(
                q, k, v, log_g,
                jnp.where(start[:, None, None, None], s0, 0.0),
                jnp.where(start[:, None, None], z0, 0.0))
            for i, w in enumerate(where):
                ret_s = jax.lax.dynamic_update_slice(
                    ret_s, s1[i][None, None], w + (0,))
                ret_z = jax.lax.dynamic_update_slice(
                    ret_z, z1[i][None, None], w)
        o = o.at[jnp.where(valid_g, cells, n).reshape(-1)].set(
            o_g.reshape(group * tq, -1).astype(o.dtype), mode="drop")
        return o, ret_s, ret_z

    o, ret_s, ret_z = jax.lax.fori_loop(
        0, -(-rows.n_long // group), chunk_group, (o, ret_s, ret_z))
    return ret_s, ret_z, o


def _layer_scan(*args, **kwargs):
    """`jax.lax.scan` over a model's layers (or its periods): what the
    loop itself does with its stacked operands (a layer's slice of every
    xs leaf read, of every ys leaf written) counts to `layers.stack`; the
    body's ops name their own scopes, which stand inside it. A scope of
    its own a call: a period's scan holds its parts' scans, and ONE
    `named_scope` object entered inside itself hands the outer one the
    inner one's name stack back."""
    with jax.named_scope("layers.stack"):
        return jax.lax.scan(*args, **kwargs)

# the scope of a layer's output projection `wo`, by the layer's kind
_WO_SCOPE = {"conv": "shortconv.out_proj", "kda": "linattn.wo",
             "ret": "retention.out"}


def layer_back(x: jax.Array, attn: jax.Array, lp: Params, cfg: ModelConfig,
               mlp, reduce=None, kind: str = "", ssm=None):

    """(x [B, T, D], attn [B, T, ...heads]) -> (next x, the MLP's stats):
    (softmax attention over pool rows of several heads: each head's own
    lanes first, `_head_values`,) output projection, residual, MLP norm,
    `mlp(xn, lp)` -> (out, stats),
    residual, with Gemma's post-norms where the configuration has them.
    `reduce` sums a partial product over the caller's manual "tp" axis; it
    comes BEFORE the post-norm, which is nonlinear and must see the whole
    output, not a shard's partial sum. Latent attention hands over the
    weighted latents; their value projection (`_mla_out`) comes first.
    Softmax attention's output gate (`_out_gate`) sits before `wo`.
    `ssm` (a parallel block, `kind` "par"): the mixer's output past its
    gated norm [B, T, d_ssm]; its projection `ssm_out` is ADDED to
    attention's, each times its multiplier, before the one residual. A
    conv layer (`kind` "conv") hands over its gated convolution [B, T,
    D], and `wo` is its `out_proj`; a power-retention layer (`kind`
    "ret") its quotient [B, T, H hd], and `wo` is its `o_proj`."""
    b, t = x.shape[:2]
    if kind == "kda":
        attn = _kda_out(attn, x, lp, cfg)
    elif kind in ("conv", "ret"):
        pass
    elif cfg.is_mla:
        attn = _mla_out(attn.reshape(b, t, cfg.num_heads, -1), lp, cfg)
        if cfg.mla_gate:
            attn = _mla_gate(attn, x, lp, cfg)
    else:
        if cfg.kv_row_heads > 1:
            attn = _head_values(
                attn.reshape(b, t, -1, cfg.kv_row_heads * cfg.head_dim), cfg)
        if cfg.attn_out_gate:
            attn = _out_gate(attn.reshape(b, t, -1), x, lp, cfg)
    with jax.named_scope(_WO_SCOPE.get(kind, "attention.wo")):
        out = jnp.einsum("bte,ed->btd", attn.reshape(b, t, -1),
                         wmat(lp["wo"], x.dtype))
    if kind == "par":
        with jax.named_scope("block.parallel/ssm.out_proj"):
            out = _times(out, cfg.attention_out_multiplier) + _times(
                jnp.einsum("bte,ed->btd", ssm,
                           wmat(lp["ssm_out"], x.dtype)),
                cfg.ssm_out_multiplier)
    if reduce is not None:
        out = reduce(out)
    if cfg.post_norms:
        with jax.named_scope("norm.post"):
            out = rms_norm(out, lp["post_attn_norm"], cfg.rms_norm_eps,
                           cfg.norm_plus_one)
    x = x + out
    with jax.named_scope("norm.mlp"):
        xn = rms_norm(x, lp["mlp_norm"], cfg.rms_norm_eps,
                      cfg.norm_plus_one)
    out, stats = mlp(xn, lp)
    if reduce is not None:
        out = reduce(out)
    if cfg.post_norms:
        with jax.named_scope("norm.post"):
            out = rms_norm(out, lp["post_mlp_norm"], cfg.rms_norm_eps,
                           cfg.norm_plus_one)
    return x + out, stats


def lm_head(params: Params, cfg: ModelConfig) -> jax.Array:
    """The [D, V] head operand: the embedding transposed when tied (may be
    a quantized leaf)."""
    return (params["embed"].T if cfg.tie_word_embeddings
            else params["lm_head"])


@jax.named_scope("head")
def lm_logits(x: jax.Array, final_norm: jax.Array, head: jax.Array,
              cfg: ModelConfig) -> jax.Array:
    """x [..., D] -> float32 logits [..., V]: final norm, head matmul,
    final soft-cap. Takes the two arrays, not `params`: a pp stage holds
    stage-local ones."""
    x = rms_norm(x, final_norm, cfg.rms_norm_eps, cfg.norm_plus_one)
    if cfg.lm_head_multiplier != 1.0:
        # the multiplier meets the products' float32 sums, not their
        # rounding to the model's dtype: one rounding fewer (an 8-bit
        # mantissa on a logit of 4 is a step of 1/32 nat) for 33 MB more
        # written a step at [64, 261120]
        logits = jnp.einsum("...d,dv->...v", x, wmat(head, x.dtype),
                            preferred_element_type=jnp.float32)
        return _softcap(logits * cfg.lm_head_multiplier, cfg.final_softcap)
    logits = jnp.einsum("...d,dv->...v", x, wmat(head, x.dtype))
    return _softcap(logits.astype(jnp.float32), cfg.final_softcap)


def decode_forward(
    params: Params,
    cfg: ModelConfig,
    tokens: jax.Array,        # [B] int32 — one token per sequence
    cache: Dict[str, jax.Array],
    page_table: jax.Array,    # [B, Pb]
    prefix_lens: jax.Array,   # [B] — valid kv BEFORE this token (0 = pad)
    positions: jax.Array,     # [B] — absolute position of this token
    valid: Optional[jax.Array] = None,  # [B] bool, real (non-pad) slots
    mesh=None,
    with_aux: bool = False,
    window: Optional[tuple] = None,  # split-KV window fast path, see below
    state: Optional[tuple] = None,   # (the state's leaves, slots [B])
    swa: Optional[tuple] = None,     # the window layers' split-KV view
) -> tuple:
    """Deferred-write decode step: the KV cache is READ-ONLY.

    `swa` (a model with a window pool, `cfg.window_pool`; only with
    `window`): (k_base, v_base [Lw, Hkv, B, Wb * ps, hd], k_win, v_win
    [Lw, Hkv, B, Nw, hd], base_lens [B]) — what `window` is to the full
    layers, gathered from each row's table in the WINDOW pool, whose
    first key sits at the row's `woff`; `base_lens` is in that table's
    coordinates (the valid kv at window start less `woff`), in which
    every mask of ops/attention.decode_attention_split holds as it is
    (`win_lens` is a difference and moves with neither). The window
    layers' new rows come back LAST, as (wk_new, wv_new [Lw, B, Hkv,
    hd]): k_new / v_new cover the layers of the full pool.

    `state` (a model with linear-attention layers, or with a state-space
    mixer beside its attention): the recurrent state's leaves and each
    row's slot in them. Unlike the cache it is WRITTEN here, a layer at a
    time (`kda_decode`, `ssm_decode`), and handed back as the last
    element of the result: the caller carries it to the next step. The
    new-row stacks k_new / v_new then cover the layers that HAVE a cache
    (a parallel block has both: its layer emits its rows AND carries the
    state).

    Returns (last_logits [B, V] f32, k_new [L, B, Hkv, hd],
    v_new [L, B, Hkv, hd], aux) — the caller scatters the new kv rows into
    the cache in ONE in-place update per step (engine._scatter_new_kv).
    Under latent attention k_new is the one cache row a token has,
    [L, B, 1, r + dr], and v_new None (so are `window`'s v_base and
    v_win): the row's leading columns are its values.
    The pool is read in place: a layer gathers the pages its rows name
    from the stacked leaves by (layer, page), or the kernel streams them;
    no [Hkv, P, ps, hd] slice of a layer's pool is ever formed. Rationale:
    threading cache slices through the layer scan's outputs made XLA copy
    the whole cache every step (~8 ms for the 1B flagship — the round-2
    decode gap; the same defect cost forward() a fifth of the device's
    time until PR 26, PERF.md section 6); attention instead adds the
    current token via an explicit self-term (ops/attention.
    decode_attention_deferred, ops/paged_attention.
    combine_self_attention), which is exact because decode is causal.

    `window`: window-decode fast path — (k_base, v_base [L, Hkv, B, Lb,
    hd], k_win, v_win [L, Hkv, B, Nw, hd], base_lens [B], win_lens [B]).
    The caller gathered each slot's VALID prefix pages once per decode
    window (base, read-only; Lb is bucketed to the true kv length, not
    the admission-time allocation) and accumulates each step's new kv
    rows into the small window buffer AFTER this call returns; attention
    merges base + window + current-token self-term in one joint softmax
    (ops/attention.decode_attention_split). Kills both the per-step page
    gather (~2.5 ms/step, 1B @ b8) and the full-allocation-width reads
    of the round-3 single-buffer design.
    """
    heads = (cfg.num_heads, cfg.num_kv_heads)
    kernel_mode = _decode_kernel_mode(cfg)
    kvq = bool(_validate_kv_quant(cfg.kv_quant))
    lw = cfg.layer_windows()
    layer_wnd = None if lw is None else jnp.asarray(lw, jnp.int32)
    with jax.named_scope("embed"):
        # ids validated at admission (_validate_prompt); decode feeds only
        # committed sampler outputs  # dynalint: disable-next-line=R1
        x = scale_embeds(jnp.take(params["embed"], tokens, axis=0),
                         cfg)[:, None]  # [B, 1, D]
    layer_ids = jnp.arange(cfg.num_layers, dtype=jnp.int32)
    moe_aux = cfg.is_moe and cfg.moe_impl == "dispatch"
    token_valid = valid[:, None] if (moe_aux and valid is not None) else None

    runs = layer_runs(cfg)
    whole = len(runs) == 1
    if window is not None:
        kb_all, vb_all, kw_all, vw_all, base_lens, win_lens = window
        win_leaves = (kb_all, vb_all, kw_all, vw_all)
    if cfg.window_pool and (window is None or swa is None):
        raise NotImplementedError(
            f"{cfg.name}: a decode step of a model with a window pool "
            f"reads its window layers from the split-KV view (`window` "
            f"and `swa`); the per-step gather and the kernel are not "
            f"planned over two page tables")
    swa_wnd = jnp.int32(cfg.sliding_window) if cfg.window_pool else None
    row_valid = valid if valid is not None else jnp.ones(tokens.shape, bool)

    @jax.named_scope("layers.body")
    def kda_step_layer(carry, xs, run, expert_stacks):
        """A linear layer: no cache row, a state update instead."""
        x, st = carry
        lp, lid = xs
        pre, g, beta = layer_front(x, lp, cfg, None, heads, "kda")
        st, o = kda_decode(st, run.store_index(lid), state[1],
                           lp, cfg, pre[:, 0], g[:, 0], beta[:, 0],
                           row_valid)
        x, drop_stats = layer_back(
            x, o[:, None], lp, cfg, lambda xn, lp: _mlp_block(
                xn, lp, cfg, mesh, token_valid, expert_stacks,
                lid - run.first, run.dense), kind="kda")
        return (x, st), drop_stats if moe_aux else None

    @jax.named_scope("layers.stack")
    def stack_layer(stack, lid, run):
        """A period's part: layer `lid` read from its kind's stack."""
        return jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
            a, lid - run.first, keepdims=False), stack)

    @jax.named_scope("layers.body")
    def conv_step_layer(carry, xs, run, expert_stacks, stack=None):
        """A conv layer: no cache row, its tail moved on a token."""
        x, st = carry
        lp, lid = xs[:2]
        if lp is None:
            lp = stack_layer(stack, lid, run)
        g, c = _conv_front(x, lp, cfg)
        st, o = conv_decode(st, run.store_index(lid), state[1], lp,
                            g[:, 0], c[:, 0], row_valid)
        x, drop_stats = layer_back(
            x, o[:, None], lp, cfg, lambda xn, lp: _mlp_block(
                xn, lp, cfg, mesh, token_valid, expert_stacks,
                lid - run.first, run.dense), kind="conv")
        return (x, st), drop_stats if moe_aux else None

    @jax.named_scope("layers.body")
    def ret_step_layer(carry, xs, run, expert_stacks):
        """A power-retention layer: no cache row, its state moved on a
        token where it rests."""
        x, st = carry
        lp, lid = xs
        q, k, v, log_g = _ret_front(x, lp, cfg, positions[:, None])
        st, o = ret_decode(st, run.store_index(lid), state[1], q[:, 0],
                           k[:, 0], v[:, 0], log_g[:, 0], row_valid)
        x, drop_stats = layer_back(
            x, o.reshape(o.shape[0], 1, -1).astype(x.dtype), lp, cfg,
            lambda xn, lp: _mlp_block(
                xn, lp, cfg, mesh, token_valid, expert_stacks,
                lid - run.first, run.dense), kind="ret")
        return (x, st), drop_stats if moe_aux else None

    @jax.named_scope("layers.body")
    def par_step_layer(carry, xs, run, expert_stacks):
        """A parallel block: the mixer's state update from the block's
        input, then `layer_step`, which adds its output to attention's."""
        x, st = carry
        lp, lid = xs[:2]
        z, xbc, dt = _ssm_front(x, lp, cfg)
        st, o = ssm_decode(st, run.store_index(lid), state[1], lp, cfg,
                           z[:, 0], xbc[:, 0], dt[:, 0], row_valid)
        x, out = layer_step(x, xs, run, expert_stacks, ssm=o[:, None])
        return (x, st), out

    @jax.named_scope("layers.body")
    def layer_step(x, xs, run, expert_stacks, stack=None, ssm=None):
        lp, lid, wnd, win = xs
        first, dense = run.first, run.dense
        if lp is None:
            lp = stack_layer(stack, lid, run)
        # this layer's index in the cache's (and the window's) layer axis
        cl = run.store_index(lid)
        q, k, v = layer_front(x, lp, cfg, positions[:, None], heads,
                              run.kind)
        k_new = k[:, 0]                                  # [B, Hkv, hd]
        v_new = None if v is None else v[:, 0]
        if run.kind == "swa":
            # a window layer: its own base and buffer, by its index among
            # the window layers, in its table's coordinates
            kb, vb, kw, vw = (jax.lax.dynamic_index_in_dim(
                a, cl, keepdims=False) for a in swa[:4])
            with jax.named_scope("attention.window"):
                attn = decode_attention_split(
                    q[:, 0], kb, vb, kw, vw, k_new, v_new, swa[4], win_lens,
                    softcap=cfg.attn_softcap, window=swa_wnd,
                    q_scale=attn_scale(cfg))
        elif window is not None:
            # one layer group: the window's leaves are the scan's xs. A
            # second group reads its layers from the whole leaves by
            # index, which is what a scan does with its xs; slicing them
            # by group would copy the gathered base every step
            kb, vb, kw, vw = win if whole else tuple(
                None if a is None else jax.lax.dynamic_index_in_dim(
                    a, cl, keepdims=False) for a in win_leaves)
            with _full_scope(cfg, run.kind):
                attn = decode_attention_split(
                    q[:, 0], kb, vb, kw, vw, k_new, v_new, base_lens,
                    win_lens, softcap=cfg.attn_softcap, window=wnd,
                    q_scale=attn_scale(cfg))
        elif kernel_mode is not None:
            interp = kernel_mode == "interpret"
            # int8 caches hand the kernels the raw pages plus the scale
            # stacks; dequantization folds into the in-kernel score/prob
            # rows (ops/paged_attention.py)  # dynalint: kv-codec
            scales = ((cache["k_scale"], cache["v_scale"]) if kvq
                      else (None, None))
            if mesh is not None and mesh.size > 1:
                acc, m, l = decode_paged_attention_prefix_sharded(
                    # dynalint: kv-codec — kernels dequantize in-read
                    q[:, 0], cache["k"], cache["v"], lid[None], page_table,
                    prefix_lens, mesh, interpret=interp,
                    k_scale=scales[0], v_scale=scales[1])
            else:
                acc, m, l = decode_paged_attention_prefix(
                    # dynalint: kv-codec — kernels dequantize in-read
                    q[:, 0], cache["k"], cache["v"], cl[None], page_table,
                    prefix_lens, interpret=interp,
                    k_scale=scales[0], v_scale=scales[1])
            attn = combine_self_attention(q[:, 0], k_new, v_new, acc, m, l)
        else:
            # gather fallback: the stacked leaves go in whole and only
            # this layer's pages come out (ops/attention.gather_values,
            # which dequantizes an int8 pool right after the gather)
            # dynalint: kv-codec — consumer gathers by (layer, page)
            scales = ((cache["k_scale"], cache["v_scale"]) if kvq
                      else (None, None))
            attn = decode_attention_deferred(
                # dynalint: kv-codec — consumer dequantizes at gather
                q[:, 0], cache["k"], cache.get("v"), k_new, v_new,
                page_table, prefix_lens, softcap=cfg.attn_softcap,
                window=wnd, q_scale=attn_scale(cfg),
                k_scale=scales[0], v_scale=scales[1], layer=cl)
        x, drop_stats = layer_back(
            x, attn, lp, cfg, lambda xn, lp: _mlp_block(
                xn, lp, cfg, mesh, token_valid, expert_stacks,
                lid if whole else lid - first, dense),
            **({} if ssm is None else dict(kind=run.kind, ssm=ssm)))
        return x, (k_new, v_new, drop_stats if moe_aux else None)

    k_news, v_news, drops = [], [], []
    wk_news, wv_news = [], []
    st = None if state is None else state[0]
    period = layer_period(cfg)
    # every run but a window-pool model's kind stacks: a scan a run, in
    # layer order (such a model's dense lead among them, BEFORE its loop)
    for run in runs if period is None else runs[:period.lead]:
        name, first, count, dense = run[:4]
        scan_layers, expert_stacks = (params[name], None) if dense \
            else split_expert_stacks(params[name], cfg, mesh)
        part = _group_rows(whole, first, count)
        if run.kind in ("kda", "conv", "ret"):
            with _lead_scope(run):
                (x, st), drop_g = _layer_scan(
                    functools.partial(
                        {"kda": kda_step_layer, "conv": conv_step_layer,
                         "ret": ret_step_layer}[run.kind], run=run,
                        expert_stacks=expert_stacks),
                    (x, st), (scan_layers, part(layer_ids)))
            drops.append(_sum_stats(drop_g))
            continue
        xs = (scan_layers, part(layer_ids),
              None if layer_wnd is None else part(layer_wnd),
              win_leaves if window is not None and whole else None)
        if run.kind == "par":
            (x, st), (k_g, v_g, drop_g) = _layer_scan(
                functools.partial(par_step_layer, run=run,
                                  expert_stacks=expert_stacks),
                (x, st), xs)
        else:
            with _lead_scope(run):
                x, (k_g, v_g, drop_g) = _layer_scan(
                    functools.partial(layer_step, run=run,
                                      expert_stacks=expert_stacks),
                    x, xs)
        (wk_news if run.kind == "swa" else k_news).append(k_g)
        (wv_news if run.kind == "swa" else v_news).append(v_g)
        drops.append(_sum_stats(drop_g))
    if period is not None:
        # ONE loop: a scan over periods, a scan a part inside it; the new
        # rows come out [periods, a kind's layers a period, ...] = the
        # kind's store order, behind the lead's. A conv part emits no
        # rows: its tails ride the loop's carry beside x
        stacks = {ri: split_expert_stacks(params[runs[ri].key], cfg, mesh)
                  for ri in range(period.lead, len(runs))}
        loop = [ri for ri in stacks if runs[ri].kind != "conv"]

        def period_step(carry, p):
            x, st = carry
            rows = {ri: ([], []) for ri in loop}
            stats = []
            for ri, per, offset, count in period.parts:
                run = runs[ri]
                lids = run.first + p * per + offset \
                    + jnp.arange(count, dtype=jnp.int32)
                if run.kind == "conv":
                    (x, st), drop_g = _layer_scan(
                        functools.partial(conv_step_layer, run=run,
                                          expert_stacks=stacks[ri][1],
                                          stack=stacks[ri][0]),
                        (x, st), (None, lids))
                    stats.append(_sum_stats(drop_g))
                    continue
                x, (k_g, v_g, drop_g) = _layer_scan(
                    functools.partial(layer_step, run=run,
                                      expert_stacks=stacks[ri][1],
                                      stack=stacks[ri][0]),
                    x, (None, lids, None, None))
                rows[ri][0].append(k_g)
                rows[ri][1].append(v_g)
                stats.append(_sum_stats(drop_g))
            return (x, st), (
                tuple(tuple(jnp.concatenate(g, axis=0) for g in kv)
                      for kv in rows.values()), _merge_stats(stats))

        (x, st), (rows, drop_p) = _layer_scan(
            period_step, (x, st),
            jnp.arange(period.count, dtype=jnp.int32))
        for ri, (k_g, v_g) in zip(loop, rows):
            k_g, v_g = (g.reshape((-1,) + g.shape[2:]) for g in (k_g, v_g))
            (wk_news if runs[ri].kind == "swa" else k_news).append(k_g)
            (wv_news if runs[ri].kind == "swa" else v_news).append(v_g)
        drops.append(_sum_stats(drop_p))
    k_news, v_news, wk_news, wv_news = (
        None if not g or g[0] is None else g[0] if len(g) == 1
        else jnp.concatenate(g, axis=0)
        for g in (k_news, v_news, wk_news, wv_news))
    aux = _merge_stats(drops)
    logits = lm_logits(x[:, 0], params["final_norm"], lm_head(params, cfg),
                       cfg)
    out = (logits, k_news, v_news) + ((aux,) if with_aux else ())
    if state is not None:
        out += (st,)
    return out + ((wk_news, wv_news),) if cfg.window_pool else out


def step_compaction(write_idx, sp_mesh=None) -> Optional[tuple]:
    """ops/attention.compact_step for a forward(last_idx=...) step, less
    what keeps the grid whatever its shape: ring-attention prefill
    shards the chunk axis itself. For the program and, from the same
    plan as NumPy, for the host's count of the rows it ran over."""
    if sp_mesh is not None and write_idx.shape[1] > 1:
        return None
    return compact_step(write_idx)


def step_attention_rows(cfg: ModelConfig, chunk: int) -> bool:
    """ops/attention.attention_rows_pay for `cfg`'s attention layers in a
    compact step of `chunk` columns: for the program and for the host's
    count of the steps that ran the row form. Never for a model none of
    whose layers attends to pages."""
    leaves = cfg.kv_cache_leaves()
    return bool(leaves) and attention_rows_pay(chunk, cfg.num_heads, sum(
        h * w for h, w in leaves.values()))


def forward(
    params: Params,
    cfg: ModelConfig,
    tokens: jax.Array,            # [B, Tq] int32
    cache: Dict[str, jax.Array],  # {"k","v"}: cfg.kv_cache_leaves()
    meta: AttnMetadata,
    input_embeds: Optional[jax.Array] = None,  # [B, Tq, D] overrides tokens
    embeds_mask: Optional[jax.Array] = None,   # [B, Tq] bool: mix per-token
    sp_mesh=None,  # Mesh with an "sp" axis: ring-attention prefill
    mesh=None,     # multi-device Mesh: shard_map the decode kernel over "tp"
    with_aux: bool = False,  # also return the summed ops/moe.py moe_stats
    last_idx: Optional[jax.Array] = None,  # [B] int32: the sampled positions
) -> tuple:
    """One paged forward step. Returns (logits [B, Tq, V], updated cache),
    plus an aux dict when with_aux=True (MoE capacity-drop counters summed
    over layers; empty for non-dispatch models).

    With `last_idx` (the engine's step: each row's last real token) the
    logits are [B, V]: the hidden state is taken at those positions
    BEFORE the head, and the token-wise layers run over the step's REAL
    tokens (`meta.write_idx` >= 0) where they are few. A [32, 16] mixed
    step holds 31 + 16 real tokens in 512 grid cells; its tokens are
    gathered into `width` flat rows (ops/attention.compact_step: 128
    there), and norm, projections, RoPE, `wo`, the MLP or experts and
    the residuals run over [1, width, D]. The KV-row write needs the
    pool: the new rows lead the token rows the write takes them from.
    Attention needs each row's pages. Where the grid's scores outweigh
    what is gathered for them (`step_attention_rows`: [8, 64] and
    [64, 64] steps, not [32, 16] ones) the pages are gathered once for
    all rows outside every `cond` (ops/attention.gather_kv) and
    attention runs inside the back half's `cond` (`attend_back`): over
    the flat rows it computes the step's real queries alone, every
    row's last beside the chunk rows' own
    (ops/attention.attention_rows), never the [B, Tq] grid of them.
    Elsewhere it runs over the grid outside the `cond`s: q is spread
    back to [B, Tq, ...] and the output gathered again. A linear layer
    of a step that
    `kda_mix_splits` needs neither: it runs whole over the step's rows
    (`kda_mix_rows`: a row's tokens are contiguous token rows in both
    layouts), outside every `cond`, and hands `back` its output as
    token rows in x's own layout. A step with more real tokens
    than `width` takes the same halves at the grid's full width, and
    attention over the grid (ops/attention.attend): each
    half of a layer (`layer_front`, `layer_back`) is one `jax.lax.cond`
    on the step's real-token count, inside the same program; a shape
    whose grid is no larger than `width` has no `cond`. Padding cells'
    results were never read: a row with no real token at `last_idx`
    reads flat row 0.
    What keeps the grid, each a static fact of the model or the mesh:
    ring-attention prefill (`sp_mesh`), and the CAPACITY-form expert
    block (`moe_dispatch_mlp` and its sharded form). Its capacity is
    per batch row (`ops/moe._capacity`), so one flat group would change
    which assignments drop and could drop a decode token that never
    drops today: that block alone still sees the [B, Tq] rows (its
    input spread back, its output gathered), the layer around it runs
    flat. The dropless form takes any rows with a `valid` mask.

    The pool stays where it is: the stacked leaves ride the layer scan's
    CARRY, never its xs / ys. A layer scatters the rows it produced into
    them at (layer, head, page, slot) (ops/attention.write_kv_rows) and
    then gathers the pages its rows name by (layer, page)
    (gather_pages): write, then read, the arithmetic of a per-layer
    write_kv_pages + paged_attention bit for bit, in place in the
    donated buffers. As xs / ys every layer sliced its whole pool out of
    the stack, re-laid it out for the scatter and back and copied it into
    the stacked output: eight moves of 134 MB a layer for a few hundred
    kilobytes of new rows (PERF.md section 6, PR 26). On an int8 pool the scale leaves travel
    the same way.

    When sp_mesh is given, prefill (Tq > 1) runs ring attention with the
    sequence sharded over "sp" (ops/ring_attention.py) instead of attending
    to the paged cache — the engine guarantees such prefills are whole-prompt
    single chunks with no cached prefix (engine.py asserts, prefix matching
    disabled), so chunk-internal attention IS the full attention.
    """
    b, tq = tokens.shape
    heads = (cfg.num_heads, cfg.num_kv_heads)
    kvq = bool(_validate_kv_quant(cfg.kv_quant))

    @jax.named_scope("embed")
    def embed(tokens, input_embeds, embeds_mask):
        if input_embeds is None:
            # admission validated the ids  # dynalint: disable-next-line=R1
            x = jnp.take(params["embed"], tokens, axis=0)
        elif embeds_mask is not None:
            # multimodal prefill: image-patch positions take the vision
            # encoder's projected embeds, text positions take the token
            # embeds (the token ids at masked positions are hashing salts,
            # not real vocab ids — see scheduler._admit)
            x = jnp.where(embeds_mask[..., None],
                          input_embeds.astype(_dtype(cfg)),
                          # masked positions carry salts by design; the
                          # where drops their NaN embed rows
                          # dynalint: disable-next-line=R1
                          jnp.take(params["embed"], tokens, axis=0))
        else:
            x = input_embeds.astype(_dtype(cfg))
        # HF Gemma scales whatever enters the first layer (token embeds and
        # caller-supplied inputs_embeds alike)
        return scale_embeds(x, cfg)

    use_kernel = tq == 1 and _decode_kernel_mode(cfg) is not None
    use_ring = sp_mesh is not None and tq > 1
    lw = cfg.layer_windows()
    layer_wnd = None if lw is None else jnp.asarray(lw, jnp.int32)
    if use_ring and (cfg.attn_softcap or cfg.query_scale
                     or lw is not None):
        raise NotImplementedError(
            "ring-attention (sp) prefill does not support attention "
            "soft-caps, sliding windows, or query-scale overrides; run "
            "Gemma-2-class models with sp=1 (chunked paged prefill)")
    if use_ring:
        from jax.sharding import NamedSharding
        from dynamo_tpu.ops.ring_attention import ring_attention
        # padding slots carry position == last valid; mark keys invalid by
        # index (valid tokens occupy the first kv_len slots of the chunk)
        idx = jnp.arange(tq, dtype=jnp.int32)[None, :]
        kv_positions = jnp.where(idx < meta.kv_lens[:, None],
                                 meta.positions, -1)

    moe_aux = cfg.is_moe and cfg.moe_impl == "dispatch"
    # the capacity form counts an expert's slots per batch row
    grid_mlp = moe_aux and not _use_dropless(cfg, mesh)
    # real (non-padding) positions: padding slots carry write_idx < 0
    grid_valid = meta.write_idx >= 0
    write_plan = kv_write_plan(meta.write_idx)
    # a model with a window pool writes every real token's row a second
    # time, into the window pool at ITS slot: the same rows in the same
    # order (`wwrite_idx` >= 0 exactly where `write_idx` is)
    wwrite_plan = wkv_lens = wpositions = None
    if cfg.window_pool:
        if use_ring or use_kernel or meta.wtable is None:
            raise NotImplementedError(
                f"{cfg.name}: a window pool is served by the gather path "
                f"alone, with the window tables in `meta`")
        wwrite_plan = write_plan._replace(
            write_idx=meta.wwrite_idx.reshape(-1))
        # the window table's own coordinates: key 0 is the row's first
        # held key, and every mask of paged_attention holds as it is
        wkv_lens = meta.kv_lens - meta.woff
        wpositions = meta.positions - meta.woff[:, None]
        swa_wnd = jnp.int32(cfg.sliding_window)
    layer_ids = jnp.arange(cfg.num_layers, dtype=jnp.int32)
    runs = layer_runs(cfg)
    whole = len(runs) == 1
    n = b * tq

    # the token rows are [B, Tq, ...] arrays throughout. A compact step
    # (`sel`, where `fits`) keeps its real tokens in the first `width` of
    # the B * Tq rows, and the token-wise halves of a layer (and, where
    # `rows_attn`, the attention between them) run over those alone:
    # each half is one branch of a `cond`, whose other
    # branch is the same half over all the rows, the grid. The pool
    # never enters a `cond`: XLA:TPU copied both leaves in and out of
    # every layer's write when the layer scan sat inside one (PERF.md
    # section 6, PR 32)
    sel = fits = None
    compact = None if last_idx is None \
        else step_compaction(meta.write_idx, sp_mesh)
    if compact is not None:
        width, fits = compact
        sel = compact_index(write_plan, width)
        write_plan = jax.tree.map(functools.partial(jnp.where, fits),
                                  sel.plan, write_plan)
        if wwrite_plan is not None:
            flat_w = sel.plan._replace(write_idx=jnp.pad(
                jnp.where(sel.live, wwrite_plan.write_idx[sel.cells], -1),
                (0, n - width), constant_values=-1))
            wwrite_plan = jax.tree.map(
                functools.partial(jnp.where, fits), flat_w, wwrite_plan)

    def either(fn, *operands):
        """fn(sel, ...) over a compact step's flat rows, or fn(None, ...)
        over the grid: whichever the step's real tokens allow."""
        if sel is None:
            return fn(None, *operands)
        return jax.lax.cond(fits, functools.partial(fn, sel),
                            functools.partial(fn, None), *operands)

    @jax.named_scope("step.compact")
    def flat(a):        # token rows [B, Tq, ...] -> the flat [1, W, ...]
        return a.reshape((1, n) + a.shape[2:])[:, :width]

    @jax.named_scope("step.compact")
    def unflat(a):      # [1, W, ...] -> token rows, the rest zero
        pad = [(0, 0), (0, n - width)] + [(0, 0)] * (a.ndim - 2)
        return jnp.pad(a, pad).reshape((b, tq) + a.shape[2:])

    @jax.named_scope("step.compact")
    def from_grid(a):   # cells [B, Tq, ...] -> their flat rows [1, W, ...]
        return jnp.take(a.reshape((n,) + a.shape[2:]), sel.cells, axis=0,
                        mode="clip")[None]

    @jax.named_scope("step.compact")
    def to_grid(a):     # flat rows [1, W, ...] -> their cells [B, Tq, ...]
        return jnp.take(a[0], sel.slot, axis=0, mode="clip"
                        ).reshape((b, tq) + a.shape[2:])

    flat_positions = None if sel is None else from_grid(meta.positions)
    # what the row forms read of the plan: a row's tokens are contiguous
    # token rows in both layouts. `kda_plan`: a step whose state layers
    # work over its rows (`kda_mix_rows`, `ssm_mix_rows`); `rows_attn`: a
    # compact step whose attention does in its flat branch
    # (`attention_rows`), where the shape says that pays
    rows_plan = kda_plan = None
    state_rows = cfg.has_state and mix_splits(cfg, b, tq)
    rows_attn = sel is not None and step_attention_rows(cfg, tq)
    if rows_attn or state_rows:
        row0 = jnp.arange(b, dtype=jnp.int32) * tq
        rows_plan = step_rows(grid_valid, row0 if sel is None else jnp.where(
            fits, sel.slot[row0], row0))
        kda_plan = rows_plan if state_rows else None

    def embed_rows(sel):
        if sel is None:
            return embed(tokens, input_embeds, embeds_mask)
        return unflat(embed(
            from_grid(tokens),
            None if input_embeds is None else from_grid(input_embeds),
            None if embeds_mask is None else from_grid(embeds_mask)))

    x = either(embed_rows)
    if use_ring:
        # shard the token axis so layernorm/projections parallelize over sp
        x = jax.lax.with_sharding_constraint(
            x, NamedSharding(sp_mesh, P(None, "sp", None)))

    @jax.named_scope("layers.body")
    def layer_step(carry, layer, stack, run, expert_stacks):
        # pool: (k, v[, k_scale, v_scale]) stacks; state: the recurrent
        # state's leaves, () for a model without linear layers; wpool:
        # the window layers' (wk, wv), () for a model without that pool
        x, pool, state, wpool = carry
        lp, lid, wnd = layer
        first, dense, kind = run.first, run.dense, run.kind
        # this layer's index among the layers that share its store
        sl = run.store_index(lid)
        if lp is None:
            # a `cond` branch is handed its operands as buffers: a layer's
            # slice of the stack would be copied for it, so the branches
            # read the stack where it lies
            @jax.named_scope("layers.stack")
            def lp_of():
                return jax.tree.map(
                    lambda a: jax.lax.dynamic_index_in_dim(
                        a, lid if whole else lid - first, keepdims=False),
                    stack)
        else:
            def lp_of():
                return lp

        def front(sel, x):
            """-> q, k, v as token rows [B, Tq, heads, hd]: k and v in
            x's own layout, where their rows are written from; q where
            attention reads it, in that layout for the row form
            (`attend_back`), at its cells for the grid form. A linear
            layer on the grid (`kda_mix`): what its state update takes,
            all three at their cells."""
            if sel is None:
                return layer_front(x, lp_of(), cfg, meta.positions, heads,
                                   kind)
            q, k, v = layer_front(flat(x), lp_of(), cfg, flat_positions,
                                  heads, kind)
            if kind == "kda":
                return to_grid(q), to_grid(k), to_grid(v)
            return (unflat(q) if rows_attn else to_grid(q)), unflat(k), \
                None if v is None else unflat(v)

        def back(sel, x, attn, ssm=None, stored=False):
            # stored: attn is [B * Tq, ...] token rows in x's own layout,
            # as a parallel block's `ssm` (its mixer's output) always is
            block = functools.partial(
                _mlp_block, cfg=cfg, mesh=mesh, stacks=expert_stacks,
                lid=lid if whole else lid - first, dense=dense)
            if sel is None:
                if stored:
                    attn = attn.reshape((b, tq) + attn.shape[1:])
                return layer_back(
                    x, attn, lp_of(), cfg, lambda xn, lp: block(
                        xn, lp, token_valid=grid_valid if moe_aux else None),
                    kind=kind, ssm=None if ssm is None
                    else ssm.reshape(b, tq, -1))

            def mlp(xn, lp):
                if grid_mlp and not dense:
                    out, stats = block(to_grid(xn), lp,
                                       token_valid=grid_valid)
                    return from_grid(out), stats
                return block(xn, lp, token_valid=sel.live[None]
                             if moe_aux else None)
            x, stats = layer_back(
                flat(x), attn[None, :width] if stored else from_grid(attn),
                lp_of(), cfg, mlp, kind=kind,
                ssm=None if ssm is None else ssm[None, :width])
            return unflat(x), stats

        def attend_back(sel, x, q, k, v, ssm=None, *, scope, lens,
                        positions, wnd):
            """A layer from its gathered K / V [Hkv, B, Lk, hd] on (the
            pool itself enters no `cond`): attention, then `back`. Over
            the grid every query of [B, Tq] is computed; a compact step
            computes its real ones, each row's last beside the chunk
            rows' own (ops/attention.attention_rows), from q's flat rows
            into the flat rows `back` reads."""
            with scope(), jax.named_scope("attention"):
                if sel is None:
                    attn = attend(q, k, v, lens, positions,
                                  cfg.attn_softcap, wnd, attn_scale(cfg))
                else:
                    attn = attention_rows(
                        flat(q)[0], k, v, lens, positions, rows_plan,
                        grid_valid, cfg.attn_softcap, wnd, attn_scale(cfg))
            return back(sel, x, attn, ssm, stored=sel is not None)

        def attention_back(x, q, kc, vc, table, lens, positions, wnd, scope,
                           ksc=None, vsc=None, ssm=()):
            """Attention of every query beside its row's page table,
            against the pool just written, then the back half. The row
            form (`rows_attn`): the pages gathered once for all rows, out
            here, and the queries meet them in `back`'s own `cond`. Else
            the grid form, whole, outside it."""
            if rows_attn:
                with scope(), jax.named_scope("attention"):
                    kv = gather_kv(kc, vc, table, q.dtype, ksc, vsc, sl)
                return either(functools.partial(
                    attend_back, lens=lens, positions=positions, wnd=wnd,
                    scope=scope), x, q, *kv, *ssm)
            with scope():
                attn = paged_attention(
                    q, kc, vc, table, lens, positions,
                    softcap=cfg.attn_softcap, window=wnd,
                    q_scale=attn_scale(cfg), k_scale=ksc, v_scale=vsc,
                    layer=sl)
            return either(back, x, attn, *ssm)

        if kind == "kda" and kda_plan is not None:
            # a linear layer of a split step, whole, over the step's
            # rows: no [B, Tq] tensor of the layer's width, and the state
            # leaves in no `cond` (the branches of `back` read o's rows)
            state = kda_mix_rows(
                state, sl, meta.state_slots, lp_of(), cfg,
                x.reshape(n, -1), kda_plan, grid_valid,
                meta.positions[:, 0] == 0)
            x, drop_stats = either(functools.partial(back, stored=True),
                                   x, state[2])
            return (x, pool, state, wpool), drop_stats
        if kind == "conv":
            # a conv layer, whole, over the step's rows: its tails in no
            # `cond` (the branches of `back` read o's rows)
            state = conv_mix_rows(
                state, sl, meta.state_slots, lp_of(), cfg,
                x.reshape(n, -1), kda_plan, grid_valid,
                meta.positions[:, 0] == 0)
            x, drop_stats = either(functools.partial(back, stored=True),
                                   x, state[1])
            return (x, pool, state, wpool), drop_stats
        if kind == "ret":
            # a power-retention layer, whole, over the step's rows: its
            # state in no `cond` (the branches of `back` read o's rows)
            state = ret_mix_rows(
                state, sl, meta.state_slots, lp_of(), cfg,
                x.reshape(n, -1), meta.positions, kda_plan, grid_valid,
                meta.positions[:, 0] == 0)
            x, drop_stats = either(functools.partial(back, stored=True),
                                   x, state[2])
            return (x, pool, state, wpool), drop_stats
        if kind == "par":
            # a parallel block's mixer, whole, over the step's rows, from
            # the block's input: no [B, Tq] tensor of its width, and the
            # state leaves in no `cond`. Its output rides state[2] to the
            # block's one `back`, behind the attention below
            state = ssm_mix_rows(
                state, sl, meta.state_slots, lp_of(), cfg,
                x.reshape(n, -1), kda_plan, grid_valid,
                meta.positions[:, 0] == 0)
        q, k, v = either(front, x)
        if kind == "kda":
            # the grid's part of a linear layer: each row's convolution
            # and state update, in its slot (no cache row, no attention)
            state, attn = kda_mix(
                state, sl, meta.state_slots, lp_of(), cfg, q, k, v,
                grid_valid, meta.positions[:, 0] == 0)
            x, drop_stats = either(back, x, attn)
            return (x, pool, state, wpool), drop_stats
        if kind == "swa":
            # a window layer: the same write and read against ITS pool,
            # over the short table of the pages the rows hold there
            wpool = write_kv_rows(
                wpool, tuple(r.reshape((1, n) + r.shape[2:])
                             for r in stored_kv_rows(k, v, False)),
                wwrite_plan, sl[None])
            x, drop_stats = attention_back(
                x, q, wpool[0], wpool[1], meta.wtable, wkv_lens, wpositions,
                swa_wnd, functools.partial(jax.named_scope,
                                           "attention.window"))
            return (x, pool, state, wpool), drop_stats
        # rows as stored (an int8 pool quantizes them here, at capture);
        # [B, Tq, Hkv, ...] -> this layer's [1, B*Tq, Hkv, ...]
        pool = write_kv_rows(
            pool, tuple(r.reshape((1, n) + r.shape[2:])
                        for r in stored_kv_rows(k, v, kvq)),
            write_plan, sl[None])
        # a one-leaf pool (latent attention) has no values leaf
        kc, vc = pool[0], (None if v is None else pool[1])
        ksc, vsc = pool[2:] if kvq else (None, None)
        if use_kernel:
            # decode hot path: stream pages HBM->VMEM, no materialized gather
            interp = _decode_kernel_mode(cfg) == "interpret"
            if mesh is not None and mesh.size > 1:
                attn = decode_paged_attention_sharded(
                    q[:, 0], kc, vc, meta.page_table, meta.kv_lens, mesh,
                    interpret=interp, k_scale=ksc, v_scale=vsc,
                    layer=sl[None])[:, None]
            else:
                attn = decode_paged_attention(
                    q[:, 0], kc, vc, meta.page_table, meta.kv_lens,
                    interpret=interp, k_scale=ksc, v_scale=vsc,
                    layer=sl[None])[:, None]
        elif use_ring:
            attn = ring_attention(q, k, v, meta.positions, kv_positions,
                                  sp_mesh, scale=attn_scale(cfg))
        else:
            x, drop_stats = attention_back(
                x, q, kc, vc, meta.page_table, meta.kv_lens, meta.positions,
                wnd, functools.partial(_full_scope, cfg, kind), ksc, vsc,
                (state[2],) if kind == "par" else ())
            return (x, pool, state, wpool), drop_stats

        x, drop_stats = either(back, x, attn, state[2]) if kind == "par" \
            else either(back, x, attn)
        return (x, pool, state, wpool), drop_stats

    # the stacked leaves ride the scan's carry whole, in the stored
    # representation  # dynalint: kv-codec — values are encoded at the
    # write (stored_kv_rows) and decoded at the gather (gather_values)
    pool_keys = tuple(key for key in cache_keys(kvq) if key in cache)
    pool = tuple(cache[key] for key in pool_keys)
    state_keys = tuple(cfg.state_leaves())
    state = tuple(cache[key] for key in state_keys)
    wpool_keys = tuple(cfg.window_cache_leaves())
    wpool = tuple(cache[key] for key in wpool_keys)
    if kda_plan is not None:
        # the scratch that carries a state layer's o to its back half
        state += (jnp.zeros((n, cfg.mamba_d_ssm), _dtype(cfg))
                  if cfg.has_ssm else
                  jnp.zeros((n, cfg.hidden_size), _dtype(cfg))
                  if cfg.has_conv else
                  jnp.zeros((n, cfg.num_heads * cfg.head_dim), _dtype(cfg))
                  if cfg.has_retention else
                  jnp.zeros((n, cfg.num_heads, cfg.linear_head_dim),
                            jnp.float32),)
    drops = []
    period = layer_period(cfg)
    # every run but a window-pool model's kind stacks: a scan a run, in
    # layer order (such a model's dense lead among them, BEFORE its loop)
    for run in runs if period is None else runs[:period.lead]:
        name, first, count, dense = run[:4]
        scan_layers, expert_stacks = (params[name], None) if dense \
            else split_expert_stacks(params[name], cfg, mesh)
        part = _group_rows(whole, first, count)
        scan_xs = (scan_layers if sel is None else None, part(layer_ids),
                   None if layer_wnd is None else part(layer_wnd))
        with _lead_scope(run):
            (x, pool, state, wpool), drop_g = _layer_scan(
                functools.partial(layer_step, stack=scan_layers, run=run,
                                  expert_stacks=expert_stacks),
                (x, pool, state, wpool), scan_xs)
        drops.append(_sum_stats(drop_g))
    if period is not None:
        # ONE loop: a scan over periods, a scan a part inside it, every
        # layer read from its kind's stack where it lies (`lp_of`)
        stacks = {ri: split_expert_stacks(params[runs[ri].key], cfg, mesh)
                  for ri in range(period.lead, len(runs))}

        def period_step(carry, p):
            stats = []
            for ri, per, offset, count in period.parts:
                run = runs[ri]
                lids = run.first + p * per + offset \
                    + jnp.arange(count, dtype=jnp.int32)
                carry, drop_g = _layer_scan(
                    functools.partial(layer_step, stack=stacks[ri][0],
                                      run=run, expert_stacks=stacks[ri][1]),
                    carry, (None, lids, None))
                stats.append(_sum_stats(drop_g))
            return carry, _merge_stats(stats)

        (x, pool, state, wpool), drop_p = _layer_scan(
            period_step, (x, pool, state, wpool),
            jnp.arange(period.count, dtype=jnp.int32))
        drops.append(_sum_stats(drop_p))
    aux = _merge_stats(drops)

    if last_idx is not None:
        # the rows that are sampled, before the head
        at = jnp.arange(b) * tq + last_idx
        if sel is not None:
            at = jnp.where(fits, sel.slot[at], at)
        x = jnp.take(x.reshape(n, -1), at, axis=0, mode="clip")
    logits = lm_logits(x, params["final_norm"], lm_head(params, cfg), cfg)
    cache_out = dict(zip(pool_keys + state_keys + wpool_keys,
                         pool + state[:len(state_keys)] + wpool))
    if with_aux:
        return logits, cache_out, aux
    return logits, cache_out
