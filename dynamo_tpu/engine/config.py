"""Model + engine configuration.

The reference carries model metadata in a ModelDeploymentCard built from HF
config.json / GGUF (reference: lib/llm/src/model_card/model.rs:55-201). Here the
architectural subset needed by the JAX engine lives in ModelConfig; the serving
metadata (tokenizer, chat template, context length) lives in
dynamo_tpu/llm/model_card.py.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Union


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """Architecture hyper-parameters for a decoder-only transformer."""

    name: str = "tiny"
    vocab_size: int = 256
    hidden_size: int = 128
    intermediate_size: int = 256
    num_layers: int = 2
    num_heads: int = 4
    num_kv_heads: int = 2
    head_dim: int = 32
    rope_theta: float = 500000.0
    rms_norm_eps: float = 1e-5
    attn_bias: bool = False      # q/k/v projection bias (Qwen2-style)
    # An RMSNorm on q and k before RoPE (leaves q_norm / k_norm), by its
    # granularity: True (OLMoE) = over the WHOLE projection, all heads
    # together, one weight vector of H x head_dim each, before the split
    # into heads; "head" (afmoe) = over EACH head's head_dim values, one
    # weight vector of head_dim shared by the heads
    qk_norm: Union[bool, str] = False   # False | True | "head"
    # softmax attention's output gate (afmoe): o = Wo (attn *
    # sigmoid(x_normed Wg)), element-wise, Wg the leaf `w_out_gate`
    # [D, H x head_dim]. The latent path has its own, head-wise
    # (`mla_gate`), the linear layers theirs (`kda_wg`)
    attn_out_gate: bool = False
    # Gemma-family architecture deltas (HF GemmaForCausalLM), which other
    # families share one at a time (afmoe: `embed_scale` under
    # `mup_enabled`, and `post_norms`):
    embed_scale: float = 0.0     # 0 = off; the embeddings are multiplied
    #                              by this (sqrt(hidden_size)) before the
    #                              first layer
    norm_plus_one: bool = False  # RMSNorm weight applied as (1 + w), in f32
    mlp_act: str = "silu"        # "silu" | "gelu_tanh" (Gemma GeGLU)
    # Gemma-2 deltas:
    post_norms: bool = False     # extra post-attention / post-ffw RMSNorms
    #                              (four norms a block; afmoe has them too)
    attn_softcap: float = 0.0    # tanh soft-cap on attention logits (50.0)
    final_softcap: float = 0.0   # tanh soft-cap on lm-head logits (30.0)
    query_scale: float = 0.0     # q scaling; 0 = default head_dim**-0.5
    #                              (Gemma-2 uses query_pre_attn_scalar**-0.5)
    sliding_window: int = 0      # sliding-window attention width; 0 = full
    # which layers use the sliding window (only meaningful when
    # sliding_window > 0): one entry a layer, "sliding_attention" |
    # "full_attention", as HF's `layer_types`. () = Gemma-2's default,
    # even layers sliding and odd ones global. Any pattern is one list:
    # Gemma-2's alternation, every layer sliding, three sliding to one full.
    # With `conv_l_cache` > 0 the entries are "conv" | "full_attention"
    # instead: which layers are gated short convolutions (`layer_kinds`)
    layer_types: tuple = ()
    # How a sliding layer is SERVED. False (Gemma-2): a mask of its width
    # over the full-length gather of the one pool every layer shares, one
    # traced width a layer (`layer_windows`). True: the sliding layers
    # are a layer kind of their own ("swa" in `layer_kinds`) with their
    # own cache leaves over a second page pool (`window_cache_leaves`),
    # in which a sequence holds only the pages its next step can see
    # (engine/scheduler.py), and a step gathers that short table.
    window_pool: bool = False
    # RoPE by layer kind (HF `rope_parameters`): None = plain RoPE at
    # `rope_theta`. `rope_full` serves the full-attention layers (and
    # every layer of a model with one kind), `rope_sliding` the sliding
    # ones (models/llama.rope_table).
    rope_full: Optional["RopeParams"] = None
    rope_sliding: Optional["RopeParams"] = None
    max_model_len: int = 2048
    tie_word_embeddings: bool = False
    dtype: str = "bfloat16"
    # weight-only quantization for serving (ops/quant.py): "" = weights in
    # `dtype`; "int8" = dense projections + lm_head stored int8 with
    # per-output-channel scales (halves weight HBM + decode weight reads)
    quant: str = ""
    # KV-cache page quantization (ops/kv_quant.py): "" = pages in `dtype`
    # (bit-identical to pre-knob behavior); "int8" = pages stored int8
    # with per-row f32 scales, quantized at capture inside the jitted
    # step and dequantized inside the paged read — the same
    # representation flows through offload tiers, disagg transfer, and
    # integrity checksums. Deployments usually set this through
    # EngineConfig.kv_quant (mirroring the weight knob's --quant flag).
    kv_quant: str = ""
    # MoE (Mixtral-style); num_experts == 0 means dense MLP.
    num_experts: int = 0
    num_experts_per_tok: int = 2
    # router weights: softmax over ALL experts, the k largest kept; True
    # (Mixtral) rescales the k to sum to one, False (OLMoE's published
    # `norm_topk_prob`) keeps them as they are
    norm_topk_prob: bool = True
    # "dispatch" = routed dispatch (ops/moe.py, serving default: dropless
    # sorted dispatch on one device, capacity-based on --tp/--ep meshes);
    # "dense" = every expert computes every token (exact, E/k x FLOPs —
    # oracle for tests)
    moe_impl: str = "dispatch"
    # DeepSeek-V3-family router and block (Moonlight): `moe_scoring`
    # "softmax" | "sigmoid" (scores over ALL experts, float32);
    # `moe_router_bias`: a per-expert `router_bias` leaf is added to the
    # scores to PICK the k experts and not to weigh them (`noaux_tc`);
    # `moe_routed_scale` multiplies the kept weights after renormalising;
    # `shared_expert_size`: width of ONE dense SwiGLU every token also
    # passes (n_shared_experts x moe_intermediate_size), 0 = none;
    # `first_dense_layers`: leading layers with a dense MLP of
    # `dense_intermediate_size` where the rest have experts (the layer
    # kinds are split once, models/llama.layer_runs; in a `window_pool`
    # model the lead has stacks of its own and runs BEFORE the loop over
    # periods, models/llama.layer_period)
    moe_scoring: str = "softmax"
    moe_router_bias: bool = False
    moe_routed_scale: float = 1.0
    shared_expert_size: int = 0
    first_dense_layers: int = 0
    dense_intermediate_size: int = 0
    # Multi-head latent attention (DeepSeek-V2/V3, no q-LoRA):
    # kv_lora_rank > 0 switches it on. q has num_heads heads of
    # qk_nope_head_dim | qk_rope_head_dim, the cache holds ONE leaf of
    # kv_lora_rank + qk_rope_head_dim values a token and layer (the
    # normalised latent | the rotated shared key), and attention runs in
    # the absorbed form against it as one KV head whose values are its
    # first kv_lora_rank columns (`kv_cache_leaves`). `head_dim` is then
    # v_head_dim (what `wo` takes a head) and `query_scale` the
    # (nope + rope) ** -0.5 the loader sets.
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    # the hybrid's deltas on latent attention (both off = DeepSeek-V3's):
    # `mla_qk_norm`: an RMSNorm over each head's (nope | rope) query
    # (leaf `mla_q_norm` [dn + dr]) and over the shared rope key (leaf
    # `mla_k_norm` [dr]), before RoPE. The nope keys are a linear map of
    # the latent, which `kv_a_norm` already norms; a norm over each
    # HEAD's rebuilt nope key would put a per-(token, head) scale on part
    # of the score, which the absorbed form's one row a token cannot
    # carry. `mla_gate`: each head's output is multiplied by
    # sigmoid(x Wgate)_h (leaf `w_attn_gate` [D, H]) before `wo`
    mla_qk_norm: bool = False
    mla_gate: bool = False
    # Linear-attention layers (Kimi Delta Attention: a gated delta rule
    # with a per-channel decay and a short causal convolution before q, k
    # and v). `linear_group_size` g > 0 switches them on: layer i keeps
    # the model's softmax attention where (i + 1) % g == 0 and is a KDA
    # layer otherwise (`layer_kinds`). A KDA layer has `num_heads` heads
    # of `linear_head_dim` for keys and values alike, no KV cache, and a
    # per-sequence STATE instead (`state_leaves`): the [H, dk, dv]
    # float32 matrix and the last `linear_conv_size - 1` pre-convolution
    # inputs. `linear_gate_lower_bound` is the decay's floor: g =
    # bound * sigmoid(exp(A_log) * (x Wf + dt_bias)), in (bound, 0).
    linear_group_size: int = 0
    linear_head_dim: int = 0
    linear_conv_size: int = 4
    linear_gate_lower_bound: float = -5.0
    # A chip's share of an expert layer: the router keeps its published
    # width (`num_experts`), this chip holds `experts_held` of them from
    # `expert_first` on (0 = all) and computes their part of the result;
    # the expert leaves are [L, experts_held, ...]. `moe_n_group` /
    # `moe_topk_group`: DeepSeek-V3's group-limited pick (a group's score
    # is the sum of its two largest biased scores, the best
    # `moe_topk_group` groups stay, the k experts come from them)
    experts_held: int = 0
    expert_first: int = 0
    moe_n_group: int = 1
    moe_topk_group: int = 1
    # The parallel hybrid block (Falcon-H1): EVERY layer has a Mamba-2
    # state-space mixer BESIDE its softmax attention, both reading the one
    # normed input, their outputs summed before the residual (layer kind
    # "par", `layer_kinds`). `mamba_d_ssm` > 0 switches it on: the mixer
    # has `mamba_n_heads` heads of `mamba_d_head` (their product is
    # `mamba_d_ssm`), `mamba_n_groups` groups of B and C of
    # `mamba_d_state` each (a head reads group h // (heads / groups)), a
    # causal depth-wise convolution of `mamba_d_conv` taps WITH a bias
    # over x | B | C, and a gated grouped RMSNorm, the gate first. Such a
    # layer holds K / V pages AND a per-sequence state (`state_leaves`):
    # the [heads, d_head, d_state] float32 matrix and the last
    # `mamba_d_conv - 1` pre-convolution inputs. `mamba_chunk_size` is the
    # published kernel's block and is recorded only: the served block is
    # ops/state_space.BLOCK, which does not change the result.
    mamba_d_ssm: int = 0
    mamba_n_heads: int = 0
    mamba_d_head: int = 0
    mamba_n_groups: int = 1
    mamba_d_state: int = 0
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 128
    # its muP multipliers, each applied where the published code applies
    # it (models/llama.py; 1.0 traces nothing): on the attention branch's
    # input, its keys before RoPE and its output; on the mixer's input,
    # the five segments z | x | B | C | dt of its input projection
    # (`ssm_multipliers`) and its output; on the MLP's gate before the
    # activation and on its output (`mlp_multipliers`); on the logits.
    # The embeddings' multiplier is `embed_scale`.
    attention_in_multiplier: float = 1.0
    key_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_multipliers: tuple = (1.0, 1.0, 1.0, 1.0, 1.0)
    ssm_out_multiplier: float = 1.0
    mlp_multipliers: tuple = (1.0, 1.0)
    lm_head_multiplier: float = 1.0
    # Gated short-convolution layers (LFM2). `conv_l_cache` K > 0 switches
    # them on, and `layer_types` then says which layers they are, one
    # entry a layer, "conv" | "full_attention" (layer kind "conv" in
    # `layer_kinds`). Such a layer's mixer is B | C | u = xn W_in [D, 3D];
    # c = a causal depth-wise convolution of K taps over B * u (NO
    # activation, no bias: models/loader.py refuses a file with one);
    # out = (C * c) W_out. It holds no pages and no matrix state: its
    # per-sequence state is the last K - 1 rows of B * u (`state_leaves`:
    # ONE leaf, `conv_tail`).
    conv_l_cache: int = 0
    # Power-retention layers (Brumby; arXiv:2507.04239). `retention_degree`
    # p > 0 switches them on (2 is what is modelled), for EVERY layer
    # (layer kind "ret" in `layer_kinds`): the block, q | k | v
    # projections, `qk_norm` and RoPE are the softmax model's own, and the
    # mixer is o_t[h] = sum_i w[t, i] v_i / sum_i w[t, i] with w[t, i] =
    # (g_{i+1} .. g_t) (q_t[h] . k_i)^p, g = sigmoid(xn W_g + b_g) a
    # key-value head and token (leaves `ret_wg` [D, Hkv], `ret_bg` [Hkv]),
    # no softmax, no scale, no output gate. Such a layer holds NO pages: its whole context is a
    # per-sequence state (`state_leaves`), the [Hkv, head_dim, F] float32
    # matrix and the [Hkv, F] normaliser over the F = `retention_features`
    # degree-2 features of a key (ops/power_retention.phi), constant in
    # the context's length. A model of such layers alone has no paged
    # cache at all (`num_cache_layers` 0, `kv_cache_leaves` empty).
    retention_degree: int = 0
    # what the router adds to the sum of the k kept weights before it
    # divides by it (`norm_topk_prob`): the published constant of the
    # family (DeepSeek-V3's 1e-20; LFM2's 1e-6)
    moe_renorm_eps: float = 1e-20
    # decode attention impl: "auto" and "off" are the XLA gather path on
    # every platform (models/llama._decode_kernel_mode says why); "on" is
    # the compiled Pallas kernel and raises at engine construction where it
    # cannot serve (geometry, mesh, soft-caps); "interpret" runs the kernel
    # in interpreter mode for CPU tests. On multi-device meshes the kernel
    # runs under shard_map over the "tp" axis (ops/paged_attention.py
    # decode_paged_attention_sharded).
    decode_kernel: str = "auto"
    # KV heads that share one row of the paged pool: DERIVED, never a
    # knob. `kv_heads_per_row` below is the rule (a function of the
    # shapes, the cache's kind and the mesh's `tp`); NativeEngine resolves
    # it once at construction, overwrites whatever stands here, and its
    # programs close over the result. 1 = a row is one head's `head_dim`
    # values; f > 1 = f adjacent heads of `head_dim` < 128 fill one
    # 128-lane row (`kv_cache_leaves` hands out the stored shape,
    # models/llama.layer_front forms the rows).
    kv_row_heads: int = 1
    # Lanes one row of the paged pool is STORED in: DERIVED like
    # `kv_row_heads`, by `kv_row_lanes` below, and resolved with it. 0 =
    # the model's own width. `kv_cache_leaves` reads it for a latent
    # cache, whose kv_lora_rank + qk_rope_head_dim values (576) are
    # stored in the next multiple of 128 lanes (640), the rest zeros
    # (models/llama._mla_front); every other row is `kv_row_heads` heads.
    kv_row_lanes: int = 0
    # Multimodal (Qwen2-VL-style); None means text-only.
    vision: Optional["VisionConfig"] = None

    @property
    def q_per_kv(self) -> int:
        return self.num_heads // self.num_kv_heads

    def sliding_layers(self) -> tuple:
        """One bool a layer: whether it attends inside `sliding_window`."""
        if not self.sliding_window:
            return (False,) * self.num_layers
        if not self.layer_types:
            # Gemma-2: even layers sliding, odd layers global
            return tuple(l % 2 == 0 for l in range(self.num_layers))
        if len(self.layer_types) != self.num_layers:
            raise ValueError(
                f"{self.name}: layer_types has {len(self.layer_types)} "
                f"entries for {self.num_layers} layers")
        return tuple(t == "sliding_attention" for t in self.layer_types)

    def layer_windows(self):
        """Per-layer attention window as an int32 list, for a model whose
        sliding layers are a MASK over the shared pool: the sliding width
        for sliding layers, a huge sentinel (2**30, effectively full) for
        global layers. None when every layer is full-attention, and for a
        `window_pool` model, whose sliding layers are a kind of their own
        with a static width."""
        if not self.sliding_window or self.window_pool:
            return None
        full = 1 << 30
        return [self.sliding_window if s else full
                for s in self.sliding_layers()]

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def is_mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def has_ssm(self) -> bool:
        """The parallel hybrid block: a state-space mixer beside softmax
        attention in every layer."""
        return self.mamba_d_ssm > 0

    @property
    def has_conv(self) -> bool:
        """Gated short-convolution layers among its layers (`layer_types`
        says which)."""
        return self.conv_l_cache > 0

    @property
    def has_retention(self) -> bool:
        """Power-retention layers, every layer (`retention_degree`)."""
        return self.retention_degree > 0

    @property
    def retention_features(self) -> int:
        """F: the degree-2 features of one head_dim-vector as the state
        holds them (ops/power_retention.features: 8320 at 128)."""
        return self.head_dim * (self.head_dim // 2 + 1)

    @property
    def has_state(self) -> bool:
        """Holds a recurrent state a sequence (`state_leaves`): linear-
        attention layers, a state-space mixer beside attention, gated
        short-convolution layers (whose state is their tail alone), or
        power-retention layers (whose state is all they hold)."""
        return self.linear_group_size > 0 or self.has_ssm \
            or self.has_conv or self.has_retention

    @property
    def mamba_conv_dim(self) -> int:
        """Channels of the mixer's convolution: x | B | C."""
        return self.mamba_d_ssm \
            + 2 * self.mamba_n_groups * self.mamba_d_state

    def layer_kinds(self) -> tuple:
        """Each layer's attention kind, in order: "kda" | "mla" | "mha" |
        "swa" (a sliding layer served from the window pool; "mha" in all
        but its cache and its RoPE table) | "par" (softmax attention AND
        a state-space mixer side by side: the one kind that lies on the
        paged cache's layer axis and on the state's) | "conv" (a gated
        short convolution: no pages, a tail on the state's axis; given by
        `layer_types`, as the sliding layers are) | "ret" (power
        retention: no pages, a matrix state on the state's axis; every
        layer of a model that has any). A model without linear layers,
        conv layers and a window pool is one kind throughout."""
        if self.has_retention:
            return ("ret",) * self.num_layers
        own = "par" if self.has_ssm else "mla" if self.is_mla else "mha"
        g = self.linear_group_size
        if self.has_conv:
            if len(self.layer_types) != self.num_layers or \
                    set(self.layer_types) - {"conv", "full_attention"}:
                raise ValueError(
                    f"{self.name}: layer_types {self.layer_types!r}: one "
                    f"of conv | full_attention a layer "
                    f"({self.num_layers}) is what a model with conv "
                    f"layers gives")
            return tuple("conv" if t == "conv" else own
                         for t in self.layer_types)
        if self.window_pool:
            return tuple("swa" if s else own for s in self.sliding_layers())
        return tuple(own if not g or (i + 1) % g == 0 else "kda"
                     for i in range(self.num_layers))

    @property
    def num_cache_layers(self) -> int:
        """Layers that hold pages of the FULL pool: every page of their
        sequence's context (all but the linear and the window layers)."""
        return sum(kind in ("mha", "mla", "par")
                   for kind in self.layer_kinds())

    @property
    def num_window_layers(self) -> int:
        """Layers whose pages live in the window pool."""
        return sum(kind == "swa" for kind in self.layer_kinds())

    @property
    def num_state_layers(self) -> int:
        return sum(kind in ("kda", "par", "conv", "ret")
                   for kind in self.layer_kinds())

    @property
    def local_experts(self) -> int:
        """Experts whose weights live here: the share, or all."""
        return self.experts_held or self.num_experts

    def state_leaves(self) -> dict:
        """THE description of the per-sequence recurrent state, beside
        `kv_cache_leaves`: leaf -> (shape a slot and layer, dtype), each
        stored [state layers, slots, ...], by the kind that holds one.
        Empty for a model without a state. `kda_s` is the delta rule's
        matrix, float32 whatever the model's dtype; `kda_conv` the last
        conv_size - 1 inputs of the q | k | v convolution. The parallel
        block's: `ssm_s`, the mixer's [heads, d_head, d_state] matrix,
        float32 likewise, and `ssm_conv`, the last d_conv - 1 inputs of
        the x | B | C convolution. A conv layer's: `conv_tail` alone, the
        last conv_l_cache - 1 rows of B * u, in the model's dtype. A
        power-retention layer's: `ret_s`, the [Hkv, head_dim, F] matrix,
        values-major so that the features lie on the lanes, and `ret_z`,
        the [Hkv, F] normaliser, float32 both."""
        if self.has_retention:
            hkv, f = self.num_kv_heads, self.retention_features
            return {"ret_s": ((hkv, self.head_dim, f), "float32"),
                    "ret_z": ((hkv, f), "float32")}
        if self.has_conv:
            return {"conv_tail": ((self.conv_l_cache - 1, self.hidden_size),
                                  self.dtype)}
        if self.has_ssm:
            return {"ssm_s": ((self.mamba_n_heads, self.mamba_d_head,
                               self.mamba_d_state), "float32"),
                    "ssm_conv": ((self.mamba_d_conv - 1,
                                  self.mamba_conv_dim), self.dtype)}
        if not self.has_state:
            return {}
        h, d = self.num_heads, self.linear_head_dim
        return {"kda_s": ((h, d, d), "float32"),
                "kda_conv": ((self.linear_conv_size - 1, 3 * h * d),
                             self.dtype)}

    def state_bytes_per_slot(self) -> int:
        """Bytes one sequence's state holds, all the layers that keep
        one."""
        total = 0
        for shape, dtype in self.state_leaves().values():
            n = 1
            for dim in shape:
                n *= dim
            total += n * (4 if dtype == "float32" else 2)
        return total * self.num_state_layers

    def kv_cache_leaves(self) -> dict:
        """THE description of the paged cache AS IT IS STORED: value leaf
        -> (rows a token, width), each stored [L, rows, pages, page_size,
        width]. Two leaves of num_kv_heads x head_dim, f = `kv_row_heads`
        adjacent heads to a row: (num_kv_heads / f, f x head_dim), the
        same bytes whatever f (row r holds heads r f .. r f + f - 1, head
        j of them in lanes j head_dim ..). Under latent attention ONE,
        named "k" because it is what the absorbed queries are scored
        against: a single head of kv_lora_rank + qk_rope_head_dim whose
        first kv_lora_rank columns are also the values, stored
        `kv_row_lanes` wide where an engine resolved that (the lanes past
        `latent_width` are zeros). Empty for a model none of whose layers
        holds a page (power retention). init_cache, the shardings, the
        page-byte gauges and `step_attention_rows` read this."""
        if not self.num_cache_layers:
            return {}
        if self.is_mla:
            return {"k": (1, self.latent_width + self.kv_row_pad)}
        return {"k": self._kv_row, "v": self._kv_row}

    @property
    def latent_width(self) -> int:
        """Values a token and layer holds under latent attention: the
        latent and the one rotated key part every head shares."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def kv_row_pad(self) -> int:
        """Zero lanes of a stored pool row past the model's values: a
        latent row held in whole lane tiles (`kv_row_lanes`), 0 elsewhere.
        Pages leave the pool without them (engine._logical_pages)."""
        return max(self.kv_row_lanes - self.latent_width, 0) \
            if self.is_mla else 0

    def window_cache_leaves(self) -> dict:
        """The window layers' leaves, beside `kv_cache_leaves` and by the
        same rule: value leaf -> (rows a token, width), each stored
        [window layers, rows, window pages, page_size, width] over the
        SECOND page pool. Empty for a model without a window pool."""
        if not self.window_pool:
            return {}
        return {"wk": self._kv_row, "wv": self._kv_row}

    @property
    def _kv_row(self) -> tuple:
        f = self.kv_row_heads
        return self.num_kv_heads // f, f * self.head_dim

    def kv_bytes_per_token(self) -> int:
        """Bytes one token's K and V are, unquantized, over all the layers
        that have pages in the FULL pool: the MODEL's figure, what a step
        must read a token of context. The pool's own is the stored row
        (`kv_cache_leaves`; `EngineMetrics.kv_page_bytes`), which is wider
        where a latent row is padded to whole lane tiles."""
        leaves = {"k": (1, self.latent_width)} if self.is_mla \
            else self.kv_cache_leaves()
        return self._token_bytes(self.num_cache_layers, leaves)

    def window_kv_bytes_per_token(self) -> int:
        """Bytes one token holds in the window pool, all window layers."""
        return self._token_bytes(self.num_window_layers,
                                 self.window_cache_leaves())

    def _token_bytes(self, layers: int, leaves: dict) -> int:
        itemsize = 4 if self.dtype == "float32" else 2
        return layers * itemsize * sum(h * w for h, w in leaves.values())

    @property
    def moe_dropless(self) -> bool:
        """Whether the "dispatch" impl is the dropless sorted dispatch
        (ops/moe.py moe_dropless_mlp) or the capacity form. Many small
        experts need the first: at 64 experts of 8 a token the capacity
        form drops assignments in every chunk and computes every expert
        for every decode row. Up to eight wide experts (Mixtral) keep the
        capacity form for now, on measurements (PERF.md section 6, PR
        27): in its closed cell the dropless form was no slower per
        token, but each program's first dispatch traces three Pallas
        calls more (set-up 62 s against 54.5 s warm), and an all-real
        prefill chunk re-reads an expert's weights once per row tile
        (8.6 ms a layer against 5.9)."""
        return self.num_experts > 8


# lanes of a TPU register tile's minor axis: the width of a pool row that
# XLA:TPU leaves where it rests (PERF.md section 6, PR 51)
LANES = 128


def _rows_as_published(cfg: ModelConfig) -> bool:
    """Whether the pool keeps the model's own rows, a head of its own
    width each, because another row would not be an exact view of the
    same result: an int8 pool (a row's scale is the ROW's: two heads to a
    row would share one, and the page movers re-view value leaves, not
    scales), or the Pallas decode kernel asked for (it takes its scale
    from the page's width and packs tiles its own way,
    ops/paged_attention._kernel_pack). Streamed decode is the third such
    form and the engine's to know (`EngineConfig.stream_pages`,
    NativeEngine.__init__): its staged pages meet the resident ones in the
    form they travel in."""
    return bool(cfg.kv_quant) or cfg.decode_kernel not in ("auto", "off")


def kv_heads_per_row(cfg: ModelConfig, tp: int = 1) -> int:
    """THE rule for `ModelConfig.kv_row_heads`: how many adjacent KV heads
    share one row of the paged pool, read from shapes and the cache's
    kind alone. A pool whose rows are `head_dim` < 128 lanes wide rests
    on a TPU with its PAGE axis minor, and every program that writes rows
    into it re-lays both leaves out, whole, once an attention layer
    (LFM2's 64-wide heads: 8.6 of a 28.6 ms step, PERF.md section 6, PR
    51); f = 128 / head_dim heads to a row make it the 128-lane row every
    other pool already is, over the same bytes. 1 wherever that is not an
    exact view of the same result: head_dim does not divide 128 (96), a
    "tp" shard's heads do not fill whole rows, a latent cache (one leaf of
    one head: `kv_row_lanes` below widens ITS row), or a pool that
    `_rows_as_published`."""
    hd = cfg.head_dim
    if not 0 < hd < LANES or LANES % hd or not cfg.num_cache_layers:
        return 1
    f = LANES // hd
    if (cfg.is_mla or _rows_as_published(cfg)
            or cfg.num_kv_heads % (tp * f)):
        return 1
    return f


def kv_row_lanes(cfg: ModelConfig, tp: int = 1) -> int:
    """THE rule for `ModelConfig.kv_row_lanes`, beside `kv_heads_per_row`
    and read from the same things: the lanes one row of the paged pool is
    stored in. Every cache of K and V heads: `kv_heads_per_row` x
    head_dim, what it was. A latent cache: its kv_lora_rank +
    qk_rope_head_dim values in the next whole number of 128-lane tiles
    (576 -> 640), the rest zeros in the stored row and in the query
    (models/llama._mla_front), so that q . row adds exact zeros and the
    values' pad columns are dropped with the rope columns
    (`_mla_out`). A 576-wide row is 4.5 tiles: such a pool rests
    pages-minor on a TPU too, and every program copied it whole on the
    way in and on the way out (Moonlight: 3.6 ms a program, PERF.md
    section 6, PR 53). The price is the pad's share of the pool's bytes
    (a ninth), which `kv_bytes_per_token` does not count and
    `kv_page_bytes` does. The model's own width for a pool that
    `_rows_as_published` (no engine serves a latent cache that way yet,
    `refuse_unserved`; the reasons are the forms' own)."""
    if not cfg.num_cache_layers:
        return 0        # no layer holds a page: no row is stored
    if not cfg.is_mla:
        return kv_heads_per_row(cfg, tp) * cfg.head_dim
    if _rows_as_published(cfg):
        return cfg.latent_width
    return -(-cfg.latent_width // LANES) * LANES


def with_kv_rows(cfg: ModelConfig, tp: int = 1) -> ModelConfig:
    """`cfg` as an engine on a mesh of `tp` serves it: `kv_row_heads` and
    `kv_row_lanes` by their rules. The tools and tests that build an
    engine's programs without one (tools/pool_ops.py)."""
    return dataclasses.replace(cfg, kv_row_heads=kv_heads_per_row(cfg, tp),
                               kv_row_lanes=kv_row_lanes(cfg, tp))


@dataclasses.dataclass(frozen=True)
class RopeParams:
    """One layer kind's RoPE (an entry of HF's `rope_parameters`).
    `rope_type` "default": plain RoPE at `theta`. "none": the kind has NO
    positional embedding: q and k go to attention unrotated, and no op is
    traced for it (afmoe's full-attention layers). "yarn": the frequencies
    blend interpolated (1 / (factor f_i)) and extrapolated (1 / f_i) by a
    linear ramp between the dimensions that turn `beta_fast` and
    `beta_slow` times within `original_max_position`, and cos / sin are
    multiplied by `attention_factor` (0 = 0.1 ln(factor) + 1) at every
    position (models/llama.rope_table)."""

    theta: float = 10000.0
    rope_type: str = "default"
    factor: float = 1.0
    original_max_position: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 0.0


@dataclasses.dataclass(frozen=True)
class VisionConfig:
    """Vision encoder config (ViT-style) for multimodal models."""

    image_size: int = 224
    patch_size: int = 14
    hidden_size: int = 128
    intermediate_size: int = 256
    num_layers: int = 2
    num_heads: int = 4
    # Projection into the text model's embedding space happens at hidden_size
    # -> text hidden_size.


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Serving engine knobs (continuous batching, paging, buckets).

    Mirrors the role of engine args passed to vLLM/SGLang by the reference
    (reference: launch/dynamo-run/src/flags.rs, examples/llm/configs/*.yaml);
    block/page size default matches the canonical example config's KV block 64
    (reference: examples/llm/configs/disagg_router.yaml).
    """

    page_size: int = 64                 # tokens per KV page
    num_pages: int = 512                # HBM pages per engine
    max_slots: int = 8                  # concurrent decode slots
    max_prefill_chunk: int = 512        # longest single prefill step
    prefill_buckets: tuple = (16, 32, 64, 128, 256, 512)
    # waiting sequences whose next chunk fits the same token bucket prefill
    # together in one device step (row dim bucketed to powers of two);
    # 1 = the old one-sequence-per-step behavior
    max_prefill_batch: int = 8
    # (page-count buckets are derived: pow2 up to max_model_len/page_size)
    max_model_len: int = 2048
    # host-DRAM KV tier capacity in pages (0 = tier off); evicted HBM pages
    # spill here and return on prefix hits (engine/offload.py)
    host_pages: int = 0
    # disk (NVMe-style) tier below DRAM: DRAM evictions spill down, prefix
    # hits promote back up (reference: kv/storage.rs tier ladder). Requires
    # host_pages > 0. disk_dir None = a temp directory.
    disk_pages: int = 0
    disk_dir: Optional[str] = None
    # decode-time KV streaming beyond HBM (engine/streaming.py): a request
    # whose admission-time page count exceeds stream_resident_pages keeps
    # only a resident working set in HBM and attends over the rest by
    # staging cold pages from the offload tiers (host / disk) through a
    # double-buffered window pool, prefetched ahead of the consuming
    # dispatch. stream_pages = window-pool slots per staging half (0 =
    # streaming off; requires host_pages > 0). Cold-page victims are
    # picked by a per-page attention-mass EWMA; the first
    # stream_hot_pages logical pages are never spilled (hot prefix).
    stream_pages: int = 0
    stream_resident_pages: int = 8
    stream_hot_pages: int = 2
    # mesh axes sizes: (dp, tp). dp>1 replicates the whole engine.
    tp: int = 1
    dp: int = 1
    # sequence-parallel axis for long-context ring attention (0 = off)
    sp: int = 1
    # decode steps fused into ONE device program per scheduler step
    # (lax.scan: the sampled token feeds the next iteration on device, so
    # plan uploads + token downloads amortize over the window — the fix for
    # the host-latency-bound decode loop, VERDICT r2 weak #1). Host-side
    # stop conditions are checked when the window returns; tokens past a
    # stop are discarded. 1 = the old step-per-token behavior.
    decode_steps: int = 8
    # decode pipeline depth: 2 = the overlapped host/device loop (engine
    # step N+1 is dispatched while step N's outputs transfer to host
    # asynchronously, so the commit/stop/detokenize path for window N runs
    # concurrently with device execution of window N+1 — docs/PERF.md);
    # 1 = the fully synchronous dispatch -> fetch -> commit loop. Greedy
    # and seeded-sampled streams are token-identical at any depth: the
    # engine falls back to a synchronous window whenever committed results
    # change slot membership (stop/eos/abort/length), and logprob /
    # repetition-penalty / spec-decode plans never pipeline. Values > 2
    # only deepen the scheduler's page-allocation lookahead (the in-flight
    # window count stays at one; the page tables staged on device bound
    # how far ahead the engine can run without a host re-plan).
    pipeline_depth: int = 2
    # speculative decoding ("" = off; "ngram" = prompt-lookup drafts;
    # "draft" = a small draft model proposes, engine/spec.py): greedy
    # plans verify up to spec_k draft tokens per target forward — decode
    # is weight-read-bound, so a K+1-token verify costs ~one decode step
    # of HBM traffic and accepted drafts are free throughput. Speculative
    # greedy output is token-for-token the plain greedy output up to
    # floating-point near-ties (exact on CPU/f32; on TPU bf16 the verify
    # and decode programs differ arithmetically, see engine/spec.py).
    # Sampled / logprob / penalty plans and pp meshes use the normal
    # decode window.
    spec_decode: str = ""
    spec_k: int = 4                     # draft tokens verified per forward
    # "draft" mode: the draft model — a registry name ("tiny",
    # "llama3-1b", ...) random-initialized from the engine seed, or an HF
    # checkpoint directory loaded via models/loader. Must share the
    # target's vocabulary (its token ids feed the target's verify).
    spec_draft_model: str = ""
    spec_min_ngram: int = 2             # shortest suffix n-gram to match
    spec_max_ngram: int = 4             # longest suffix n-gram to match
    # speculation-vs-window cost gate: a verify dispatch only beats the
    # fused nw-step window when expected accepted drafts outweigh the
    # window's dispatch amortization — (n_live + ema*drafts)*(nw + r) >
    # n_live*nw*(1 + r), where r is the host-dispatch-to-forward time
    # ratio (conservative default; decode forwards are ~weight-read time).
    # Acceptance ema refreshes via a forced probe every spec_probe_every
    # gate rejections, so a workload that turns lookup-friendly re-enables
    # speculation.
    spec_dispatch_ratio: float = 2.0
    spec_probe_every: int = 32
    # Sarathi-style mixed prefill+decode steps (docs/PERF.md): when
    # requests are waiting while decodes run, the scheduler plans ONE
    # [Bb, Tb] device step holding every running decode slot as a
    # single-token row plus a token-budgeted prefill chunk, so decode
    # emits a token on EVERY step and prefill rides the batch's spare
    # compute instead of preempting it (the aggregated-mode answer to
    # prefill/decode interference — the 3.19x agg-under-churn collapse
    # in BENCH_SELF_r05). The budget is device compute tokens per step:
    # every row is charged the full Tb-wide bucket it occupies (decode
    # rows pad to the chunk's token bucket), and the prefill chunk takes
    # the remainder — the chunk bucket is the largest prefill_buckets
    # rung with Tb * (n_decode_rows + 1) <= mixed_token_budget (the
    # smallest rung when nothing fits, so prefill always progresses).
    # 0 = legacy alternating prefill/decode steps (streak-bounded below).
    # sp>1 engines always use the legacy path (ring-attention prefill
    # cannot share a step with paged decode rows).
    mixed_token_budget: int = 512
    # bounded skip-ahead for the prefill queue: a head blocked on slots
    # or memory no longer blocks later waiting requests that could run —
    # up to this many blocked/mismatched entries are scanned past (queue
    # order itself is never reordered, and the head is reconsidered
    # first on every pass, so it runs as soon as its resources free).
    # 0 = strict head-only (the old head-of-line-blocking behavior).
    prefill_skip_ahead: int = 4
    # KV-cache page quantization knob, mirroring the weight `quant` knob
    # (ModelConfig.quant): "" = pages in the model dtype; "int8" = int8
    # pages + per-row f32 scales end-to-end (capture -> paged read ->
    # offload tiers -> disagg transfer; ops/kv_quant.py). Set here (the
    # deployment surface) it overrides ModelConfig.kv_quant at engine
    # construction. Composes with pipeline_depth=2, mixed steps, tp/dp
    # AND pp meshes (the GPipe stage scan threads the scale-stack shards
    # — models/pp.pp_cache_scale_sharding), and fault injection.
    kv_quant: str = ""
    # COMPAT ALIAS (legacy alternating scheduler only, i.e.
    # mixed_token_budget=0): longest run of consecutive prefill steps
    # while decodes are active; after the streak one decode step runs,
    # so a long prompt can stall running decodes by at most
    # max_prefill_streak chunk-times. Mixed-step scheduling retires the
    # knob — decode rows ride every step, so there is no streak to
    # bound. 0 = unbounded (old prefill-priority).
    max_prefill_streak: int = 2


# -- what a kind of cache is not served with ----------------------------------

# A row a consumer, a column a store beside the plain paged K / V pool
# (ModelConfig.state_leaves, the one-leaf kv_cache_leaves,
# window_cache_leaves). An entry is the reason the store gives, "" where
# the consumer's name says it all, None where the store IS served with it.
# Each consumer moves, shares, shards, packs or rolls back a sequence's
# context as K and V pages of Hkv heads in ONE pool. A recurrent state has
# no page to go with (the state after another sequence's tokens exists
# nowhere; a rejected draft's update cannot be undone), whether its layers
# hold no pages (linear attention) or plain K / V pages beside it (the
# parallel block: the pages alone are half a sequence); a latent cache is
# one leaf of one head; a window pool is a second page list that forgets.
# Prefix reuse is not a row: the scheduler switches it off for a state and
# for a window pool, and says so once in the log. A fourth store is one
# more column.
UNSERVED = (
    # consumer, recurrent state, one-leaf latent cache, window pool
    ("feature", "", "", ""),
    ("another store", None, None, ""),
    ("mesh",
     "the state slots and a share's expert exchange are one device's",
     "the one KV head cannot be sharded",
     "parallel/mesh.kv_shard_layout and the pp / sp programs know one "
     "pool"),
    ("kv_quant", "", "the codec is per K and V row",
     "the codec's scale leaves follow one pool"),
    ("quant", "ops/quant.py names the wq/wk/wv leaves",
     "ops/quant.py names the wq/wk/wv leaves",
     "ops/quant.py names params['layers']"),
    ("decode_kernel",
     "the Pallas kernel's window carries the cache alone",
     "the Pallas kernel reads separate K and V pages",
     "the Pallas kernel has no window and walks one page table"),
    ("vision", "", None, ""),
    ("tiers", "", "", ""),
    ("spec_decode", "a rejected draft's state update has no rollback", None,
     "a verify block is not planned over two page lists"),
)


def refuse_unserved(model_cfg: ModelConfig,
                    engine_cfg: Optional[EngineConfig] = None,
                    mesh=None, feature: str = "") -> None:
    """THE place that says what a model's cache cannot be served with yet
    (`UNSERVED`); a model with plain K / V pages alone passes. The engine
    calls it at construction with its configuration and mesh, and the
    entry points that move whole pages by the names "k" and "v" (disagg
    transfer, the shared pool) call it with `feature` when they are
    reached. The stores are asked in turn (state, latent, window) and the
    first with a reason raises, under its own opening sentence."""
    cfg = model_cfg
    if not (cfg.has_state or cfg.is_mla or cfg.window_pool):
        return      # the page movers ask on every call
    ecfg = engine_cfg or EngineConfig()
    kv_quant = cfg.kv_quant or ecfg.kv_quant
    # consumer -> how this call names it ("": not asked for)
    asked = {
        "feature": feature,
        "another store": "a latent cache or a recurrent state beside the "
        "window layers" * (cfg.is_mla or cfg.has_state),
        "mesh": f"a {dict(mesh.shape)} mesh (--tp/--pp/--ep/--sp/--dp)"
        if mesh is not None and mesh.size > 1 else "",
        "kv_quant": f"kv_quant={kv_quant!r}" * bool(kv_quant),
        "quant": f"quant={cfg.quant!r}" * bool(cfg.quant),
        "decode_kernel": f"decode_kernel={cfg.decode_kernel!r}"
        * (cfg.decode_kernel not in ("auto", "off")),
        "vision": "a vision tower" * (cfg.vision is not None),
        "tiers": "the host / disk KV tiers and streamed decode "
        "(--host-pages, --disk-pages, --stream-pages)" * bool(
            ecfg.host_pages or ecfg.disk_pages or ecfg.stream_pages),
        "spec_decode": f"spec_decode={ecfg.spec_decode!r}"
        * bool(ecfg.spec_decode),
    }
    keeper = "a state-space mixer beside attention keeps" if cfg.has_ssm \
        else "gated short-convolution layers keep a convolution tail," \
        if cfg.has_conv else "power-retention layers keep their whole " \
        "context in" if cfg.has_retention else "linear-attention layers keep"
    # what was only ever about pages has nothing to act on where no layer
    # holds one, and says that instead of its own reason
    pageless = {} if cfg.num_cache_layers else dict.fromkeys(
        ("kv_quant", "decode_kernel"), "no layer of it holds a page")
    stores = (
        (cfg.has_state,
         f"{keeper} a recurrent state a sequence "
         f"({cfg.state_bytes_per_slot()} bytes)"),
        (cfg.is_mla,
         f"latent attention keeps ONE cache leaf of width "
         f"{cfg.latent_width} a token"),
        (cfg.window_pool,
         f"{cfg.num_window_layers} sliding layers keep their last "
         f"{cfg.sliding_window} tokens in a page pool of their own"),
    )
    for column, (held, opening) in enumerate(stores, start=1):
        why = [asked[row[0]] + (f": {reason}" if reason else "")
               for row in UNSERVED
               for reason in [pageless.get(row[0], row[column])]
               if asked[row[0]] and reason is not None]
        if held and why:
            raise ValueError(f"{cfg.name}: {opening}; not served with it "
                             f"yet: " + "; ".join(why))


# -- named architectures ------------------------------------------------------

_CONFIGS = {
    # test-size models
    "tiny": ModelConfig(),
    "tiny-moe": ModelConfig(
        name="tiny-moe", num_experts=4, num_experts_per_tok=2,
        intermediate_size=256,
    ),
    "tiny-vl": ModelConfig(
        name="tiny-vl", dtype="float32",
        vision=VisionConfig(image_size=28, patch_size=14, hidden_size=32,
                            intermediate_size=64, num_layers=2, num_heads=2)),
    # Llama-3.2-1B-class: the single-chip flagship (fits v5e-1 HBM with cache)
    "llama3-1b": ModelConfig(
        name="llama3-1b", vocab_size=128256, hidden_size=2048,
        intermediate_size=8192, num_layers=16, num_heads=32, num_kv_heads=8,
        head_dim=64, rope_theta=500000.0, max_model_len=8192,
    ),
    # DeepSeek-R1-Distill-Llama-8B == Llama-3.1-8B architecture
    "llama3-8b": ModelConfig(
        name="llama3-8b", vocab_size=128256, hidden_size=4096,
        intermediate_size=14336, num_layers=32, num_heads=32, num_kv_heads=8,
        head_dim=128, rope_theta=500000.0, max_model_len=16384,
    ),
    "llama3-70b": ModelConfig(
        name="llama3-70b", vocab_size=128256, hidden_size=8192,
        intermediate_size=28672, num_layers=80, num_heads=64, num_kv_heads=8,
        head_dim=128, rope_theta=500000.0, max_model_len=16384,
    ),
    "mixtral-8x7b": ModelConfig(
        name="mixtral-8x7b", vocab_size=32000, hidden_size=4096,
        intermediate_size=14336, num_layers=32, num_heads=32, num_kv_heads=8,
        head_dim=128, rope_theta=1e6, max_model_len=16384,
        num_experts=8, num_experts_per_tok=2,
    ),
    "qwen2-vl-7b": ModelConfig(
        name="qwen2-vl-7b", vocab_size=152064, hidden_size=3584,
        intermediate_size=18944, num_layers=28, num_heads=28, num_kv_heads=4,
        head_dim=128, rope_theta=1e6, max_model_len=16384,
        vision=VisionConfig(image_size=448, patch_size=14, hidden_size=1280,
                            intermediate_size=3420, num_layers=32,
                            num_heads=16),
    ),
}


def get_model_config(name: str) -> ModelConfig:
    if name not in _CONFIGS:
        raise KeyError(f"unknown model config {name!r}; have {sorted(_CONFIGS)}")
    return _CONFIGS[name]
