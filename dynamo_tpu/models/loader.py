"""HF checkpoint loading: config.json -> ModelConfig, safetensors -> params.

Role of the reference's model sourcing path (reference:
launch/dynamo-run/src/hub.rs HF download + model_card/create.rs building the
MDC from a local HF dir; actual weight loading is delegated to the engines).
Here the engine is ours, so loading is first-class: map HF checkpoint tensor
names (Llama/Qwen2/Mixtral families) onto the stacked-layer functional
params used by models/llama.py, in the engine dtype, ready for device_put
with param_shardings.
"""
from __future__ import annotations

import dataclasses
import glob
import json
import os
from typing import Any, Dict

import numpy as np

from dynamo_tpu.engine.config import ModelConfig

ARCHES = {
    "LlamaForCausalLM": "llama",
    "MistralForCausalLM": "llama",
    "Qwen2ForCausalLM": "qwen2",
    "MixtralForCausalLM": "mixtral",
    "GemmaForCausalLM": "gemma",
    "Gemma2ForCausalLM": "gemma2",
    "Phi3ForCausalLM": "phi3",
    "OlmoeForCausalLM": "olmoe",
    "DeepseekV3ForCausalLM": "deepseek_v3",
    "BailingHybridForCausalLM": "bailing_hybrid",
    "MellumForCausalLM": "mellum",
    "AfmoeForCausalLM": "afmoe",
    "FalconH1ForCausalLM": "falcon_h1",
    "Lfm2ForCausalLM": "lfm2",
    "Lfm2MoeForCausalLM": "lfm2_moe",
    "BrumbyForCausalLM": "brumby",
}
# the families whose sliding layers are served as WINDOWS, from a page
# pool of their own (`window_pool`): their serving length is not capped
WINDOW_POOL_FAMILIES = ("mellum", "afmoe")


def config_from_hf(hf: Dict[str, Any], name: str = "") -> ModelConfig:
    """Map an HF config.json dict onto our ModelConfig."""
    arch = (hf.get("architectures") or ["LlamaForCausalLM"])[0]
    if arch not in ARCHES:
        raise ValueError(f"unsupported architecture {arch!r} "
                         f"(supported: {sorted(ARCHES)})")
    family = ARCHES[arch]
    heads = hf["num_attention_heads"]
    olmoe = family == "olmoe"
    moe = family == "mixtral" or olmoe
    # what a family sets beyond the shared fields below
    own = {"deepseek_v3": deepseek_v3_fields,
           "bailing_hybrid": bailing_hybrid_fields,
           "mellum": mellum_fields,
           "afmoe": afmoe_fields,
           "falcon_h1": falcon_h1_fields,
           "lfm2": lfm2_fields, "lfm2_moe": lfm2_fields,
           "brumby": brumby_fields}.get(
               family, lambda hf: {})(hf)
    if hf.get("clip_qkv") is not None:
        # OLMoE's optional clamp of q/k/v to +-clip_qkv is not modeled:
        # ignoring it would serve another function under the model's name
        raise ValueError(
            f"clip_qkv={hf['clip_qkv']!r} is not supported (only "
            f"clip_qkv: null is modeled)")
    gemma = family in ("gemma", "gemma2")
    gemma2 = family == "gemma2"
    act = hf.get("hidden_activation") or hf.get("hidden_act") or "silu"
    if hf.get("rope_scaling"):
        # e.g. phi-3 128k "longrope", llama-3.1 "llama3" scaling: silently
        # using plain rope_theta would produce wrong logits past the
        # original context, so refuse rather than mis-serve. What IS
        # modelled: YaRN by layer kind, from `rope_parameters` under the
        # `mellum` family (mellum_fields -> models/llama.rope_table), and
        # NO RoPE on a kind (`afmoe`'s full layers, afmoe_fields)
        kind = (hf["rope_scaling"].get("rope_type")
                or hf["rope_scaling"].get("type") or "?")
        raise ValueError(
            f"rope_scaling={kind!r} is not supported: a flat `rope_scaling` "
            f"(longrope, llama3, linear, dynamic, yarn) is modelled for no "
            f"family; YaRN is, per layer kind, where a `mellum` file gives "
            f"it under `rope_parameters`. Use a checkpoint without rope "
            f"scaling (e.g. the base-context variant)")
    max_len = int(hf.get("max_position_embeddings", 2048))
    sliding, layer_types = 0, ()
    if family in WINDOW_POOL_FAMILIES:
        pass   # window layers served as windows: no cap (mellum_fields,
        #        afmoe_fields)
    elif gemma2 and hf.get("sliding_window"):
        # modeled natively: a traced mask width a layer over the shared
        # pool, whatever the pattern (the published one alternates)
        sliding = int(hf["sliding_window"])
        types = hf.get("layer_types")
        if types is not None:
            unknown = set(types) - {"sliding_attention", "full_attention"}
            if unknown or len(types) != hf["num_hidden_layers"]:
                raise ValueError(
                    f"unsupported gemma2 layer_types {sorted(unknown)!r} / "
                    f"{len(types)} entries for {hf['num_hidden_layers']} "
                    f"layers (sliding_attention | full_attention, one a "
                    f"layer)")
            layer_types = tuple(types)
    # Qwen2 configs carry sliding_window but disable it by default
    elif hf.get("sliding_window") and hf.get("use_sliding_window", True):
        # full attention == sliding-window attention while the context
        # fits inside the window. This family's window layers are NOT
        # served as windows (only `mellum`'s and `afmoe`'s are, from a
        # page pool of their own; Gemma-2's are a mask), so the serving
        # length is
        # capped at the window: models like phi-3-mini-4k (window 2047) /
        # mistral-v0.1 (4096) stay exact instead of silently diverging
        # past it
        max_len = min(max_len, int(hf["sliding_window"]))
    return dataclasses.replace(ModelConfig(
        name=name or hf.get("model_type", family),
        vocab_size=hf["vocab_size"],
        hidden_size=hf["hidden_size"],
        intermediate_size=hf["intermediate_size"],
        num_layers=hf["num_hidden_layers"],
        num_heads=heads,
        num_kv_heads=hf.get("num_key_value_heads", heads),
        head_dim=hf.get("head_dim") or hf["hidden_size"] // heads,
        rope_theta=float(hf.get("rope_theta", 10000.0)),
        rms_norm_eps=float(hf.get("rms_norm_eps", 1e-5)),
        max_model_len=max_len,
        # GemmaConfig ties embeddings by default and often omits the key
        tie_word_embeddings=bool(hf.get("tie_word_embeddings", gemma)),
        attn_bias=(family == "qwen2") or bool(hf.get("attention_bias",
                                                     False)),
        embed_scale=float(hf["hidden_size"]) ** 0.5 if gemma else 0.0,
        norm_plus_one=gemma,
        mlp_act="gelu_tanh" if act in ("gelu_pytorch_tanh", "gelu_tanh",
                                       "gelu") else "silu",
        post_norms=gemma2,
        attn_softcap=float(hf.get("attn_logit_softcapping") or 0.0)
        if gemma2 else 0.0,
        final_softcap=float(hf.get("final_logit_softcapping") or 0.0)
        if gemma2 else 0.0,
        query_scale=float(hf.get("query_pre_attn_scalar", 0)) ** -0.5
        if gemma2 and hf.get("query_pre_attn_scalar") else 0.0,
        sliding_window=sliding,
        layer_types=layer_types,
        # Mixtral counts its experts in `num_local_experts`, OLMoE in
        # `num_experts`; in both `intermediate_size` is ONE expert's width
        num_experts=int(hf.get("num_experts" if olmoe
                               else "num_local_experts", 0)) if moe else 0,
        num_experts_per_tok=int(hf.get("num_experts_per_tok", 2)),
        # Mixtral always rescales the k kept router weights; OLMoE says
        # (published: false, the weights stay a slice of the full softmax)
        norm_topk_prob=bool(hf.get("norm_topk_prob", False)) if olmoe
        else True,
        qk_norm=olmoe,
    ), **own)


def _refuser(hf: Dict[str, Any]):
    """refuse(key, ok, modelled): raise, naming the key, where the file's
    value of `key` is not one that `ok` accepts."""
    def refuse(key, ok, modelled):
        if not ok(hf.get(key)):
            raise ValueError(f"{key}={hf.get(key)!r} is not supported "
                             f"(only {modelled} is modelled)")
    return refuse


def _layer_types(hf: Dict[str, Any], unread: str) -> tuple:
    """A window-pool family's `layer_types`, one of sliding_attention |
    full_attention a layer; `unread`: the key beside it that is not read."""
    layers = int(hf["num_hidden_layers"])
    types = hf.get("layer_types")
    if not types or len(types) != layers or \
            set(types) - {"sliding_attention", "full_attention"}:
        raise ValueError(
            f"layer_types={types!r}: one of sliding_attention | "
            f"full_attention a layer ({layers}) is what is modelled "
            f"({unread} is not read: layer_types governs)")
    return tuple(types)


def deepseek_v3_fields(hf: Dict[str, Any]) -> Dict[str, Any]:
    """The ModelConfig fields of a `DeepseekV3ForCausalLM` config.json
    (Moonlight-16B-A3B): latent attention without a query LoRA, a leading
    run of dense layers, then routed experts behind a sigmoid router with
    a selection bias, plus shared experts. What is not modelled is
    refused here, not mis-served."""
    refuse = _refuser(hf)
    refuse("q_lora_rank", lambda v: v is None, "q_lora_rank: null")
    refuse("n_group", lambda v: v in (None, 1), "one expert group")
    refuse("topk_group", lambda v: v in (None, 1), "one expert group")
    refuse("num_nextn_predict_layers", lambda v: not v,
           "no multi-token-prediction layers")
    refuse("scoring_func", lambda v: v in ("sigmoid", "softmax"),
           "sigmoid or softmax scoring")
    refuse("topk_method", lambda v: v in ("noaux_tc", "greedy"),
           "noaux_tc or greedy top-k")
    refuse("moe_layer_freq", lambda v: v in (None, 1),
           "an expert block in every layer after the dense lead")
    refuse("attention_bias", lambda v: not v, "no attention bias")
    if hf.get("num_key_value_heads", hf["num_attention_heads"]) \
            != hf["num_attention_heads"]:
        raise ValueError("latent attention has one key/value set a query "
                         "head: num_key_value_heads must equal "
                         "num_attention_heads")
    dn, dr = int(hf["qk_nope_head_dim"]), int(hf["qk_rope_head_dim"])
    return dict(
        head_dim=int(hf["v_head_dim"]),
        kv_lora_rank=int(hf["kv_lora_rank"]),
        qk_nope_head_dim=dn, qk_rope_head_dim=dr,
        query_scale=float(dn + dr) ** -0.5,
        num_experts=int(hf["n_routed_experts"]),
        intermediate_size=int(hf["moe_intermediate_size"]),
        dense_intermediate_size=int(hf["intermediate_size"]),
        first_dense_layers=int(hf.get("first_k_dense_replace", 0)),
        shared_expert_size=int(hf.get("n_shared_experts") or 0)
        * int(hf["moe_intermediate_size"]),
        norm_topk_prob=bool(hf.get("norm_topk_prob", False)),
        moe_scoring=hf["scoring_func"],
        moe_router_bias=hf["topk_method"] == "noaux_tc",
        moe_routed_scale=float(hf.get("routed_scaling_factor", 1.0)))


def bailing_hybrid_fields(hf: Dict[str, Any]) -> Dict[str, Any]:
    """The ModelConfig fields of a `bailing_hybrid` config.json (the
    language model of Ling-3.0-flash-VL): periods of `layer_group_size`
    layers, Kimi Delta Attention in all but each period's last, which has
    latent attention (no query LoRA) with a QK-norm and a head-wise
    output gate; leading dense MLPs, then routed experts behind a
    sigmoid router with a selection bias and a group-limited pick, plus
    shared experts. `num_experts` may be a chip's SHARE of the router's
    `num_experts_published` (from `expert_first` on). What is not
    modelled is refused here, by key, not mis-served."""
    refuse = _refuser(hf)
    refuse("q_lora_rank", lambda v: v is None, "q_lora_rank: null")
    for key in ("use_kda_lora", "use_mla_nope", "use_nGPT", "value_norm",
                "up_proj_norm", "scale_router_input", "use_bias",
                "use_qkv_bias", "mtp_use_kda"):
        refuse(key, lambda v: not v, f"{key}: false")
    refuse("no_kda_lora", lambda v: v in (None, True), "full-rank KDA gates")
    refuse("kda_safe_gate", lambda v: v in (None, True),
           "the lower-bound gate")
    refuse("linear_silu", lambda v: v in (None, True),
           "SiLU after the short convolution")
    refuse("score_function", lambda v: v == "sigmoid", "sigmoid scoring")
    refuse("gated_attention_proj_granularity_type",
           lambda v: v == "head_wise", "a head-wise attention gate")
    refuse("group_norm_size", lambda v: v in (None, 1), "group_norm_size 1")
    refuse("num_nextn_predict_layers", lambda v: not v,
           "no multi-token-prediction layers")
    refuse("num_kv_heads_for_linear_attn", lambda v: not v,
           "as many linear-attention key/value heads as query heads")
    refuse("rope_scaling", lambda v: not v, "no rope scaling")
    refuse("norm_topk_prob", lambda v: v in (None, True),
           "renormalised router weights")
    heads, layers = hf["num_attention_heads"], hf["num_hidden_layers"]
    if hf.get("num_key_value_heads", heads) != heads:
        raise ValueError("latent attention has one key/value set a query "
                         "head: num_key_value_heads must equal "
                         "num_attention_heads")
    for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        if any((hf.get(key) or [])[:layers]):
            raise ValueError(
                f"{key} is nonzero at one of the {layers} layers kept: "
                f"the clamped SwiGLU is not modelled")
    dn, dr = int(hf["qk_nope_head_dim"]), int(hf["qk_rope_head_dim"])
    held = int(hf["num_experts"])
    published = int(hf.get("num_experts_published", held))
    width = int(hf["moe_intermediate_size"])
    return dict(
        head_dim=int(hf["v_head_dim"]),
        kv_lora_rank=int(hf["kv_lora_rank"]),
        qk_nope_head_dim=dn, qk_rope_head_dim=dr,
        query_scale=float(dn + dr) ** -0.5,
        mla_qk_norm=bool(hf.get("use_qk_norm", False)), mla_gate=True,
        linear_group_size=int(hf["layer_group_size"]),
        linear_head_dim=int(hf["head_dim"]),
        linear_conv_size=int(hf.get("short_conv_kernel_size", 4)),
        linear_gate_lower_bound=float(hf.get("kda_lower_bound", -5)),
        num_experts=published,
        experts_held=held if held < published else 0,
        expert_first=int(hf.get("expert_first", 0)),
        moe_n_group=int(hf.get("n_group") or 1),
        moe_topk_group=int(hf.get("topk_group") or 1),
        intermediate_size=width,
        dense_intermediate_size=int(hf["intermediate_size"]),
        first_dense_layers=int(hf.get("first_k_dense_replace", 0)),
        shared_expert_size=int(
            hf.get("moe_shared_expert_intermediate_size")
            or int(hf.get("num_shared_experts") or 0) * width),
        norm_topk_prob=True, moe_scoring="sigmoid",
        moe_router_bias=bool(hf.get("moe_router_enable_expert_bias")),
        moe_routed_scale=float(hf.get("routed_scaling_factor", 1.0)),
        qk_norm=False)


def mellum_fields(hf: Dict[str, Any]) -> Dict[str, Any]:
    """The ModelConfig fields of a `mellum` config.json
    (Mellum2-12B-A2.5B): GQA in every layer, `layer_types` saying which
    layers attend inside `sliding_window` (served from the window pool,
    `window_pool`) and which over the whole context, a RoPE a layer kind
    from `rope_parameters` (plain on the sliding layers, YaRN on the full
    ones), and a softmax router over `num_experts` experts of
    `moe_intermediate_size` in every layer, the k largest renormalised.
    No key of THIS family's file declares a QK-norm, a shared expert, a
    selection bias, a dense lead or a multi-token-prediction head, so
    none is read; what a later `mellum` file says otherwise is refused
    here, by key, not mis-served. (A window-pool model CAN have a head's
    QK-norm, an output gate, a sigmoid router with a selection bias, a
    shared expert and a dense lead: `afmoe_fields` maps them.)"""
    refuse = _refuser(hf)
    types = _layer_types(hf, "max_window_layers")
    layers = len(types)
    refuse("mlp_layer_types",
           lambda v: v is None or (len(v) == layers
                                   and set(v) == {"sparse"}),
           "an expert block in every layer (all 'sparse')")
    refuse("use_sliding_window", lambda v: v in (None, True),
           "use_sliding_window: true")
    refuse("attention_bias", lambda v: not v, "no attention bias")
    refuse("hidden_act", lambda v: v in (None, "silu"), "silu")
    refuse("norm_topk_prob", lambda v: v in (None, True),
           "renormalised router weights")
    for key in ("use_qk_norm", "qk_norm", "n_shared_experts",
                "num_shared_experts", "shared_expert_intermediate_size",
                "num_nextn_predict_layers", "mtp_num_layers",
                "moe_router_enable_expert_bias", "attn_logit_softcapping",
                "final_logit_softcapping"):
        refuse(key, lambda v: not v, f"{key} absent")
    refuse("routed_scaling_factor", lambda v: v in (None, 1, 1.0),
           "no scale on the routed output")
    if not hf.get("sliding_window"):
        raise ValueError("a mellum file states its sliding_window")
    ropes = hf.get("rope_parameters") or {}
    if set(ropes) != {"full_attention", "sliding_attention"}:
        raise ValueError(
            f"rope_parameters keys {sorted(ropes)!r}: one entry for "
            f"full_attention and one for sliding_attention is what is "
            f"modelled")
    return dict(
        sliding_window=int(hf["sliding_window"]),
        layer_types=types, window_pool=True,
        rope_full=rope_params(ropes["full_attention"], hf),
        rope_sliding=rope_params(ropes["sliding_attention"], hf),
        rope_theta=float(ropes["full_attention"]["rope_theta"]),
        num_experts=int(hf["num_experts"]),
        intermediate_size=int(hf["moe_intermediate_size"]),
        norm_topk_prob=True)


def afmoe_fields(hf: Dict[str, Any]) -> Dict[str, Any]:
    """The ModelConfig fields of an `afmoe` config.json (Trinity-Mini):
    GQA in every layer with an RMSNorm over each head's q and k and a
    sigmoid output gate; `layer_types` saying which layers attend inside
    `sliding_window` (served from the window pool) and which over the
    whole context; RoPE at `rope_theta` on the SLIDING layers and no
    positional embedding on the full ones; four norms a block; the
    embeddings times sqrt(hidden_size) under `mup_enabled`;
    `num_dense_layers` leading layers with a dense MLP of
    `intermediate_size`, then `num_experts` routed experts of
    `moe_intermediate_size` behind a sigmoid (or softmax) router with a
    selection bias, the k picked renormalised under `route_norm` and
    scaled by `route_scale`, plus `num_shared_experts` shared ones.
    What no key states (the gate, the head norm, no RoPE on the full
    layers, the four norms, the selection bias) is the family's
    published modelling code. Every key of the catalog row's config is
    either mapped, or named here as not read, or refused."""
    refuse = _refuser(hf)
    types = _layer_types(hf, "global_attn_every_n_layers")
    layers = len(types)
    # not read: `global_attn_every_n_layers` (layer_types governs),
    # `load_balance_coeff` (training), `use_grouped_mm` (an
    # implementation's choice of kernel)
    for key in ("n_group", "num_expert_groups", "topk_group",
                "num_limited_groups"):
        refuse(key, lambda v: v in (None, 1), "one expert group")
    refuse("hidden_act", lambda v: v in (None, "silu"), "silu")
    refuse("score_func", lambda v: v in ("sigmoid", "softmax"),
           "sigmoid or softmax scoring")
    refuse("tie_word_embeddings", lambda v: not v, "an untied head")
    refuse("attention_bias", lambda v: not v, "no attention bias")
    refuse("use_sliding_window", lambda v: v in (None, True),
           "use_sliding_window: true")
    for key in ("num_nextn_predict_layers", "mtp_num_layers",
                "attn_logit_softcapping", "final_logit_softcapping",
                "q_lora_rank", "kv_lora_rank"):
        refuse(key, lambda v: not v, f"{key} absent")
    if not hf.get("sliding_window"):
        raise ValueError("an afmoe file states its sliding_window")
    lead = int(hf.get("num_dense_layers") or 0)
    if not 0 <= lead < layers:
        raise ValueError(f"num_dense_layers={lead} of {layers} layers: "
                         f"at least one expert layer behind the lead is "
                         f"what is modelled")
    from dynamo_tpu.engine.config import RopeParams
    theta = float(hf.get("rope_theta", 10000.0))
    width = int(hf["moe_intermediate_size"])
    return dict(
        sliding_window=int(hf["sliding_window"]),
        layer_types=types, window_pool=True,
        rope_sliding=RopeParams(theta=theta),
        rope_full=RopeParams(theta=theta, rope_type="none"),
        qk_norm="head", attn_out_gate=True, post_norms=True,
        embed_scale=float(hf["hidden_size"]) ** 0.5
        if hf.get("mup_enabled") else 0.0,
        num_experts=int(hf["num_experts"]),
        intermediate_size=width,
        dense_intermediate_size=int(hf["intermediate_size"]),
        first_dense_layers=lead,
        shared_expert_size=int(hf.get("num_shared_experts") or 0) * width,
        norm_topk_prob=bool(hf.get("route_norm", True)),
        moe_scoring=hf["score_func"], moe_router_bias=True,
        moe_routed_scale=float(hf.get("route_scale", 1.0)))


def falcon_h1_fields(hf: Dict[str, Any]) -> Dict[str, Any]:
    """The ModelConfig fields of a `falcon_h1` config.json (Falcon-H1):
    every block a Mamba-2 mixer BESIDE grouped-query attention on the one
    normed input (`mamba_d_ssm` > 0: layer kind "par"), a dense SwiGLU
    behind it, plain RoPE at `rope_theta`, and muP multipliers on every
    branch, each kept as the field of its name (`embedding_multiplier` is
    `embed_scale`). What is not modelled is refused here, by key."""
    refuse = _refuser(hf)
    for key in ("attention_bias", "mamba_proj_bias", "mlp_bias",
                "projectors_bias", "mamba_norm_before_gate"):
        refuse(key, lambda v: not v, f"{key}: false")
    refuse("mamba_rms_norm", lambda v: v in (None, True),
           "the gated RMSNorm after the scan")
    refuse("mamba_conv_bias", lambda v: v in (None, True),
           "a bias on the mixer's convolution")
    refuse("mamba_use_mlp", lambda v: v in (None, True),
           "an MLP in every block")
    refuse("attn_layer_indices", lambda v: v is None,
           "attention in every block")
    refuse("hidden_act", lambda v: v in (None, "silu"), "SiLU")
    heads, d_head = int(hf["mamba_n_heads"]), int(hf["mamba_d_head"])
    d_ssm = hf.get("mamba_d_ssm")
    if d_ssm is None:
        d_ssm = int(hf["mamba_expand"] * hf["hidden_size"])
    if heads * d_head != d_ssm or heads % int(hf["mamba_n_groups"]):
        raise ValueError(
            f"mamba_n_heads {heads} x mamba_d_head {d_head} must be "
            f"mamba_d_ssm {d_ssm}, the heads a multiple of mamba_n_groups "
            f"{hf['mamba_n_groups']}")
    return dict(
        mamba_d_ssm=int(d_ssm), mamba_n_heads=heads, mamba_d_head=d_head,
        mamba_n_groups=int(hf["mamba_n_groups"]),
        mamba_d_state=int(hf["mamba_d_state"]),
        mamba_d_conv=int(hf.get("mamba_d_conv", 4)),
        mamba_chunk_size=int(hf.get("mamba_chunk_size", 128)),
        embed_scale=float(hf.get("embedding_multiplier", 1.0)),
        lm_head_multiplier=float(hf.get("lm_head_multiplier", 1.0)),
        attention_in_multiplier=float(
            hf.get("attention_in_multiplier", 1.0)),
        key_multiplier=float(hf.get("key_multiplier", 1.0)),
        attention_out_multiplier=float(
            hf.get("attention_out_multiplier", 1.0)),
        ssm_in_multiplier=float(hf.get("ssm_in_multiplier", 1.0)),
        ssm_out_multiplier=float(hf.get("ssm_out_multiplier", 1.0)),
        ssm_multipliers=tuple(
            float(m) for m in hf.get("ssm_multipliers") or (1.0,) * 5),
        mlp_multipliers=tuple(
            float(m) for m in hf.get("mlp_multipliers") or (1.0, 1.0)))


def lfm2_fields(hf: Dict[str, Any]) -> Dict[str, Any]:
    """The ModelConfig fields of an `lfm2` / `lfm2_moe` config.json
    (LFM2-8B-A1B): `layer_types` saying which layers are gated short
    convolutions of `conv_L_cache` taps ("conv": layer kind "conv", a
    tail and no pages) and which grouped-query attention with an RMSNorm
    over each head's q and k ("full_attention"); RMSNorm eps under the
    key `norm_eps`; the head tied to the embedding table unless the file
    says otherwise (the family's default: `Lfm2Config`). `lfm2_moe`:
    `num_dense_layers` leading layers with a dense SwiGLU of
    `intermediate_size`, then `num_experts` experts of
    `moe_intermediate_size` behind a sigmoid router whose `expert_bias`
    (`use_expert_bias`) picks and does not weigh, the k picked
    renormalised over their sum + 1e-6 under `norm_topk_prob` and scaled
    by `routed_scaling_factor` (the expert block is the published class's
    code: `transformers` here has the dense family alone). `lfm2` (the
    dense family): a SwiGLU in every layer, its width
    `intermediate_size` as `block_auto_adjust_ff_dim` adjusts it. What is
    not modelled is refused here, by key."""
    refuse = _refuser(hf)
    layers = int(hf["num_hidden_layers"])
    types = hf.get("layer_types")
    if types is None and hf.get("full_attn_idxs") is not None:
        types = ["full_attention" if i in hf["full_attn_idxs"] else "conv"
                 for i in range(layers)]
    if not types or len(types) != layers or \
            set(types) - {"conv", "full_attention"}:
        raise ValueError(
            f"layer_types={types!r}: one of conv | full_attention a layer "
            f"({layers}) is what is modelled")
    refuse("conv_bias", lambda v: not v,
           "conv_bias: false (no bias on the convolution and its two "
           "projections)")
    refuse("conv_L_cache", lambda v: v is None or int(v) >= 2,
           "a convolution of at least two taps")
    refuse("attention_bias", lambda v: not v, "no attention bias")
    for key in ("n_group", "topk_group"):
        refuse(key, lambda v: v in (None, 1), "one expert group")
    for key in ("num_shared_experts", "n_shared_experts",
                "num_nextn_predict_layers", "sliding_window"):
        refuse(key, lambda v: not v, f"{key} absent")
    moe = hf.get("model_type") == "lfm2_moe" or "num_experts" in hf
    width = int(hf.get("block_ff_dim", hf["intermediate_size"]))
    # a file without a conv layer is plain attention throughout
    conv = "conv" in types
    own = dict(
        layer_types=tuple(types) if conv else (),
        conv_l_cache=int(hf.get("conv_L_cache", 3)) if conv else 0,
        qk_norm="head",
        rms_norm_eps=float(hf.get("norm_eps", 1e-5)),
        rope_theta=float(hf.get("rope_theta", hf.get("theta", 1e6))),
        tie_word_embeddings=bool(hf.get(
            "tie_word_embeddings", hf.get("tie_embedding", True))))
    if not moe:
        if hf.get("block_auto_adjust_ff_dim", True):
            # `Lfm2MLP`'s own arithmetic
            width = int(2 * width / 3)
            if hf.get("block_ffn_dim_multiplier", 1.0) is not None:
                width = int(hf.get("block_ffn_dim_multiplier", 1.0) * width)
                multiple = int(hf.get("block_multiple_of", 256))
                width = multiple * ((width + multiple - 1) // multiple)
        return dict(own, intermediate_size=width)
    refuse("block_auto_adjust_ff_dim", lambda v: not v,
           "the widths as the file gives them")
    lead = int(hf.get("num_dense_layers") or 0)
    if not 0 <= lead < layers:
        raise ValueError(f"num_dense_layers={lead} of {layers} layers: at "
                         f"least one expert layer behind the lead is what "
                         f"is modelled")
    return dict(
        own, num_experts=int(hf["num_experts"]),
        intermediate_size=int(hf["moe_intermediate_size"]),
        dense_intermediate_size=width, first_dense_layers=lead,
        norm_topk_prob=bool(hf.get("norm_topk_prob", True)),
        moe_scoring="sigmoid", moe_renorm_eps=1e-6,
        moe_router_bias=bool(hf.get("use_expert_bias", True)),
        moe_routed_scale=float(hf.get("routed_scaling_factor", 1.0)))


def brumby_fields(hf: Dict[str, Any]) -> Dict[str, Any]:
    """The ModelConfig fields of a `brumby` config.json (Brumby-14B-Base):
    Qwen3's block (head-wise QK-norm, RoPE at `rope_theta`, no bias) with
    every layer's attention replaced by power retention. The published
    file carries no key of the mixer itself: its degree (2) and its gate
    are what benchmark/configs/brumby-14b/meta.json lists under
    `assumed`; a file that does name a degree must name 2. It keeps
    Qwen2's window keys, switched off (`use_sliding_window` false,
    `max_window_layers` = all): a window over a state that holds the
    whole context is not modelled, and a file that asks for one is
    refused."""
    refuse = _refuser(hf)
    refuse("use_sliding_window", lambda v: not v, "false")
    for key in ("retention_degree", "power_degree", "degree"):
        refuse(key, lambda v: v in (None, 2), "2")
    return dict(qk_norm="head", retention_degree=2)


def rope_params(entry: Dict[str, Any], hf: Dict[str, Any]):
    """One entry of `rope_parameters` -> RopeParams. Plain RoPE
    ("default") and YaRN are modelled; longrope, llama3, linear and
    dynamic scaling are refused by name."""
    from dynamo_tpu.engine.config import RopeParams
    kind = entry.get("rope_type") or entry.get("type") or "default"
    theta = float(entry.get("rope_theta", hf.get("rope_theta", 10000.0)))
    if kind == "default":
        return RopeParams(theta=theta)
    if kind != "yarn":
        raise ValueError(
            f"rope_type={kind!r} is not supported (default and yarn are "
            f"modelled; longrope, llama3, linear and dynamic are not)")
    for key in ("mscale", "mscale_all_dim"):
        if entry.get(key):
            raise ValueError(f"yarn {key}={entry[key]!r} is not supported "
                             f"(attention_factor, or its default 0.1 "
                             f"ln(factor) + 1, is what is modelled)")
    if entry.get("truncate") is False:
        raise ValueError("yarn truncate=false is not supported")
    return RopeParams(
        theta=theta, rope_type="yarn", factor=float(entry["factor"]),
        original_max_position=int(
            entry.get("original_max_position_embeddings")
            or hf["max_position_embeddings"]),
        beta_fast=float(entry.get("beta_fast") or 32.0),
        beta_slow=float(entry.get("beta_slow") or 1.0),
        attention_factor=float(entry.get("attention_factor") or 0.0))


def _read_all_tensors(path: str) -> Dict[str, np.ndarray]:
    from safetensors import safe_open
    files = sorted(glob.glob(os.path.join(path, "*.safetensors")))
    if not files:
        raise FileNotFoundError(f"no *.safetensors under {path}")
    out: Dict[str, np.ndarray] = {}
    for f in files:
        with safe_open(f, framework="np") as st:
            for key in st.keys():
                out[key] = st.get_tensor(key)
    return out


def load_params_from_hf(path: str, cfg: ModelConfig,
                        dtype: str = "") -> Dict[str, Any]:
    """Read an HF-style dir into our stacked-layer params pytree (numpy).

    Tensor name mapping (HF stores projections as [out, in]; ours are
    [in, out], hence the transposes):
      model.embed_tokens.weight          -> embed
      model.layers.{i}.input_layernorm   -> attn_norm[i]
      .self_attn.{q,k,v}_proj.weight(.T) -> wq/wk/wv[i] (+ .bias -> w*_b)
      .self_attn.o_proj.weight.T         -> wo[i]
      .post_attention_layernorm          -> mlp_norm[i]
      .mlp.{gate,up,down}_proj.weight.T  -> w_gate/w_up/w_down[i]
      .block_sparse_moe.gate.weight.T    -> router[i]        (Mixtral)
      .block_sparse_moe.experts.{e}.w{1,3,2}.T -> w_gate/up/down[i,e]
      .mlp.gate.weight.T                 -> router[i]        (OLMoE)
      .mlp.experts.{e}.{gate,up,down}_proj.weight.T -> w_gate/up/down[i,e]
      .self_attn.{q,k}_norm.weight       -> q_norm/k_norm[i] (OLMoE)
    DeepseekV3 (latent attention, `_load_deepseek_v3`; the layers before
    `first_k_dense_replace` go to `dense_layers`, the rest to `layers`):
      .self_attn.q_proj.weight.T         -> wq[i], rope columns of every
                                            head de-interleaved
      .self_attn.kv_a_proj_with_mqa.weight.T -> wkv_a[i], likewise
      .self_attn.kv_a_layernorm.weight   -> kv_a_norm[i]
      .self_attn.kv_b_proj.weight.T      -> wkv_b[i]
      .mlp.gate.weight.T / .mlp.gate.e_score_correction_bias
                                         -> router[i] / router_bias[i]
      .mlp.experts.{e}.{gate,up,down}_proj.weight.T -> w_gate/up/down[i,e]
      .mlp.shared_experts.{gate,up,down}_proj.weight.T -> ws_gate/up/down
      model.norm.weight                  -> final_norm
      lm_head.weight.T                   -> lm_head (absent when tied)
    FalconH1 (`transformers`' own names; the attention leaves as above):
      .pre_ff_layernorm / model.final_layernorm -> mlp_norm[i] / final_norm
      .feed_forward.{gate,up,down}_proj.weight.T -> w_gate/w_up/w_down[i]
      .mamba.in_proj.weight.T            -> ssm_in[i]  (z | x | B | C | dt)
      .mamba.conv1d.weight [C, 1, K] / .bias -> ssm_conv_w[i] [K, C] /
                                            ssm_conv_b[i]
      .mamba.{A_log,D,dt_bias}           -> ssm_a_log / ssm_d /
                                            ssm_dt_bias[i], float32
      .mamba.norm.weight / .out_proj.weight.T -> ssm_norm[i] / ssm_out[i]
    Brumby (Qwen3's names; the head norms as OLMoE's, one weight of
    head_dim each; the gate's name assumed):
      .self_attn.g_proj.weight.T / .bias -> ret_wg[i] / ret_bg[i] (float32;
                                            zeros where the file has none)
    """
    if cfg.linear_group_size or cfg.window_pool:
        # the catalog gives these families' configs and no tensor names:
        # a guessed mapping would serve another function under its name
        raise ValueError(
            f"{cfg.name}: no checkpoint mapping for a model with "
            f"linear-attention layers or a window pool (its tensor names "
            f"are not known); remove the *.safetensors to serve seeded "
            f"weights")
    if cfg.has_conv and cfg.is_moe:
        # `transformers` here has the dense `lfm2` family alone: the
        # expert block's tensor names are not known
        raise ValueError(
            f"{cfg.name}: no checkpoint mapping for the expert block of a "
            f"model with conv layers (its tensor names are not known); "
            f"remove the *.safetensors to serve seeded weights")
    import jax.numpy as jnp
    dt = jnp.empty((), dtype or cfg.dtype).dtype
    raw = _read_all_tensors(path)

    def t(name):  # transposed projection in target dtype
        return np.asarray(raw[name].T, dtype=dt)

    def w(name):
        return np.asarray(raw[name], dtype=dt)

    def stack(fn):
        return np.stack([fn(i) for i in range(cfg.num_layers)])

    if cfg.is_mla:
        return _load_deepseek_v3(raw, cfg, t, w)
    if cfg.has_conv:
        return _load_lfm2(raw, cfg, t, w)

    fused_qkv = "model.layers.0.self_attn.qkv_proj.weight" in raw  # Phi-3
    # transformers' `falcon_h1` layout, told by the file's own tensor names
    # as Phi-3's and OLMoE's are, not by the mechanism: another model with
    # a state-space mixer brings its own names, and is refused until
    # they are written here
    falcon_h1 = "model.layers.0.mamba.in_proj.weight" in raw
    if cfg.has_ssm and not falcon_h1:
        raise ValueError(
            f"{cfg.name}: a state-space mixer whose tensors are not named "
            f"as transformers' falcon_h1 names them (model.layers.N.mamba."
            f"in_proj.weight): no checkpoint mapping for it")
    qo, ko = cfg.num_heads * cfg.head_dim, cfg.num_kv_heads * cfg.head_dim

    def qkv(i, part):  # split Phi-3's fused [q|k|v, in] rows, then transpose
        full = raw[f"model.layers.{i}.self_attn.qkv_proj.weight"]
        lo, hi = {"q": (0, qo), "k": (qo, qo + ko),
                  "v": (qo + ko, qo + 2 * ko)}[part]
        return np.asarray(full[lo:hi].T, dtype=dt)

    layers: Dict[str, Any] = {
        "attn_norm": stack(
            lambda i: w(f"model.layers.{i}.input_layernorm.weight")),
        "wq": stack((lambda i: qkv(i, "q")) if fused_qkv else
                    (lambda i: t(f"model.layers.{i}.self_attn.q_proj.weight"))),
        "wk": stack((lambda i: qkv(i, "k")) if fused_qkv else
                    (lambda i: t(f"model.layers.{i}.self_attn.k_proj.weight"))),
        "wv": stack((lambda i: qkv(i, "v")) if fused_qkv else
                    (lambda i: t(f"model.layers.{i}.self_attn.v_proj.weight"))),
        "wo": stack(lambda i: t(f"model.layers.{i}.self_attn.o_proj.weight")),
        # in llama-family checkpoints post_attention_layernorm is the
        # PRE-MLP norm; in gemma2 (post_norms) it is a true post-attention
        # norm and pre_feedforward_layernorm takes the pre-MLP role
        "mlp_norm": stack(
            lambda i: w(f"model.layers.{i}.pre_feedforward_layernorm.weight"
                        if cfg.post_norms else
                        f"model.layers.{i}.pre_ff_layernorm.weight"
                        if falcon_h1 else
                        f"model.layers.{i}.post_attention_layernorm.weight")),
    }
    mlp = "feed_forward" if falcon_h1 else "mlp"
    if falcon_h1:
        mamba = "model.layers.{}.mamba."
        layers.update({
            "ssm_in": stack(lambda i: t(mamba.format(i) + "in_proj.weight")),
            "ssm_conv_w": stack(lambda i: np.asarray(
                raw[mamba.format(i) + "conv1d.weight"][:, 0, :].T, dtype=dt)),
            "ssm_conv_b": stack(lambda i: w(mamba.format(i) + "conv1d.bias")),
            "ssm_norm": stack(lambda i: w(mamba.format(i) + "norm.weight")),
            "ssm_out": stack(
                lambda i: t(mamba.format(i) + "out_proj.weight")),
        })
        for ours, theirs in (("ssm_a_log", "A_log"), ("ssm_d", "D"),
                             ("ssm_dt_bias", "dt_bias")):
            layers[ours] = stack(lambda i, p=theirs: np.asarray(
                raw[mamba.format(i) + p], np.float32))
    if cfg.has_retention:
        # Qwen3's names for everything else; the gate's are ASSUMED
        # (`g_proj`, a Linear to one logit a key-value head, its bias
        # optional): the catalog gives the config and no tensor names
        gate = "model.layers.{}.self_attn.g_proj."
        if gate.format(0) + "weight" not in raw:
            raise ValueError(
                f"{cfg.name}: no tensor {gate.format(0)}weight: the "
                f"power-retention gate's projection is looked for under "
                f"that (assumed) name, [num_key_value_heads, hidden_size]")
        layers["ret_wg"] = stack(lambda i: t(gate.format(i) + "weight"))
        layers["ret_bg"] = stack(lambda i: np.asarray(
            raw.get(gate.format(i) + "bias",
                    np.zeros(cfg.num_kv_heads)), np.float32))
    if cfg.post_norms:
        layers["post_attn_norm"] = stack(
            lambda i: w(f"model.layers.{i}.post_attention_layernorm.weight"))
        layers["post_mlp_norm"] = stack(
            lambda i: w(f"model.layers.{i}.post_feedforward_layernorm.weight"))
    if cfg.attn_bias:
        for ours, theirs in (("wq_b", "q_proj"), ("wk_b", "k_proj"),
                             ("wv_b", "v_proj")):
            layers[ours] = stack(
                lambda i, p=theirs:
                w(f"model.layers.{i}.self_attn.{p}.bias"))
    if cfg.qk_norm:
        for ours in ("q_norm", "k_norm"):
            layers[ours] = stack(
                lambda i, n=ours:
                w(f"model.layers.{i}.self_attn.{n}.weight"))
    if cfg.is_moe:
        if "model.layers.0.mlp.gate.weight" in raw:          # OLMoE names
            moe = "model.layers.{}.mlp"
            names = (("w_gate", "gate_proj"), ("w_up", "up_proj"),
                     ("w_down", "down_proj"))
        else:                                                # Mixtral
            moe = "model.layers.{}.block_sparse_moe"
            names = (("w_gate", "w1"), ("w_up", "w3"), ("w_down", "w2"))
        layers["router"] = stack(
            lambda i: t(moe.format(i) + ".gate.weight"))
        for ours, theirs in names:
            layers[ours] = np.stack([
                np.stack([t(moe.format(i) + f".experts.{e}.{theirs}.weight")
                          for e in range(cfg.num_experts)])
                for i in range(cfg.num_layers)])
    elif "model.layers.0.mlp.gate_up_proj.weight" in raw:  # Phi-3 fused GLU
        f = cfg.intermediate_size

        def gate_up(i, lo, hi):
            full = raw[f"model.layers.{i}.mlp.gate_up_proj.weight"]
            return np.asarray(full[lo:hi].T, dtype=dt)

        layers["w_gate"] = stack(lambda i: gate_up(i, 0, f))
        layers["w_up"] = stack(lambda i: gate_up(i, f, 2 * f))
        layers["w_down"] = stack(
            lambda i: t(f"model.layers.{i}.mlp.down_proj.weight"))
    else:
        layers["w_gate"] = stack(
            lambda i: t(f"model.layers.{i}.{mlp}.gate_proj.weight"))
        layers["w_up"] = stack(
            lambda i: t(f"model.layers.{i}.{mlp}.up_proj.weight"))
        layers["w_down"] = stack(
            lambda i: t(f"model.layers.{i}.{mlp}.down_proj.weight"))

    params: Dict[str, Any] = {
        "embed": w("model.embed_tokens.weight"),
        "layers": layers,
        "final_norm": w("model.final_layernorm.weight" if falcon_h1
                        else "model.norm.weight"),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = t("lm_head.weight")
    return params


def deinterleave_rope(width: int) -> np.ndarray:
    """Column order that turns interleaved rotary pairs (x0, x1 | x2, x3
    | ...: the published DeepSeek code's layout, which it undoes on the
    activations with a view + transpose before rotating halves) into
    halves (evens | odds). Applied once, to the weights' rope columns."""
    return np.concatenate([np.arange(0, width, 2), np.arange(1, width, 2)])


def _load_deepseek_v3(raw, cfg: ModelConfig, t, w) -> Dict[str, Any]:
    """`load_params_from_hf` for latent attention and the two layer
    groups of models/llama.layer_groups."""
    from dynamo_tpu.models.llama import layer_groups
    h, r = cfg.num_heads, cfg.kv_lora_rank
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    perm = deinterleave_rope(dr)
    # wq's columns are H heads of (dn | dr); wkv_a's are (r | dr)
    q_cols = np.concatenate([hd * (dn + dr) + np.concatenate(
        [np.arange(dn), dn + perm]) for hd in range(h)])
    kv_cols = np.concatenate([np.arange(r), r + perm])

    def group(first, count, dense):
        def stack(fn):
            return np.stack([fn(i) for i in range(first, first + count)])
        pre = "model.layers.{}"
        attn = pre + ".self_attn"
        layers = {
            "attn_norm": stack(
                lambda i: w(pre.format(i) + ".input_layernorm.weight")),
            "wq": stack(lambda i: t(attn.format(i) + ".q_proj.weight"
                                    )[:, q_cols]),
            "wkv_a": stack(lambda i: t(
                attn.format(i) + ".kv_a_proj_with_mqa.weight")[:, kv_cols]),
            "kv_a_norm": stack(
                lambda i: w(attn.format(i) + ".kv_a_layernorm.weight")),
            "wkv_b": stack(lambda i: t(attn.format(i) + ".kv_b_proj.weight")),
            "wo": stack(lambda i: t(attn.format(i) + ".o_proj.weight")),
            "mlp_norm": stack(lambda i: w(
                pre.format(i) + ".post_attention_layernorm.weight")),
        }
        mlp = pre + ".mlp"
        names = (("gate", "gate_proj"), ("up", "up_proj"),
                 ("down", "down_proj"))
        if dense:
            for ours, theirs in names:
                layers[f"w_{ours}"] = stack(
                    lambda i, p=theirs: t(mlp.format(i) + f".{p}.weight"))
            return layers
        layers["router"] = stack(lambda i: t(mlp.format(i) + ".gate.weight"))
        if cfg.moe_router_bias:
            layers["router_bias"] = stack(lambda i: np.asarray(
                raw[mlp.format(i) + ".gate.e_score_correction_bias"],
                np.float32))
        for ours, theirs in names:
            layers[f"w_{ours}"] = stack(lambda i, p=theirs: np.stack([
                t(mlp.format(i) + f".experts.{e}.{p}.weight")
                for e in range(cfg.num_experts)]))
            if cfg.shared_expert_size:
                layers[f"ws_{ours}"] = stack(lambda i, p=theirs: t(
                    mlp.format(i) + f".shared_experts.{p}.weight"))
        return layers

    params: Dict[str, Any] = {
        "embed": w("model.embed_tokens.weight"),
        "final_norm": w("model.norm.weight"),
    }
    for name, first, count, dense in layer_groups(cfg):
        params[name] = group(first, count, dense)
    if not cfg.tie_word_embeddings:
        params["lm_head"] = t("lm_head.weight")
    return params


def _load_lfm2(raw, cfg: ModelConfig, t, w) -> Dict[str, Any]:
    """`load_params_from_hf` for `transformers`' dense `lfm2` names, a
    stack a layer kind (models/llama.layer_runs):
      model.layers.{i}.operator_norm / .ffn_norm -> attn_norm / mlp_norm
      .conv.in_proj.weight.T  (B | C | u)        -> conv_in
      .conv.conv.weight [D, 1, K]                -> conv_w [K, D]
      .conv.out_proj.weight.T / .self_attn.out_proj.weight.T -> wo
      .self_attn.{q,k}_layernorm.weight          -> q_norm / k_norm
      .feed_forward.w1 / w3 / w2 .weight.T       -> w_gate / w_up / w_down
      model.embedding_norm.weight                -> final_norm"""
    from dynamo_tpu.models.llama import layer_runs
    kinds = cfg.layer_kinds()
    pre = "model.layers.{}."

    def group(run):
        ids = [i for i, kind in enumerate(kinds) if kind == run.kind]

        def stack(fn):
            return np.stack([fn(pre.format(i)) for i in ids])
        layers = {
            "attn_norm": stack(lambda p: w(p + "operator_norm.weight")),
            "mlp_norm": stack(lambda p: w(p + "ffn_norm.weight")),
            "w_gate": stack(lambda p: t(p + "feed_forward.w1.weight")),
            "w_up": stack(lambda p: t(p + "feed_forward.w3.weight")),
            "w_down": stack(lambda p: t(p + "feed_forward.w2.weight")),
        }
        if run.kind == "conv":
            layers.update({
                "conv_in": stack(lambda p: t(p + "conv.in_proj.weight")),
                "conv_w": stack(lambda p: np.asarray(
                    raw[p + "conv.conv.weight"][:, 0, :].T,
                    dtype=layers["attn_norm"].dtype)),
                "wo": stack(lambda p: t(p + "conv.out_proj.weight")),
            })
            return layers
        attn = "self_attn."
        layers.update({
            "wq": stack(lambda p: t(p + attn + "q_proj.weight")),
            "wk": stack(lambda p: t(p + attn + "k_proj.weight")),
            "wv": stack(lambda p: t(p + attn + "v_proj.weight")),
            "wo": stack(lambda p: t(p + attn + "out_proj.weight")),
            "q_norm": stack(lambda p: w(p + attn + "q_layernorm.weight")),
            "k_norm": stack(lambda p: w(p + attn + "k_layernorm.weight")),
        })
        return layers

    params: Dict[str, Any] = {
        "embed": w("model.embed_tokens.weight"),
        "final_norm": w("model.embedding_norm.weight"),
    }
    for run in layer_runs(cfg):
        params[run.key] = group(run)
    if not cfg.tie_word_embeddings:
        params["lm_head"] = t("lm_head.weight")
    return params


def load_model_dir(path: str, dtype: str = ""):
    """Convenience: (ModelConfig, params) from one HF-style directory."""
    with open(os.path.join(path, "config.json")) as f:
        hf = json.load(f)
    cfg = config_from_hf(hf, name=os.path.basename(path.rstrip("/")))
    if dtype:
        import dataclasses
        cfg = dataclasses.replace(cfg, dtype=dtype)
    return cfg, load_params_from_hf(path, cfg)
