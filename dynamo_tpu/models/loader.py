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
}


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
        # original context, so refuse rather than mis-serve
        kind = (hf["rope_scaling"].get("rope_type")
                or hf["rope_scaling"].get("type") or "?")
        raise ValueError(
            f"rope_scaling={kind!r} is not supported; use a checkpoint "
            f"without rope scaling (e.g. the base-context variant)")
    max_len = int(hf.get("max_position_embeddings", 2048))
    sliding = 0
    sliding_pattern = "alternate"
    if gemma2 and hf.get("sliding_window"):
        # modeled natively: per-layer sliding/global alternation
        sliding = int(hf["sliding_window"])
        types = hf.get("layer_types")
        if types is not None and all(t == "sliding_attention"
                                     for t in types):
            sliding_pattern = "all"
        elif types is not None and types != [
                "sliding_attention" if i % 2 == 0 else "full_attention"
                for i in range(hf["num_hidden_layers"])]:
            raise ValueError(
                "unsupported gemma2 layer_types pattern (only the "
                "alternating default or all-sliding are modeled)")
    # Qwen2 configs carry sliding_window but disable it by default
    elif hf.get("sliding_window") and hf.get("use_sliding_window", True):
        # full attention == sliding-window attention while the context
        # fits inside the window; cap the serving length there so models
        # like phi-3-mini-4k (window 2047) / mistral-v0.1 (4096) stay
        # exact instead of silently diverging past the window
        max_len = min(max_len, int(hf["sliding_window"]))
    return ModelConfig(
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
        sliding_pattern=sliding_pattern,
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
    )


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
      model.norm.weight                  -> final_norm
      lm_head.weight.T                   -> lm_head (absent when tied)
    """
    import jax.numpy as jnp
    dt = jnp.empty((), dtype or cfg.dtype).dtype
    raw = _read_all_tensors(path)

    def t(name):  # transposed projection in target dtype
        return np.asarray(raw[name].T, dtype=dt)

    def w(name):
        return np.asarray(raw[name], dtype=dt)

    def stack(fn):
        return np.stack([fn(i) for i in range(cfg.num_layers)])

    fused_qkv = "model.layers.0.self_attn.qkv_proj.weight" in raw  # Phi-3
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
                        f"model.layers.{i}.post_attention_layernorm.weight")),
    }
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
            lambda i: t(f"model.layers.{i}.mlp.gate_proj.weight"))
        layers["w_up"] = stack(
            lambda i: t(f"model.layers.{i}.mlp.up_proj.weight"))
        layers["w_down"] = stack(
            lambda i: t(f"model.layers.{i}.mlp.down_proj.weight"))

    params: Dict[str, Any] = {
        "embed": w("model.embed_tokens.weight"),
        "layers": layers,
        "final_norm": w("model.norm.weight"),
    }
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
