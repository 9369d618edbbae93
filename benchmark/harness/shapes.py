"""Bytes and operations a step needs, from the configuration's shapes.
Kept with the benchmark: a roofline share divides THESE by the measured
time, so no PR that claims a gain can change what is counted.

Counted for one decode step (every live row emits one token):
  weights   every layer's attention and MLP matrices once, the final norm
            and the output head; on a mixture-of-experts layer every expert
            (at 32 rows x 2 experts a token all 8 are touched, and the
            engine's dispatch reads them all in any case) plus the router.
            The embedding table is not counted: a step gathers `rows` rows.
  kv        K and V of every live token, all layers.
Both are lower bounds on what the program reads (the gather path reads a
whole page bucket, not the live tokens), so the share cannot pass 100%.
"""
from __future__ import annotations

BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def layer_params(cfg: dict) -> dict:
    h, inter = cfg["hidden_size"], cfg["intermediate_size"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or h // heads
    attn = h * heads * hd + 2 * h * kv * hd + heads * hd * h
    experts = int(cfg.get("num_local_experts", 0))
    mlp = 3 * h * inter
    if experts:
        mlp = experts * mlp + h * experts      # experts + router
    return {"attention": attn, "mlp": mlp, "norms": 2 * h}


def weight_bytes_per_step(cfg: dict) -> int:
    per = layer_params(cfg)
    n = cfg["num_hidden_layers"] * sum(per.values())
    n += cfg["hidden_size"]                              # final norm
    n += cfg["hidden_size"] * cfg["vocab_size"]          # output head
    return n * BYTES[cfg.get("torch_dtype", "bfloat16")]


def kv_bytes_per_token(cfg: dict) -> int:
    heads = cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or cfg["hidden_size"] // heads
    return (2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"] * hd
            * BYTES[cfg.get("torch_dtype", "bfloat16")])


def decode_step_bytes(cfg: dict, live_kv_tokens: float, chips: int = 1
                      ) -> float:
    """Bytes ONE chip must read for one decode step (tensor-parallel
    shards split both weights and KV heads evenly)."""
    return (weight_bytes_per_step(cfg)
            + kv_bytes_per_token(cfg) * live_kv_tokens) / chips


def resident_bytes(cfg: dict, num_pages: int, page_size: int) -> dict:
    """Weights (embedding included) and KV pages resident on the device."""
    emb = cfg["hidden_size"] * cfg["vocab_size"] \
        * BYTES[cfg.get("torch_dtype", "bfloat16")]
    tied = bool(cfg.get("tie_word_embeddings"))
    return {"weights": weight_bytes_per_step(cfg) + (0 if tied else emb),
            "kv_pages": kv_bytes_per_token(cfg) * num_pages * page_size}
