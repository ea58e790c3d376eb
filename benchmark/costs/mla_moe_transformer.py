"""Operations and bytes of a decoder-only transformer with LATENT
attention (MLA) in every layer, leading dense layers, and an expert FFN
with shared experts and a sigmoid router (published ``deepseek_v3`` key
names). As in ``moe_transformer``: what the ALGORITHM needs, weights in
the served dtype (bf16), not what today's program stores or recomputes.

What differs is the cache. A token's row a layer is the latent ``c``
(``kv_lora_rank``) and ONE rotary key part (``qk_rope_head_dim``): 576
numbers, 1,152 B in bf16, whatever a pool pads it to, where ``heads`` x
(K 192 + V 128) would be 20,480 B. A decode step's absorbed attention
reads each live row once, for scores and values both, and does ``2 x
heads x (576 + 512)`` FLOPs on it: 60 FLOP/B, where the v5e's ridge is
240, and with 32 heads in the MXU's 128 rows the two roofs meet.
"""

from __future__ import annotations

from typing import Dict, Optional

from benchmark.costs.moe_transformer import expected_distinct_experts


def dims(cfg: Dict) -> Dict:
    lead = cfg["first_k_dense_replace"]
    return {"d": cfg["hidden_size"], "layers": cfg["num_hidden_layers"],
            "dense_layers": lead,
            "moe_layers": cfg["num_hidden_layers"] - lead,
            "heads": cfg["num_attention_heads"],
            "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
            "v": cfg["v_head_dim"], "rank": cfg["kv_lora_rank"],
            "dense_ff": cfg["intermediate_size"],
            "ff": cfg["moe_intermediate_size"],
            "shared": cfg["n_shared_experts"],
            "experts": cfg["n_routed_experts"],
            "top_k": cfg["num_experts_per_tok"], "vocab": cfg["vocab_size"],
            "tied": bool(cfg.get("tie_word_embeddings"))}


def attention_params(cfg: Dict) -> int:
    """One layer's q, down, up (``kv_b_proj``) and output projections."""
    s = dims(cfg)
    return (s["d"] * s["heads"] * (s["nope"] + s["rope"])
            + s["d"] * (s["rank"] + s["rope"])
            + s["rank"] * s["heads"] * (s["nope"] + s["v"])
            + s["heads"] * s["v"] * s["d"])


def expert_params(cfg: Dict) -> int:
    """ONE routed expert's gate, up and down matrices."""
    s = dims(cfg)
    return 3 * s["d"] * s["ff"]


def shared_params(cfg: Dict) -> int:
    """The shared experts' gate, up and down, side by side."""
    s = dims(cfg)
    return 3 * s["d"] * s["shared"] * s["ff"]


def dense_ffn_params(cfg: Dict) -> int:
    s = dims(cfg)
    return 3 * s["d"] * s["dense_ff"]


def router_params(cfg: Dict) -> int:
    """The router's matrix and its selection bias (both float32)."""
    s = dims(cfg)
    return s["d"] * s["experts"] + s["experts"]


def total_params(cfg: Dict) -> int:
    """Every parameter the model holds: embedding; per layer attention,
    ``kv_a_layernorm`` and the two block norms; a dense layer's FFN; an
    expert layer's router, bias, experts and shared experts; the final
    norm; the head (if untied)."""
    s = dims(cfg)
    common = attention_params(cfg) + s["rank"] + 2 * s["d"]
    dense = common + dense_ffn_params(cfg)
    moe = (common + router_params(cfg) + s["experts"] * expert_params(cfg)
           + shared_params(cfg))
    head = 0 if s["tied"] else s["d"] * s["vocab"]
    return (s["vocab"] * s["d"] + s["dense_layers"] * dense
            + s["moe_layers"] * moe + s["d"] + head)


def matmul_params(cfg: Dict) -> int:
    """Parameters ONE token multiplies with: attention, a dense layer's
    FFN, an expert layer's router, top-k experts and shared experts, the
    output head."""
    s = dims(cfg)
    moe = (s["d"] * s["experts"] + s["top_k"] * expert_params(cfg)
           + shared_params(cfg))
    return (s["layers"] * attention_params(cfg)
            + s["dense_layers"] * dense_ffn_params(cfg)
            + s["moe_layers"] * moe + s["d"] * s["vocab"])


def kv_bytes_per_token_layer(cfg: Dict, bytes_per_el: int = 2) -> int:
    """The latent row and the rotary key part of one token in one layer:
    1,152 B at 512 + 64 in bf16."""
    s = dims(cfg)
    return (s["rank"] + s["rope"]) * bytes_per_el


def mha_kv_bytes_per_token_layer(cfg: Dict, bytes_per_el: int = 2) -> int:
    """What the same token's keys and values would take a layer if every
    head's were cached expanded: heads x (192 + 128) numbers, 20,480 B."""
    s = dims(cfg)
    return s["heads"] * (s["nope"] + s["rope"] + s["v"]) * bytes_per_el


def mla_attention_bytes(cfg: Dict, live_rows: float) -> float:
    """Bytes the absorbed attention of one decode step must read with
    ``live_rows`` cached over all slots: every live row of every layer
    once (it is key and value both)."""
    return dims(cfg)["layers"] * live_rows * kv_bytes_per_token_layer(cfg)


def mla_attention_flops(cfg: Dict, live_rows: float) -> float:
    """Its operations: a row meets every head twice, ``q_lat . c + q_pe .
    k_pe`` over 576 lanes and ``p c`` over 512, 2 FLOPs a lane."""
    s = dims(cfg)
    return (s["layers"] * live_rows
            * 2 * s["heads"] * (2 * s["rank"] + s["rope"]))


def decode_step_bytes(cfg: Dict, live_tokens: float,
                      weight_bytes_per_param: int = 2,
                      batch: Optional[int] = None) -> float:
    """Least bytes one decode step must move from HBM: the bf16 matmul
    weights a step reads once (attention and shared experts of every
    layer, the dense layers' FFN, the head), the float32 router and
    bias, the routed weights of the EXPECTED distinct experts ``batch``
    tokens hit a layer under uniform routing (``batch`` defaults to the
    configuration's ``decode_slots``: 100.4 of 128 at 32 x top-6), and
    every LIVE latent row of every layer once. Memory-bound but for the
    attention, which sits at the ridge (module docstring)."""
    s = dims(cfg)
    batch = cfg["decode_slots"] if batch is None else batch
    hit = expected_distinct_experts(s["experts"], s["top_k"], batch)
    bf16 = (s["layers"] * attention_params(cfg)
            + s["dense_layers"] * dense_ffn_params(cfg)
            + s["moe_layers"] * (shared_params(cfg)
                                 + hit * expert_params(cfg))
            + s["d"] * s["vocab"])
    return (bf16 * weight_bytes_per_param
            + s["moe_layers"] * router_params(cfg) * 4
            + mla_attention_bytes(cfg, live_tokens))
