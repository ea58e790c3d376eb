"""Operations and bytes of a decoder-only transformer whose FFN is a
mixture of experts, from shapes (published OLMoE-style key names). As in
``dense_transformer``: what the ALGORITHM needs, weights in the served
dtype (bf16), not what today's program stores or recomputes.

What differs from the dense model is WHICH expert weights a step must
read. A token multiplies with ``num_experts_per_tok`` experts; a batch of
B tokens touches, under uniform routing, an expected

    E * (1 - (1 - K/E) ** B)

distinct experts a layer (each expert is missed by one token with
probability 1 - K/E, by all B independently): 63.1 of 64 at B = 32,
K = 8. Each is read once however many tokens chose it.
"""

from __future__ import annotations

from typing import Dict, Optional


def dims(cfg: Dict) -> Dict:
    d = cfg["hidden_size"]
    hd = cfg.get("head_dim") or d // cfg["num_attention_heads"]
    return {"d": d, "layers": cfg["num_hidden_layers"],
            "heads": cfg["num_attention_heads"],
            "kv_heads": cfg["num_key_value_heads"], "head_dim": hd,
            "ff": cfg["intermediate_size"], "experts": cfg["num_experts"],
            "top_k": cfg["num_experts_per_tok"], "vocab": cfg["vocab_size"],
            "tied": bool(cfg.get("tie_word_embeddings"))}


def attention_params(cfg: Dict) -> int:
    """One layer's q, k, v and output projections."""
    s = dims(cfg)
    return (2 * s["d"] * s["heads"] * s["head_dim"]
            + 2 * s["d"] * s["kv_heads"] * s["head_dim"])


def expert_params(cfg: Dict) -> int:
    """ONE expert's gate, up and down matrices."""
    s = dims(cfg)
    return 3 * s["d"] * s["ff"]


def total_params(cfg: Dict) -> int:
    """Every parameter the model holds: embedding, per layer attention,
    the two block norms, the q and k norms, the router and all experts,
    the final norm, the head (if untied)."""
    s = dims(cfg)
    qk_norms = (s["heads"] + s["kv_heads"]) * s["head_dim"]
    layer = (attention_params(cfg) + 2 * s["d"] + qk_norms
             + s["d"] * s["experts"] + s["experts"] * expert_params(cfg))
    head = 0 if s["tied"] else s["d"] * s["vocab"]
    return s["vocab"] * s["d"] + s["layers"] * layer + s["d"] + head


def matmul_params(cfg: Dict) -> int:
    """Parameters ONE token multiplies with: attention, the router, its
    top-k experts, the output head."""
    s = dims(cfg)
    layer = (attention_params(cfg) + s["d"] * s["experts"]
             + s["top_k"] * expert_params(cfg))
    return s["layers"] * layer + s["d"] * s["vocab"]


def expected_distinct_experts(num_experts: int, top_k: int,
                              batch: float) -> float:
    """Experts a layer touches for ``batch`` tokens choosing ``top_k``
    distinct experts each, uniformly and independently."""
    return num_experts * (1.0 - (1.0 - top_k / num_experts) ** batch)


def kv_bytes_per_token(cfg: Dict, bytes_per_el: int = 2) -> int:
    s = dims(cfg)
    return s["layers"] * 2 * s["kv_heads"] * s["head_dim"] * bytes_per_el


def decode_step_bytes(cfg: Dict, live_tokens: float,
                      weight_bytes_per_param: int = 2,
                      batch: Optional[int] = None) -> float:
    """Least bytes one decode step must move from HBM: attention, router
    and head weights once; the expert weights of the EXPECTED distinct
    experts ``batch`` tokens hit a layer under uniform routing (``batch``
    defaults to the configuration's ``decode_slots``, the cell's full
    batch); the K/V of every LIVE cached token once (8 KiB a token a
    layer at 16 KV heads of 128 in bf16). Memory-bound: an expert row
    does 2 FLOPs a weight, and a chosen expert sees ~4 rows a step."""
    s = dims(cfg)
    batch = cfg["decode_slots"] if batch is None else batch
    hit = expected_distinct_experts(s["experts"], s["top_k"], batch)
    layer = (attention_params(cfg) + s["d"] * s["experts"]
             + hit * expert_params(cfg))
    weights = s["layers"] * layer + s["d"] * s["vocab"]
    return (weights * weight_bytes_per_param
            + live_tokens * kv_bytes_per_token(cfg))
