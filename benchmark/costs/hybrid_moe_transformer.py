"""Operations and bytes of a decoder-only transformer whose FFN is a
mixture of experts and whose attention layers are of two KINDS, full and
sliding-window (published Mellum-style key names: ``layer_types``,
``sliding_window``, ``moe_intermediate_size``, a ``head_dim`` of its
own). As in ``moe_transformer``: what the ALGORITHM needs, weights in the
served dtype (bf16), not what today's program stores or recomputes.

What differs from ``moe_transformer`` is the K/V a decode step reads. A
full layer reads every live token. A sliding layer reads at most its
window a slot, and by blocks, so up to one block more where the window
starts inside one: ``min(live, slots x (window + block))`` tokens.
"""

from __future__ import annotations

from typing import Dict, Optional

from benchmark.costs.moe_transformer import expected_distinct_experts


def dims(cfg: Dict) -> Dict:
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    return {"d": cfg["hidden_size"], "layers": cfg["num_hidden_layers"],
            "full_layers": kinds.count("full_attention"),
            "sliding_layers": kinds.count("sliding_attention"),
            "window": cfg["sliding_window"],
            "heads": cfg["num_attention_heads"],
            "kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["head_dim"], "ff": cfg["moe_intermediate_size"],
            "experts": cfg["num_experts"],
            "top_k": cfg["num_experts_per_tok"], "vocab": cfg["vocab_size"],
            "tied": bool(cfg.get("tie_word_embeddings"))}


def attention_params(cfg: Dict) -> int:
    """One layer's q, k, v and output projections (q and o are
    hidden x heads*head_dim: head_dim is not hidden / heads)."""
    s = dims(cfg)
    return (2 * s["d"] * s["heads"] * s["head_dim"]
            + 2 * s["d"] * s["kv_heads"] * s["head_dim"])


def expert_params(cfg: Dict) -> int:
    """ONE expert's gate, up and down matrices."""
    s = dims(cfg)
    return 3 * s["d"] * s["ff"]


def total_params(cfg: Dict) -> int:
    """Every parameter the model holds: embedding, per layer attention,
    the two block norms, the router and all experts, the final norm, the
    head (if untied). Both kinds of layer have the same parameters."""
    s = dims(cfg)
    layer = (attention_params(cfg) + 2 * s["d"] + s["d"] * s["experts"]
             + s["experts"] * expert_params(cfg))
    head = 0 if s["tied"] else s["d"] * s["vocab"]
    return s["vocab"] * s["d"] + s["layers"] * layer + s["d"] + head


def matmul_params(cfg: Dict) -> int:
    """Parameters ONE token multiplies with: attention, the router, its
    top-k experts, the output head."""
    s = dims(cfg)
    layer = (attention_params(cfg) + s["d"] * s["experts"]
             + s["top_k"] * expert_params(cfg))
    return s["layers"] * layer + s["d"] * s["vocab"]


def kv_bytes_per_token_layer(cfg: Dict, bytes_per_el: int = 2) -> int:
    """K and V of one token in one layer (2 KiB at 4 KV heads of 128)."""
    s = dims(cfg)
    return 2 * s["kv_heads"] * s["head_dim"] * bytes_per_el


def kv_read_bytes(cfg: Dict, live_tokens: float,
                  slots: Optional[int] = None,
                  block_size: Optional[int] = None) -> float:
    """K/V one decode step must read with ``live_tokens`` cached over
    all ``slots``: all of them in every full layer, at most a window and
    a block a slot in every sliding layer."""
    s = dims(cfg)
    slots = cfg["decode_slots"] if slots is None else slots
    block_size = cfg["decode_block_size"] if block_size is None else block_size
    windowed = min(live_tokens, slots * (s["window"] + block_size))
    return kv_bytes_per_token_layer(cfg) * (
        s["full_layers"] * live_tokens + s["sliding_layers"] * windowed)


def decode_step_bytes(cfg: Dict, live_tokens: float,
                      weight_bytes_per_param: int = 2,
                      batch: Optional[int] = None) -> float:
    """Least bytes one decode step must move from HBM: attention, router
    and head weights once; the expert weights of the EXPECTED distinct
    experts ``batch`` tokens hit a layer under uniform routing (``batch``
    defaults to the configuration's ``decode_slots``); and the K/V of
    ``kv_read_bytes``. Memory-bound: an expert row does 2 FLOPs a
    weight, and a chosen expert sees ~4 rows a step."""
    s = dims(cfg)
    batch = cfg["decode_slots"] if batch is None else batch
    hit = expected_distinct_experts(s["experts"], s["top_k"], batch)
    layer = (attention_params(cfg) + s["d"] * s["experts"]
             + hit * expert_params(cfg))
    weights = s["layers"] * layer + s["d"] * s["vocab"]
    return (weights * weight_bytes_per_param
            + kv_read_bytes(cfg, live_tokens, slots=batch))


def uniform_pool_blocks(cfg: Dict, slots: int, max_seq: int,
                        block_size: int) -> int:
    """Blocks ONE pool in which every layer holds every token would
    take, all layers together, at ``slots`` x ``max_seq``."""
    return cfg["num_hidden_layers"] * slots * -(-max_seq // block_size)
