"""Operations and bytes of a mixture-of-experts decoder that generates
BY DIFFUSION OVER BLOCKS, from shapes (published ``sdar_moe`` /
Qwen3-MoE key names). As in ``moe_transformer``: what the ALGORITHM
needs, weights in the served dtype (bf16), not what today's program
stores or recomputes.

What differs from the one-token expert model is the PASS: every slot
runs its whole block of ``block_length`` rows, so the FFN sees
``decode_slots x block_length`` rows (128 here: under uniform routing
127.97 of 128 experts a layer are hit, every expert's weights are read
each pass), the attention's ``block_length x heads`` query rows a slot
read the slot's rows ONCE, and the block's own K/V rows are written.
How many passes a token costs is the schedule's, not this file's: the
cell's tokens per second are passes per second x the tokens a pass
yields (``blockdiff.*`` metrics).
"""

from __future__ import annotations

from typing import Dict, Optional

from benchmark.costs.moe_transformer import expected_distinct_experts


def dims(cfg: Dict) -> Dict:
    return {"d": cfg["hidden_size"], "layers": cfg["num_hidden_layers"],
            "heads": cfg["num_attention_heads"],
            "kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["head_dim"], "ff": cfg["moe_intermediate_size"],
            "experts": cfg["num_experts"],
            "top_k": cfg["num_experts_per_tok"], "vocab": cfg["vocab_size"],
            "block": cfg["generation"]["block_length"]}


def attention_params(cfg: Dict) -> int:
    """One layer's q, k, v and output projections."""
    s = dims(cfg)
    return (2 * s["d"] * s["heads"] * s["head_dim"]
            + 2 * s["d"] * s["kv_heads"] * s["head_dim"])


def expert_params(cfg: Dict) -> int:
    """ONE expert's gate, up and down matrices."""
    s = dims(cfg)
    return 3 * s["d"] * s["ff"]


def layer_params(cfg: Dict) -> int:
    """Attention, the two block norms, the q and k norms (one weight of
    ``head_dim`` each), the router and all experts."""
    s = dims(cfg)
    return (attention_params(cfg) + 2 * s["d"] + 2 * s["head_dim"]
            + s["d"] * s["experts"] + s["experts"] * expert_params(cfg))


def total_params(cfg: Dict, layers: Optional[int] = None) -> int:
    """Every parameter the model holds at ``layers`` layers (the
    configuration's): embedding, the layers, the final norm, the untied
    head."""
    s = dims(cfg)
    layers = s["layers"] if layers is None else layers
    return 2 * s["vocab"] * s["d"] + layers * layer_params(cfg) + s["d"]


def kv_bytes_per_token(cfg: Dict, bytes_per_el: int = 2) -> int:
    s = dims(cfg)
    return s["layers"] * 2 * s["kv_heads"] * s["head_dim"] * bytes_per_el


def decode_step_bytes(cfg: Dict, live_tokens: float,
                      weight_bytes_per_param: int = 2,
                      batch: Optional[int] = None) -> float:
    """Least bytes one PASS must move: attention, router and head weights
    once; the expert weights of the EXPECTED distinct experts that
    ``batch`` rows hit a layer under uniform routing (``batch`` defaults
    to ``decode_slots x block_length``); the K/V of every LIVE row once
    (``live_tokens``: all slots' rows up to their blocks' ends, 2 KiB a
    row a layer at 4 KV heads of 128 in bf16); the blocks' own rows
    written."""
    s = dims(cfg)
    slots = cfg["decode_slots"]
    batch = slots * s["block"] if batch is None else batch
    hit = expected_distinct_experts(s["experts"], s["top_k"], batch)
    layer = (attention_params(cfg) + s["d"] * s["experts"]
             + hit * expert_params(cfg))
    weights = s["layers"] * layer + s["d"] * s["vocab"]
    return (weights * weight_bytes_per_param
            + (live_tokens + slots * s["block"]) * kv_bytes_per_token(cfg))


def attention_bytes(cfg: Dict, rows: float) -> float:
    """Bytes the paged attention of one pass must read: K and V of the
    ``rows`` live rows (all slots together), every layer."""
    return rows * kv_bytes_per_token(cfg)


def attention_flops(cfg: Dict, rows: float) -> float:
    """Operations of the same: each of a slot's ``block_length x heads``
    query rows scores and weighs every live row of the slot (2 x
    ``head_dim`` each way), every layer."""
    s = dims(cfg)
    return rows * s["layers"] * s["block"] * s["heads"] * 4 * s["head_dim"]
