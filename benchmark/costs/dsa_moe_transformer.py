"""Operations and bytes of a decoder-only transformer with LEARNED SPARSE
latent attention in every layer (a lightning indexer with a cache of its
own, top-k selection, the absorbed latent attention over the selected
rows), a compressed query, leading dense layers, and an expert FFN of
which this chip HOLDS A SHARE (published ``deepseek_v32`` key names;
``n_routed_experts`` is the experts held, ``router_experts`` the
router's width). As in ``mla_moe_transformer``: what the ALGORITHM needs,
weights in the served dtype (bf16), whatever implements it.

What differs is what a decode step reads of the cache. A token's row a
layer is the latent ``c`` and one rotary key part (576 numbers, 1,152 B)
and ONE index key (128 numbers, 256 B): 1,408 B, however a pool lays it
out. A step's indexer reads every live row's INDEX KEY once (``2 x 64 x
128`` FLOPs a row: 64 FLOP/B); its attention reads the SELECTED rows'
latent parts once, ``min(live, index_topk)`` a slot, ``2 x 128 x 1,088``
FLOPs a row: 242 FLOP/B, the v5e's ridge.
"""

from __future__ import annotations

from typing import Dict, Optional

# what the family's latent-attention model counts the same way: one
# expert's, the shared experts' and a dense layer's matrices; the latent
# row and rotary key part (1,152 B); every head's expanded K and V
# (128 x (192 + 128) numbers, 81,920 B here)
from benchmark.costs.mla_moe_transformer import (  # noqa: F401
    dense_ffn_params, expert_params, kv_bytes_per_token_layer,
    mha_kv_bytes_per_token_layer, shared_params)
from benchmark.costs.moe_transformer import expected_distinct_experts


def dims(cfg: Dict) -> Dict:
    lead = cfg["first_k_dense_replace"]
    return {"d": cfg["hidden_size"], "layers": cfg["num_hidden_layers"],
            "dense_layers": lead,
            "moe_layers": cfg["num_hidden_layers"] - lead,
            "heads": cfg["num_attention_heads"],
            "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
            "v": cfg["v_head_dim"], "rank": cfg["kv_lora_rank"],
            "q_rank": cfg["q_lora_rank"],
            "index_heads": cfg["index_n_heads"],
            "index_dim": cfg["index_head_dim"],
            "index_topk": cfg["index_topk"],
            "dense_ff": cfg["intermediate_size"],
            "ff": cfg["moe_intermediate_size"],
            "shared": cfg["n_shared_experts"],
            "held": cfg["n_routed_experts"],
            "experts": cfg["router_experts"],
            "top_k": cfg["num_experts_per_tok"], "vocab": cfg["vocab_size"],
            "tied": bool(cfg.get("tie_word_embeddings"))}


def latent_path_params(cfg: Dict) -> int:
    """One layer's compressed query (with ``q_norm``), down (with
    ``kv_norm``), up (``kv_b_proj``) and output projections."""
    s = dims(cfg)
    return (s["d"] * s["q_rank"] + s["q_rank"]
            + s["q_rank"] * s["heads"] * (s["nope"] + s["rope"])
            + s["d"] * (s["rank"] + s["rope"]) + s["rank"]
            + s["rank"] * s["heads"] * (s["nope"] + s["v"])
            + s["heads"] * s["v"] * s["d"])


def indexer_params(cfg: Dict) -> int:
    """One layer's indexer: its query and key projections, the key's
    LayerNorm (scale and bias) and the heads' weights."""
    s = dims(cfg)
    return (s["q_rank"] * s["index_heads"] * s["index_dim"]
            + s["d"] * s["index_dim"] + 2 * s["index_dim"]
            + s["d"] * s["index_heads"])


def attention_params(cfg: Dict) -> int:
    return latent_path_params(cfg) + indexer_params(cfg)


def router_params(cfg: Dict) -> int:
    """The router's matrix and its selection bias (both float32), at the
    router's own width."""
    s = dims(cfg)
    return s["d"] * s["experts"] + s["experts"]


def total_params(cfg: Dict) -> int:
    """Every parameter this chip holds: embedding; per layer attention
    (its norms' scales among it) and the two block norms; a dense layer's
    FFN; an expert layer's router, bias, HELD experts and shared expert;
    the final norm; the head (if untied)."""
    s = dims(cfg)
    common = attention_params(cfg) + 2 * s["d"]
    dense = common + dense_ffn_params(cfg)
    moe = (common + router_params(cfg) + s["held"] * expert_params(cfg)
           + shared_params(cfg))
    head = 0 if s["tied"] else s["d"] * s["vocab"]
    return (s["vocab"] * s["d"] + s["dense_layers"] * dense
            + s["moe_layers"] * moe + s["d"] + head)


def expected_held_experts_hit(cfg: Dict, batch: int) -> float:
    """Distinct HELD experts ``batch`` tokens hit a layer under uniform
    routing over the router's experts: ``held x (1 - (1 - K/E)^batch)``,
    6.37 of 16 at 16 tokens x top-8 of 256."""
    s = dims(cfg)
    return (expected_distinct_experts(s["experts"], s["top_k"], batch)
            * s["held"] / s["experts"])


def index_bytes_per_token_layer(cfg: Dict, bytes_per_el: int = 2) -> int:
    """The index key: 256 B."""
    return dims(cfg)["index_dim"] * bytes_per_el


def dsa_indexer_bytes(cfg: Dict, live_rows: float) -> float:
    """Bytes the indexer of one decode step must read with ``live_rows``
    cached over all slots: every live row's index key, every layer."""
    return dims(cfg)["layers"] * live_rows * index_bytes_per_token_layer(cfg)


def dsa_indexer_flops(cfg: Dict, live_rows: float) -> float:
    s = dims(cfg)
    return (s["layers"] * live_rows
            * 2 * s["index_heads"] * s["index_dim"])


def selected_rows(cfg: Dict, live_rows: float,
                  batch: Optional[int] = None) -> float:
    """Rows a step's attention reads a layer: ``min(live a slot,
    index_topk)`` a slot, the live rows spread evenly over the slots."""
    batch = cfg["decode_slots"] if batch is None else batch
    return batch * min(live_rows / batch, dims(cfg)["index_topk"])


def dsa_attention_bytes(cfg: Dict, rows_selected: float) -> float:
    """Bytes the sparse attention of one decode step must read:
    ``rows_selected`` (over all slots, a layer) latent rows, every layer,
    once for scores and values both."""
    return dims(cfg)["layers"] * rows_selected * kv_bytes_per_token_layer(cfg)


def dsa_attention_flops(cfg: Dict, rows_selected: float) -> float:
    s = dims(cfg)
    return (s["layers"] * rows_selected
            * 2 * s["heads"] * (2 * s["rank"] + s["rope"]))


def decode_step_bytes(cfg: Dict, live_tokens: float,
                      weight_bytes_per_param: int = 2,
                      batch: Optional[int] = None) -> float:
    """Least bytes one decode step must move from HBM: the bf16 matmul
    weights a step reads once (attention with its indexer and the shared
    expert of every layer, the dense layers' FFN, the head's slice), the
    float32 routers and biases, the EXPECTED distinct held experts
    ``batch`` tokens hit a layer, every live row's INDEX KEY once, and
    the SELECTED rows' latent parts once."""
    s = dims(cfg)
    batch = cfg["decode_slots"] if batch is None else batch
    hit = expected_held_experts_hit(cfg, batch)
    bf16 = (s["layers"] * attention_params(cfg)
            + s["dense_layers"] * dense_ffn_params(cfg)
            + s["moe_layers"] * (shared_params(cfg)
                                 + hit * expert_params(cfg))
            + s["d"] * s["vocab"])
    return (bf16 * weight_bytes_per_param
            + s["moe_layers"] * router_params(cfg) * 4
            + dsa_indexer_bytes(cfg, live_tokens)
            + dsa_attention_bytes(cfg, selected_rows(cfg, live_tokens,
                                                     batch)))
