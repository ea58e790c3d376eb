"""Operations and bytes of a dense decoder-only transformer whose
attention is EVA (published EvaByte key names: ``attention_class``
"eva", ``window_size``, ``chunk_size``, ``num_pred_heads``): an exact
window that resets at every multiple of ``window_size`` and one summary
row per ``chunk_size`` positions of everything before it. As in
``dense_transformer``: what the ALGORITHM needs, weights in the served
dtype (bf16), not what today's program stores or recomputes.

What differs is the K/V a decode step reads. A query at position ``p``
reads ``p % window + 1`` exact rows and ``(p // window) * (window /
chunk)`` summary rows a layer, each a K and a V row of every KV head.
"""

from __future__ import annotations

from typing import Dict


def dims(cfg: Dict) -> Dict:
    d = cfg["hidden_size"]
    return {"d": d, "layers": cfg["num_hidden_layers"],
            "heads": cfg["num_attention_heads"],
            "kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg.get("head_dim") or d // cfg["num_attention_heads"],
            "ff": cfg["intermediate_size"], "vocab": cfg["vocab_size"],
            "pred_heads": cfg["num_pred_heads"],
            "window": cfg["window_size"], "chunk": cfg["chunk_size"]}


def attention_params(cfg: Dict) -> int:
    """One layer's q, k, v and output projections."""
    s = dims(cfg)
    return (2 * s["d"] * s["heads"] * s["head_dim"]
            + 2 * s["d"] * s["kv_heads"] * s["head_dim"])


def layer_params(cfg: Dict) -> int:
    """Attention, SwiGLU, the two block norms, and the pooling's two
    vectors a KV head (phi, mu)."""
    s = dims(cfg)
    return (attention_params(cfg) + 3 * s["d"] * s["ff"] + 2 * s["d"]
            + 2 * s["kv_heads"] * s["head_dim"])


def total_params(cfg: Dict) -> int:
    """Every parameter the model holds: the embedding, the layers, the
    final norm, and ALL the head's ``pred_heads x vocab`` columns."""
    s = dims(cfg)
    return (s["vocab"] * s["d"] + s["layers"] * layer_params(cfg) + s["d"]
            + s["d"] * s["pred_heads"] * s["vocab"])


def matmul_params(cfg: Dict) -> int:
    """Parameters ONE served token multiplies with: the layers'
    projections and head 0's ``vocab`` columns (the next byte's: the
    other heads draft and are not computed)."""
    s = dims(cfg)
    return (s["layers"] * (attention_params(cfg) + 3 * s["d"] * s["ff"])
            + s["d"] * s["vocab"])


def kv_bytes_per_row_layer(cfg: Dict, bytes_per_el: int = 2) -> int:
    """K and V of one row, exact or summary, in one layer (16 KiB at 32
    KV heads of 128)."""
    s = dims(cfg)
    return 2 * s["kv_heads"] * s["head_dim"] * bytes_per_el


def rows_read(cfg: Dict, position: int) -> Dict[str, int]:
    """Rows a query at ``position`` reads in one layer, by part, and
    what one row a position would read there."""
    s = dims(cfg)
    return {"exact": position % s["window"] + 1,
            "summary": position // s["window"] * (s["window"] // s["chunk"]),
            "full_equivalent": position + 1}


def attention_bytes(cfg: Dict, live_rows: float) -> float:
    """K/V one decode step's attention reads with ``live_rows`` rows
    (both parts, all slots together) live a layer."""
    return live_rows * kv_bytes_per_row_layer(cfg) * dims(cfg)["layers"]


def decode_step_bytes(cfg: Dict, live_rows: float,
                      weight_bytes_per_param: int = 2) -> float:
    """Least bytes one decode step must move from HBM: every matmul
    weight once (bf16) and the ``live_rows`` K/V rows a layer its
    attention reads (exact and summary rows alike, all slots together:
    what the engine counts as ``decode_kv_blocks_live`` x the block).
    Memory-bound: 2 x 16 FLOPs per weight at batch 16."""
    return (matmul_params(cfg) * weight_bytes_per_param
            + attention_bytes(cfg, live_rows))
