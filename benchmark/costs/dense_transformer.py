"""Operations and bytes of a dense decoder-only transformer, from shapes.

Reads the published key names of either family the benchmark runs
(Mistral/Llama-style ``hidden_size`` …, GPT-2-style ``n_embd`` …). These
are what the ALGORITHM needs, not what today's program happens to do:
recomputed (remat) operations do not count, and weights are counted in
the dtype the configuration is served in, not the one the program
stores.
"""

from __future__ import annotations

from typing import Dict


def dims(cfg: Dict) -> Dict:
    if "n_embd" in cfg:                       # GPT-2 naming
        d = cfg["n_embd"]
        return {"d": d, "layers": cfg["n_layer"], "heads": cfg["n_head"],
                "kv_heads": cfg["n_head"], "head_dim": d // cfg["n_head"],
                "ff": cfg.get("n_inner") or 4 * d, "vocab": cfg["vocab_size"],
                "mlp_mats": 2, "tied": True}
    d = cfg["hidden_size"]
    hd = cfg.get("head_dim") or d // cfg["num_attention_heads"]
    return {"d": d, "layers": cfg["num_hidden_layers"],
            "heads": cfg["num_attention_heads"],
            "kv_heads": cfg["num_key_value_heads"], "head_dim": hd,
            "ff": cfg["intermediate_size"], "vocab": cfg["vocab_size"],
            "mlp_mats": 3, "tied": bool(cfg.get("tie_word_embeddings"))}


def matmul_params(cfg: Dict) -> int:
    """Parameters that take part in a matrix multiplication for every
    token: the layers' projections and the output head (tied or not).
    The embedding LOOKUP, norms and biases are not matmuls."""
    s = dims(cfg)
    attn = s["d"] * s["heads"] * s["head_dim"] * 2 \
        + s["d"] * s["kv_heads"] * s["head_dim"] * 2
    mlp = s["mlp_mats"] * s["d"] * s["ff"]
    return s["layers"] * (attn + mlp) + s["d"] * s["vocab"]


def train_flops_per_token(cfg: Dict, seq_len: int) -> float:
    """Model FLOPs per trained token, forward + backward (3x forward),
    NO recomputation: 6 per matmul parameter, plus causal attention
    (QK^T and AV over an average context of seq_len/2: 2*2*(S/2)*d
    forward per layer)."""
    s = dims(cfg)
    attn_fwd = s["layers"] * 2 * 2 * (seq_len / 2) * s["heads"] * s["head_dim"]
    return 6.0 * matmul_params(cfg) + 3.0 * attn_fwd


def kv_bytes_per_token(cfg: Dict, bytes_per_el: int = 2) -> int:
    s = dims(cfg)
    return s["layers"] * 2 * s["kv_heads"] * s["head_dim"] * bytes_per_el


def decode_step_bytes(cfg: Dict, live_tokens: float,
                      weight_bytes_per_param: int = 2) -> float:
    """Least bytes one decode step must move from HBM: every matmul
    weight once (in the served dtype — bf16 here) and the K/V of every
    LIVE cached token once. Memory-bound: at batch 32 the step does
    2*32 FLOPs per weight byte pair, far under the chip's ~240 FLOP/B."""
    return (matmul_params(cfg) * weight_bytes_per_param
            + live_tokens * kv_bytes_per_token(cfg))
