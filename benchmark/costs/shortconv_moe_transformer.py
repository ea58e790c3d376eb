"""Operations and bytes of a hybrid decoder whose layers are TWO sublayers
each (published ``lfm2_moe`` key names): a mixer (GQA attention where
``layer_types[i]`` is ``"full_attention"``, else the gated short
convolution) and an FFN (a dense SwiGLU ``intermediate_size`` in the first
``num_dense_layers`` layers, ``num_experts`` experts of
``moe_intermediate_size`` after them). As in the other costs files: what
the ALGORITHM needs, weights in the served dtype (bf16), whatever
implements it.

What this shape has that no other costs file has: a mixer that reads NO
cache that grows. A conv layer of a decode step reads its three
projections (``in_proj`` d x 3d, ``out_proj`` d x d, the filter's d x K
taps) once and, a slot, ``conv_L_cache - 1`` rows of d numbers, which it
writes back shifted: 16 KB a slot a layer both ways at d 2048 in bf16,
the same at every context length. The attention layers' K/V is counted a
ROW: 2 x kv_heads x head_dim numbers a position a layer (2 KiB at 8 heads
of 64) however the pool lays them out.
"""

from __future__ import annotations

from typing import Dict, Optional

ATTENTION = "full_attention"


def dims(cfg: Dict) -> Dict:
    layers = cfg["num_hidden_layers"]
    types = list(cfg["layer_types"])[:layers]
    attn = sum(t == ATTENTION for t in types)
    heads = cfg["num_attention_heads"]
    dense = min(cfg["num_dense_layers"], layers)
    return {"d": cfg["hidden_size"], "layers": layers,
            "attn_layers": attn, "conv_layers": layers - attn,
            "dense_layers": dense, "expert_layers": layers - dense,
            "taps": cfg["conv_L_cache"], "heads": heads,
            "kv_heads": cfg["num_key_value_heads"],
            "head_dim": int(cfg.get("head_dim")
                            or cfg["hidden_size"] // heads),
            "ff": cfg["intermediate_size"],
            "expert_ff": cfg["moe_intermediate_size"],
            "experts": cfg["num_experts"],
            "top_k": cfg["num_experts_per_tok"], "vocab": cfg["vocab_size"]}


def conv_mixer_params(cfg: Dict) -> int:
    """A conv mixer: ``in_proj`` d x 3d, ``out_proj`` d x d, d x K taps."""
    s = dims(cfg)
    return s["d"] * 3 * s["d"] + s["d"] * s["d"] + s["d"] * s["taps"]


def attention_params(cfg: Dict) -> int:
    """q, k, v, o (no bias) and the two head norms."""
    s = dims(cfg)
    q, kv = s["heads"] * s["head_dim"], s["kv_heads"] * s["head_dim"]
    return 2 * s["d"] * q + 2 * s["d"] * kv + 2 * s["head_dim"]


def dense_ffn_params(cfg: Dict) -> int:
    s = dims(cfg)
    return 3 * s["d"] * s["ff"]


def expert_params(cfg: Dict) -> int:
    """ONE expert's three matrices."""
    s = dims(cfg)
    return 3 * s["d"] * s["expert_ff"]


def router_params(cfg: Dict) -> int:
    """The gate and the selection bias."""
    s = dims(cfg)
    return s["d"] * s["experts"] + s["experts"]


def layer_params(cfg: Dict, conv: bool, dense: bool) -> int:
    """One layer of a kind: its mixer, its FFN and the two norms."""
    s = dims(cfg)
    mixer = conv_mixer_params(cfg) if conv else attention_params(cfg)
    ffn = dense_ffn_params(cfg) if dense else (
        s["experts"] * expert_params(cfg) + router_params(cfg))
    return mixer + ffn + 2 * s["d"]


def total_params(cfg: Dict) -> int:
    """Every parameter: the layers, the embedding (the head is tied to
    it), the final norm."""
    s = dims(cfg)
    types = list(cfg["layer_types"])[:s["layers"]]
    return (sum(layer_params(cfg, t != ATTENTION, i < s["dense_layers"])
                for i, t in enumerate(types))
            + s["vocab"] * s["d"] + s["d"])


def expected_distinct_experts(num_experts: int, top_k: int,
                              batch: float) -> float:
    """Experts a layer touches for ``batch`` tokens choosing ``top_k``
    distinct experts each, uniformly and independently."""
    return num_experts * (1.0 - (1.0 - top_k / num_experts) ** batch)


def kv_bytes_per_token_layer(cfg: Dict, bytes_per_el: int = 2) -> int:
    """K and V of one position of one ATTENTION layer: 2 KiB at 8 heads
    of 64 in bf16."""
    s = dims(cfg)
    return 2 * s["kv_heads"] * s["head_dim"] * bytes_per_el


def attention_bytes(cfg: Dict, rows: float) -> float:
    """Bytes the attention of one decode step must read for ``rows`` live
    cached positions, all slots together: every attention layer's K and V
    of each once, WHATEVER implements it (a pool that pads its lanes or a
    copy beside the kernel reads more and shows as a low share)."""
    return dims(cfg)["attn_layers"] * rows * kv_bytes_per_token_layer(cfg)


def conv_state_bytes_per_slot_layer(cfg: Dict, bytes_per_el: int = 2) -> int:
    """``conv_L_cache - 1`` rows of d numbers: 8 KB at 2 x 2048 in bf16."""
    s = dims(cfg)
    return (s["taps"] - 1) * s["d"] * bytes_per_el


def state_bytes_per_slot(cfg: Dict) -> int:
    """What a slot holds beside its pages, all conv layers."""
    return dims(cfg)["conv_layers"] * conv_state_bytes_per_slot_layer(cfg)


def shortconv_mixer_bytes(cfg: Dict, batch: Optional[int] = None) -> float:
    """Bytes ALL the conv mixers of one decode step must move: each
    layer's weights once (bf16) and every slot's state read AND
    written."""
    batch = cfg["decode_slots"] if batch is None else batch
    s = dims(cfg)
    return s["conv_layers"] * (
        2.0 * conv_mixer_params(cfg)
        + 2 * batch * conv_state_bytes_per_slot_layer(cfg))


def decode_step_parts(cfg: Dict, live_tokens: float,
                      batch: Optional[int] = None) -> Dict[str, float]:
    """``decode_step_bytes`` by part, for the cell's ``why`` and PERF.md."""
    s = dims(cfg)
    batch = cfg["decode_slots"] if batch is None else batch
    read = expected_distinct_experts(s["experts"], s["top_k"], batch)
    return {
        "dense_ffn": 2.0 * s["dense_layers"] * dense_ffn_params(cfg),
        "conv_mixers": shortconv_mixer_bytes(cfg, batch),
        "attention_weights": 2.0 * s["attn_layers"] * attention_params(cfg),
        "experts": 2.0 * s["expert_layers"] * (
            read * expert_params(cfg) + router_params(cfg)),
        "kv": attention_bytes(cfg, live_tokens),
        "head": 2.0 * s["d"] * s["vocab"]}


def decode_step_bytes(cfg: Dict, live_tokens: float,
                      batch: Optional[int] = None) -> float:
    """Least bytes one decode step must move from HBM: the dense layers'
    SwiGLUs, the conv mixers (weights, and every slot's state both ways:
    the term that does not grow with the context), the attention layers'
    weights, the experts a batch of ``batch`` tokens is expected to touch
    (``expected_distinct_experts``: 55.9 of 64 at 32 x top-4) with the
    routers, the head (the embedding, tied), and the K/V of every live
    cached token of the attention layers once."""
    return sum(decode_step_parts(cfg, live_tokens, batch).values())
