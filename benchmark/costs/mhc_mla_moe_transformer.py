"""Operations and bytes of a decoder-only transformer whose residual is
``hc_mult`` STREAMS mixed by manifold-constrained hyper-connections, around
LATENT attention with a COMPRESSED QUERY, leading dense layers, and an
expert FFN with a shared expert and a sigmoid router (published
``xing4_0`` key names). As in ``mla_moe_transformer``, whose terms this
imports: what the ALGORITHM needs, weights in the served dtype (bf16),
whatever implements it.

What differs is the residual path. A sublayer (two a layer) makes its
three maps from a token's ``n C`` stream lanes with ``Phi`` [n C, 2n +
n^2] (float32: 1.38 MB at 4 x 3,584 x 24, read once a sublayer whatever
the batch), reads its input out of the streams and writes its output
back: the streams read ONCE and written ONCE a row a sublayer (``mix_out``
of one sublayer and ``stream_maps`` + ``mix_in`` of the next are one pass)
and the sublayer's input and output once each. The arithmetic is ``2 n C
(2n + n^2)`` FLOPs a row for the maps' product, ``2 n^2 C + 2 n C`` for
the write and ``2 n C`` for the read: ~0.9 MFLOP a row beside 86 KB, 10
FLOP/B, far under the v5e's ridge of 240: bound by bytes wherever it is
not bound by launches (a decode step's 32 rows move 3.7 MB a sublayer,
4.5 us at the roof). No kernel implements it (XLA's fusions won at both
shapes: ``ops/mhc.py``), so no roofline share reads these two; they are
part of ``decode_step_bytes``.
"""

from __future__ import annotations

from typing import Dict, Optional

from benchmark.costs.mla_moe_transformer import (  # noqa: F401
    dense_ffn_params, expert_params, kv_bytes_per_token_layer,
    mha_kv_bytes_per_token_layer, mla_attention_bytes, mla_attention_flops,
    router_params, shared_params)
from benchmark.costs.moe_transformer import expected_distinct_experts


def dims(cfg: Dict) -> Dict:
    lead = cfg["first_k_dense_replace"]
    n = cfg["hc_mult"]
    return {"d": cfg["hidden_size"], "layers": cfg["num_hidden_layers"],
            "dense_layers": lead,
            "moe_layers": cfg["num_hidden_layers"] - lead,
            "heads": cfg["num_attention_heads"],
            "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
            "v": cfg["v_head_dim"], "rank": cfg["kv_lora_rank"],
            "q_rank": cfg["q_lora_rank"],
            "dense_ff": cfg["intermediate_size"],
            "ff": cfg["moe_intermediate_size"],
            "shared": cfg["n_shared_experts"],
            "experts": cfg["n_routed_experts"],
            "top_k": cfg["num_experts_per_tok"], "vocab": cfg["vocab_size"],
            "streams": n, "map_width": 2 * n + n * n,
            "tied": bool(cfg.get("tie_word_embeddings"))}


def attention_params(cfg: Dict) -> int:
    """One layer's compressed query (with ``q_a_layernorm``), down (with
    ``kv_a_layernorm``), up (``kv_b_proj``) and output projections:
    28,411,136 at the published widths."""
    s = dims(cfg)
    return (s["d"] * s["q_rank"] + s["q_rank"]
            + s["q_rank"] * s["heads"] * (s["nope"] + s["rope"])
            + s["d"] * (s["rank"] + s["rope"]) + s["rank"]
            + s["rank"] * s["heads"] * (s["nope"] + s["v"])
            + s["heads"] * s["v"] * s["d"])


def mhc_params(cfg: Dict) -> int:
    """ONE sublayer's stream maps: ``Phi``, three ``alpha``, ``b``
    (344,091; a layer has two sublayers)."""
    s = dims(cfg)
    return s["streams"] * s["d"] * s["map_width"] + 3 + s["map_width"]


def total_params(cfg: Dict) -> int:
    """Every parameter the model holds: embedding; per layer attention
    (its norms' scales among it), the two block norms and the two
    sublayers' stream maps; a dense layer's FFN; an expert layer's router,
    bias, experts and shared expert; the final norm; the untied head."""
    s = dims(cfg)
    common = attention_params(cfg) + 2 * s["d"] + 2 * mhc_params(cfg)
    dense = common + dense_ffn_params(cfg)
    moe = (common + router_params(cfg) + s["experts"] * expert_params(cfg)
           + shared_params(cfg))
    head = 0 if s["tied"] else s["d"] * s["vocab"]
    return (s["vocab"] * s["d"] + s["dense_layers"] * dense
            + s["moe_layers"] * moe + s["d"] + head)


def matmul_params(cfg: Dict) -> int:
    """Parameters ONE token multiplies with: attention and the stream
    maps' ``Phi`` of every layer, a dense layer's FFN, an expert layer's
    router, top-k experts and shared expert, the output head."""
    s = dims(cfg)
    moe = (s["d"] * s["experts"] + s["top_k"] * expert_params(cfg)
           + shared_params(cfg))
    return (s["layers"] * (attention_params(cfg)
                           + 2 * s["streams"] * s["d"] * s["map_width"])
            + s["dense_layers"] * dense_ffn_params(cfg)
            + s["moe_layers"] * moe + s["d"] * s["vocab"])


def mhc_bytes(cfg: Dict, rows: float, bytes_per_el: int = 2) -> float:
    """Bytes the residual path of ONE program of ``rows`` tokens must
    move: a sublayer (two a layer) reads ``Phi`` once (float32) and, a
    row, the streams once and writes them once, reads the sublayer
    before's output and writes this one's input."""
    s = dims(cfg)
    a_row = (2 * s["streams"] + 2) * s["d"] * bytes_per_el
    return 2 * s["layers"] * (
        s["streams"] * s["d"] * s["map_width"] * 4 + rows * a_row)


def mhc_flops(cfg: Dict, rows: float) -> float:
    """Its operations: the maps' product, the write (``H_res X + H_post
    y``) and the read (``H_pre X``), 2 FLOPs a multiply-add; the
    sigmoids and the Sinkhorn rounds (~40 n^2 divisions a row) are not
    counted."""
    s = dims(cfg)
    n, C = s["streams"], s["d"]
    a_row = 2 * n * C * s["map_width"] + 2 * n * n * C + 2 * n * C + 2 * n * C
    return 2 * s["layers"] * rows * a_row


def decode_step_bytes(cfg: Dict, live_tokens: float,
                      weight_bytes_per_param: int = 2,
                      batch: Optional[int] = None) -> float:
    """Least bytes one decode step must move from HBM: the bf16 matmul
    weights a step reads once (attention and the shared expert of every
    layer, the dense layers' FFN, the head), the float32 routers and
    biases, the routed weights of the EXPECTED distinct experts ``batch``
    tokens hit a layer under uniform routing (``batch`` defaults to the
    configuration's ``decode_slots``: 55.9 of 64 at 32 x top-4), every
    LIVE latent row of every layer once, and the residual path
    (``mhc_bytes`` at ``batch`` rows)."""
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
            + mla_attention_bytes(cfg, live_tokens)
            + mhc_bytes(cfg, batch))
