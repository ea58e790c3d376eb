"""Operations and bytes of a hybrid STATE-SPACE decoder whose layers are
ONE sublayer each, by a pattern string (published ``nemotron_h`` key
names): ``M`` Mamba-2, ``*`` GQA attention, ``E`` experts in a latent of
which this chip HOLDS A SHARE (``n_routed_experts`` is the experts held,
``router_experts`` the router's width). As in the other costs files: what
the ALGORITHM needs, weights in the served dtype (bf16), whatever
implements it.

What differs from every other configuration: a decode step's bytes hold
a term that DOES NOT GROW with the context. A slot carries, a Mamba
layer, ``S`` (heads x head_dim x state numbers in float32: 4 MiB) and
the convolution's last ``conv_kernel - 1`` inputs (61,440 B in bf16),
and a step reads and writes both whole, whatever the slot's length;
only the ONE attention layer a period reads rows that grow (1 KiB of K
and V a token). The state update does ~5 operations a number it moves
8 bytes of: bound by bytes by two orders of magnitude.
"""

from __future__ import annotations

from typing import Dict, Optional

from benchmark.costs.moe_transformer import expected_distinct_experts

STATE_BYTES_PER_EL = 4          # S is held in float32
UPDATE_FLOPS_PER_EL = 5         # decay x S, dt x (x) B, +, x C, + into y


def dims(cfg: Dict) -> Dict:
    pattern = cfg["hybrid_override_pattern"]
    heads, hd = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    gn = cfg["n_groups"] * cfg["ssm_state_size"]
    return {"d": cfg["hidden_size"], "pattern": pattern,
            "mamba_layers": pattern.count("M"),
            "attn_layers": pattern.count("*"),
            "moe_layers": pattern.count("E"),
            "m_heads": heads, "m_head_dim": hd, "inner": heads * hd,
            "state": cfg["ssm_state_size"], "groups": cfg["n_groups"],
            "conv_channels": heads * hd + 2 * gn,
            "conv_kernel": cfg["conv_kernel"],
            "heads": cfg["num_attention_heads"],
            "kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["head_dim"],
            "latent": cfg["moe_latent_size"],
            "ff": cfg["moe_intermediate_size"],
            "shared_ff": (cfg["n_shared_experts"]
                          * cfg["moe_shared_expert_intermediate_size"]),
            "held": cfg["n_routed_experts"],
            "experts": cfg["router_experts"],
            "top_k": cfg["num_experts_per_tok"], "vocab": cfg["vocab_size"]}


def mamba_matmul_params(cfg: Dict) -> int:
    """A Mamba layer's two projections and its convolution (bf16)."""
    s = dims(cfg)
    return (s["d"] * (s["inner"] + s["conv_channels"] + s["m_heads"])
            + s["conv_channels"] * (s["conv_kernel"] + 1)
            + s["inner"] * s["d"])


def mamba_params(cfg: Dict) -> int:
    """... and dt_bias, A_log, D, the gated norm's weight, the block norm."""
    s = dims(cfg)
    return mamba_matmul_params(cfg) + 3 * s["m_heads"] + s["inner"] + s["d"]


def attention_params(cfg: Dict) -> int:
    """q, k, v, o (no bias) and the block norm."""
    s = dims(cfg)
    q, kv = s["heads"] * s["head_dim"], s["kv_heads"] * s["head_dim"]
    return 2 * s["d"] * q + 2 * s["d"] * kv + s["d"]


def expert_params(cfg: Dict) -> int:
    """One routed expert: two matrices in the latent, no gate matrix."""
    s = dims(cfg)
    return 2 * s["latent"] * s["ff"]


def shared_params(cfg: Dict) -> int:
    s = dims(cfg)
    return 2 * s["d"] * s["shared_ff"]


def latent_params(cfg: Dict) -> int:
    """The two latent projections."""
    s = dims(cfg)
    return 2 * s["d"] * s["latent"]


def router_params(cfg: Dict) -> int:
    """The router's matrix and its selection bias (both float32)."""
    s = dims(cfg)
    return s["d"] * s["experts"] + s["experts"]


def moe_layer_params(cfg: Dict, experts: Optional[int] = None) -> int:
    s = dims(cfg)
    experts = s["held"] if experts is None else experts
    return (router_params(cfg) + latent_params(cfg) + shared_params(cfg)
            + experts * expert_params(cfg) + s["d"])


def total_params(cfg: Dict) -> int:
    """Every parameter this chip holds: the three kinds' layers, the
    embedding and head slices, the final norm."""
    s = dims(cfg)
    return (s["mamba_layers"] * mamba_params(cfg)
            + s["attn_layers"] * attention_params(cfg)
            + s["moe_layers"] * moe_layer_params(cfg)
            + 2 * s["vocab"] * s["d"] + s["d"])


def expected_held_experts_hit(cfg: Dict, batch: int) -> float:
    """Distinct HELD experts ``batch`` tokens hit a layer under uniform
    routing over the router's experts: 120.3 of 128 at 64 tokens x top-22
    of 512."""
    s = dims(cfg)
    return (expected_distinct_experts(s["experts"], s["top_k"], batch)
            * s["held"] / s["experts"])


def kv_bytes_per_token_layer(cfg: Dict, bytes_per_el: int = 2) -> int:
    """K and V of one position of one ATTENTION layer: 1 KiB."""
    s = dims(cfg)
    return 2 * s["kv_heads"] * s["head_dim"] * bytes_per_el


def ssm_state_bytes_per_slot_layer(cfg: Dict) -> int:
    """``S`` of one slot of one Mamba layer: 4 MiB in float32."""
    s = dims(cfg)
    return s["inner"] * s["state"] * STATE_BYTES_PER_EL


def conv_window_bytes_per_slot_layer(cfg: Dict, bytes_per_el: int = 2) -> int:
    s = dims(cfg)
    return (s["conv_kernel"] - 1) * s["conv_channels"] * bytes_per_el


def state_bytes_per_slot(cfg: Dict) -> int:
    """What a slot holds beside its pages, all Mamba layers."""
    return dims(cfg)["mamba_layers"] * (
        ssm_state_bytes_per_slot_layer(cfg)
        + conv_window_bytes_per_slot_layer(cfg))


def ssm_state_update_bytes(cfg: Dict, batch: Optional[int] = None) -> float:
    """Bytes the state update of one decode step must move: every
    slot's ``S`` of every Mamba layer read AND written."""
    batch = cfg["decode_slots"] if batch is None else batch
    return (dims(cfg)["mamba_layers"] * batch * 2
            * ssm_state_bytes_per_slot_layer(cfg))


def ssm_state_update_flops(cfg: Dict, batch: Optional[int] = None) -> float:
    batch = cfg["decode_slots"] if batch is None else batch
    s = dims(cfg)
    return (s["mamba_layers"] * batch * s["inner"] * s["state"]
            * UPDATE_FLOPS_PER_EL)


def ssm_scan_flops(cfg: Dict, tokens: int) -> float:
    """FLOPs the chunked scan of ``tokens`` positions of ONE row of ONE
    layer needs (``chunk_size`` Q a chunk): ``C B^T`` (Q^2 G N), its
    product with ``x`` (Q^2 H P), what the carried state adds to ``y``
    and the state after the chunk (Q N H P each), two FLOPs a
    multiply-add."""
    s = dims(cfg)
    q = int(cfg["chunk_size"])
    hp = s["inner"]
    a_chunk = 2.0 * (q * q * int(cfg["n_groups"]) * s["state"] + q * q * hp
                     + 2 * q * s["state"] * hp)
    return -(-tokens // q) * a_chunk


def ssm_scan_bytes(cfg: Dict, tokens: int, bytes_per_el: int = 2) -> float:
    """Bytes that scan must move: ``x`` in, ``y`` out (float32), ``B``
    and ``C``, and ``S`` in and out ONCE (what it needs, whatever
    implements it: a scan that carries ``S`` through memory chunk by
    chunk moves more)."""
    s = dims(cfg)
    gn = int(cfg["n_groups"]) * s["state"]
    return (tokens * (s["inner"] * (bytes_per_el + 4) + 2 * gn * bytes_per_el)
            + 2 * ssm_state_bytes_per_slot_layer(cfg))


def decode_weight_bytes(cfg: Dict, batch: Optional[int] = None,
                        weight_bytes_per_param: int = 2) -> float:
    """The matmul weights a step reads once (Mamba projections and
    convolution, attention, latent projections, shared expert, the
    EXPECTED distinct held experts ``batch`` tokens hit a layer, the
    head's slice) in bf16 and the float32 routers with their biases."""
    s = dims(cfg)
    batch = cfg["decode_slots"] if batch is None else batch
    hit = expected_held_experts_hit(cfg, batch)
    bf16 = (s["mamba_layers"] * mamba_matmul_params(cfg)
            + s["attn_layers"] * (attention_params(cfg) - s["d"])
            + s["moe_layers"] * (latent_params(cfg) + shared_params(cfg)
                                 + hit * expert_params(cfg))
            + s["d"] * s["vocab"])
    return (bf16 * weight_bytes_per_param
            + s["moe_layers"] * router_params(cfg) * 4)


def decode_step_bytes(cfg: Dict, live_tokens: float,
                      weight_bytes_per_param: int = 2,
                      batch: Optional[int] = None) -> float:
    """Least bytes one decode step must move from HBM: the weights
    (``decode_weight_bytes``), every slot's recurrent state read and
    written (``S`` and the convolution's window: the term that does not
    grow with the context), and the K/V of every live cached token of
    the attention layers once."""
    s = dims(cfg)
    batch = cfg["decode_slots"] if batch is None else batch
    return (decode_weight_bytes(cfg, batch, weight_bytes_per_param)
            + 2 * batch * state_bytes_per_slot(cfg)
            + s["attn_layers"] * live_tokens * kv_bytes_per_token_layer(cfg))
