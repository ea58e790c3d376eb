"""Operations and bytes of a hybrid MAMBA-1 decoder whose layers are TWO
sublayers each (published ``jamba`` key names): a mixer (attention where
``i % attn_layer_period == attn_layer_offset``, else Mamba-1) and a dense
SwiGLU. As in the other costs files: what the ALGORITHM needs, weights in
the served dtype (bf16), whatever implements it.

What differs from ``ssm_latent_moe_transformer`` (Mamba-2): the decay is a
number a (channel, state index) pair, ``exp(dt[d] A[d, n])``, so

- the decode update of a slot of a layer reads and writes ``S`` (inner x
  state numbers in float32: 327,680 B) and needs ONE EXPONENTIAL a number
  beside ~6 other operations; the decay is made where it is used from
  ``dt`` (inner numbers a slot) and ``A`` (inner x state numbers a LAYER,
  read once a step, not once a slot): 8 B a number moved, not 12;
- the prefill scan has no matmul form: a position of a layer needs inner
  x state exponentials (81,920) and ~7 other operations a number, and
  moves ``u`` in, ``dt`` in, ``y`` out; its least time is the larger of
  the exponentials over the chip's transcendental rate and those bytes
  over the memory's, not the MXU's.
"""

from __future__ import annotations

from typing import Dict, Optional

STATE_BYTES_PER_EL = 4          # S is held in float32
# decay x S, dt u x B, +, x C, + into y, dt x A (before the exponential)
UPDATE_FLOPS_PER_EL = 6
# v5e: the transcendental unit retires one vector register (8 x 128
# lanes) a cycle at 940 MHz. Not in the public system-architecture table
# (``lib/peaks.py`` holds only what is): a DERIVED rate, stated here with
# the costs that use it
V5E_EXP_PER_S = 1024 * 940e6


def dims(cfg: Dict) -> Dict:
    layers = cfg["num_hidden_layers"]
    attn = sum(i % cfg["attn_layer_period"] == cfg["attn_layer_offset"]
               for i in range(layers))
    heads = cfg["num_attention_heads"]
    return {"d": cfg["hidden_size"], "layers": layers,
            "attn_layers": attn, "mamba_layers": layers - attn,
            "inner": cfg["mamba_expand"] * cfg["hidden_size"],
            "state": cfg["mamba_d_state"], "dt_rank": cfg["mamba_dt_rank"],
            "conv_kernel": cfg["mamba_d_conv"],
            "heads": heads, "kv_heads": cfg["num_key_value_heads"],
            "head_dim": int(cfg.get("head_dim")
                            or cfg["hidden_size"] // heads),
            "ff": cfg["intermediate_size"], "vocab": cfg["vocab_size"]}


def mamba_matmul_params(cfg: Dict) -> int:
    """A Mamba mixer's four projections and its convolution (bf16)."""
    s = dims(cfg)
    return (s["d"] * 2 * s["inner"] + s["inner"] * (s["conv_kernel"] + 1)
            + s["inner"] * (s["dt_rank"] + 2 * s["state"])
            + s["dt_rank"] * s["inner"] + s["inner"] * s["d"])


def mamba_params(cfg: Dict) -> int:
    """... and dt_proj's bias, A_log, D and the three inner norms."""
    s = dims(cfg)
    return (mamba_matmul_params(cfg) + s["inner"] + s["inner"] * s["state"]
            + s["inner"] + s["dt_rank"] + 2 * s["state"])


def attention_params(cfg: Dict) -> int:
    """q, k, v, o (no bias)."""
    s = dims(cfg)
    q, kv = s["heads"] * s["head_dim"], s["kv_heads"] * s["head_dim"]
    return 2 * s["d"] * q + 2 * s["d"] * kv


def swiglu_params(cfg: Dict) -> int:
    s = dims(cfg)
    return 3 * s["d"] * s["ff"]


def total_params(cfg: Dict) -> int:
    """Every parameter: the layers (a mixer, a SwiGLU, two norms each),
    the embedding (the head is tied to it), the final norm."""
    s = dims(cfg)
    sublayers = swiglu_params(cfg) + 2 * s["d"]
    return (s["mamba_layers"] * (mamba_params(cfg) + sublayers)
            + s["attn_layers"] * (attention_params(cfg) + sublayers)
            + s["vocab"] * s["d"] + s["d"])


def kv_bytes_per_token_layer(cfg: Dict, bytes_per_el: int = 2) -> int:
    """K and V of one position of one ATTENTION layer: 512 B at one head."""
    s = dims(cfg)
    return 2 * s["kv_heads"] * s["head_dim"] * bytes_per_el


def ssm_state_bytes_per_slot_layer(cfg: Dict) -> int:
    """``S`` of one slot of one Mamba layer: 327,680 B in float32."""
    s = dims(cfg)
    return s["inner"] * s["state"] * STATE_BYTES_PER_EL


def conv_window_bytes_per_slot_layer(cfg: Dict, bytes_per_el: int = 2) -> int:
    s = dims(cfg)
    return (s["conv_kernel"] - 1) * s["inner"] * bytes_per_el


def state_bytes_per_slot(cfg: Dict) -> int:
    """What a slot holds beside its pages, all Mamba layers."""
    return dims(cfg)["mamba_layers"] * (
        ssm_state_bytes_per_slot_layer(cfg)
        + conv_window_bytes_per_slot_layer(cfg))


def ssm_a_bytes(cfg: Dict) -> int:
    """``A`` of every Mamba layer, float32: read once a step."""
    s = dims(cfg)
    return s["mamba_layers"] * s["inner"] * s["state"] * 4


def ssm_state_update_bytes(cfg: Dict, batch: Optional[int] = None) -> float:
    """Bytes the state update of one decode step must move: every slot's
    ``S`` of every Mamba layer read AND written, and each layer's ``A``
    once. NOT the decay: it is made from ``dt`` and ``A`` where it is
    used."""
    batch = cfg["decode_slots"] if batch is None else batch
    return (dims(cfg)["mamba_layers"] * batch * 2
            * ssm_state_bytes_per_slot_layer(cfg) + ssm_a_bytes(cfg))


def ssm_state_update_exps(cfg: Dict, batch: Optional[int] = None) -> float:
    batch = cfg["decode_slots"] if batch is None else batch
    s = dims(cfg)
    return s["mamba_layers"] * batch * s["inner"] * s["state"]


def ssm_state_update_flops(cfg: Dict, batch: Optional[int] = None) -> float:
    return ssm_state_update_exps(cfg, batch) * UPDATE_FLOPS_PER_EL


def ssm_state_update_least_s(cfg: Dict, peaks: Dict,
                             batch: Optional[int] = None) -> float:
    """The least time of one decode step's updates, all Mamba layers: the
    larger of the bytes over the memory's rate and the operations over
    the chip's peak (the bytes, by two orders of magnitude; the
    exponentials, 68 M a step at 32 slots, are ~70 us of the
    transcendental unit beside ~690 us of bytes)."""
    return max(
        ssm_state_update_bytes(cfg, batch) / peaks["hbm_bytes_per_s"],
        ssm_state_update_flops(cfg, batch) / peaks["bf16_flops_per_s"])


def ssm_scan_exps(cfg: Dict, tokens: int) -> float:
    """Exponentials the scan of ``tokens`` positions of ONE layer needs:
    inner x state a position (81,920)."""
    s = dims(cfg)
    return float(tokens) * s["inner"] * s["state"]


def ssm_scan_flops(cfg: Dict, tokens: int) -> float:
    """... and its other operations: dt x A, decay x S, dt u x B, +, x C,
    the sum over the state index, a number a position."""
    return ssm_scan_exps(cfg, tokens) * 7


def ssm_scan_bytes(cfg: Dict, tokens: int, bytes_per_el: int = 2) -> float:
    """Bytes that scan must move: ``u`` in (bf16), ``dt`` in and ``y``
    out (float32 each), ``B`` and ``C`` (float32), and one row's ``S`` in
    and out ONCE."""
    s = dims(cfg)
    return (tokens * (s["inner"] * (bytes_per_el + 4 + 4)
                      + 2 * s["state"] * 4)
            + 2 * ssm_state_bytes_per_slot_layer(cfg))


def ssm_scan_least_s(cfg: Dict, peaks: Dict, tokens: int,
                     exp_per_s: Optional[float] = V5E_EXP_PER_S) -> float:
    """The least time of ONE layer's scan over ``tokens`` positions: the
    larger of its exponentials over the transcendental unit's rate and
    its bytes over the memory's."""
    least = ssm_scan_bytes(cfg, tokens) / peaks["hbm_bytes_per_s"]
    if exp_per_s:
        least = max(least, ssm_scan_exps(cfg, tokens) / exp_per_s)
    return least


def decode_weight_bytes(cfg: Dict, batch: Optional[int] = None,
                        weight_bytes_per_param: int = 2) -> float:
    """The matmul weights a step reads once (every layer's SwiGLU, the
    Mamba projections and convolutions, the attention projections, the
    head = the embedding) in bf16 and every Mamba layer's ``A``, float32."""
    s = dims(cfg)
    bf16 = (s["layers"] * swiglu_params(cfg)
            + s["mamba_layers"] * mamba_matmul_params(cfg)
            + s["attn_layers"] * attention_params(cfg)
            + s["d"] * s["vocab"])
    return bf16 * weight_bytes_per_param + ssm_a_bytes(cfg)


def decode_step_parts(cfg: Dict, live_tokens: float,
                      batch: Optional[int] = None) -> Dict[str, float]:
    """``decode_step_bytes`` by part, for the cell's ``why`` and PERF.md."""
    s = dims(cfg)
    batch = cfg["decode_slots"] if batch is None else batch
    return {
        "swiglu": 2.0 * s["layers"] * swiglu_params(cfg),
        "mamba_projections": 2.0 * s["mamba_layers"]
        * mamba_matmul_params(cfg),
        "state": 2.0 * batch * state_bytes_per_slot(cfg) + ssm_a_bytes(cfg),
        "attention_weights": 2.0 * s["attn_layers"] * attention_params(cfg),
        "kv": s["attn_layers"] * live_tokens * kv_bytes_per_token_layer(cfg),
        "head": 2.0 * s["d"] * s["vocab"]}


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
