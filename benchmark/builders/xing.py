"""Published Xing4.0 (``xing4_0``) keys -> the program's ``MLAModel`` with
``hc_mult`` residual streams mixed by manifold-constrained hyper-
connections, a compressed query, YaRN and the sigmoid router
(``ray_tpu/models/llama.py``, ``ray_tpu/models/mla.py``,
``ray_tpu/models/moe.py``, ``ray_tpu/ops/mhc.py``), and the reference to
compare with."""

from __future__ import annotations

from typing import Dict, Optional

REFERENCE = "xing"


def program_config(cfg: Dict, max_seq_len: int, extra: Optional[Dict] = None):
    from ray_tpu.models.mla import MLAConfig
    from ray_tpu.ops.rope import YarnScaling

    only = {"topk_method": "noaux_tc", "scoring_func": "sigmoid",
            "n_group": 1, "topk_group": 1, "moe_layer_freq": 1,
            "attention_bias": False, "hidden_act": "silu", "ep_size": 1,
            "num_nextn_predict_layers": 0, "tie_word_embeddings": False,
            "rope_interleave": True}
    for key, value in only.items():
        if cfg.get(key) != value:
            raise ValueError(
                f"models/mla.py, models/moe.py and ops/mhc.py have {key} = "
                f"{value!r} alone, got {cfg.get(key)!r}")
    yarn = cfg["rope_scaling"]
    if yarn["type"] != "yarn" or yarn["mscale"] != yarn["mscale_all_dim"]:
        raise ValueError(
            "the latent path's RoPE is YaRN with cos and sin unscaled "
            f"(mscale = mscale_all_dim), got {yarn}")
    if not cfg["q_lora_rank"] or cfg["hc_mult"] < 2:
        raise ValueError(
            "this builder's model has a compressed query (q_lora_rank) and "
            f"more than one residual stream (hc_mult), got "
            f"{cfg['q_lora_rank']!r} and {cfg['hc_mult']!r}")
    extra = dict(extra or {})
    if cfg.get("compute_dtype") == "float32":      # the --tiny-cpu widths
        import jax.numpy as jnp
        extra.setdefault("dtype", jnp.float32)
    return MLAConfig(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        ffn_dim=cfg["moe_intermediate_size"], max_seq_len=max_seq_len,
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]), tie_embeddings=False,
        num_experts=cfg["n_routed_experts"],
        expert_top_k=cfg["num_experts_per_tok"],
        norm_topk_prob=bool(cfg["norm_topk_prob"]), router_kind="sigmoid",
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        router_bias_init_std=float(cfg["router_bias_init_std"]),
        shared_ffn_dim=cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        leading_layers=cfg["first_k_dense_replace"],
        leading_ffn_dim=cfg["intermediate_size"],
        kv_lora_rank=cfg["kv_lora_rank"], q_lora_rank=cfg["q_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        yarn=YarnScaling(
            factor=float(yarn["factor"]),
            original_max_position=yarn["original_max_position_embeddings"],
            beta_fast=float(yarn["beta_fast"]),
            beta_slow=float(yarn["beta_slow"]), attention_factor=1.0),
        yarn_mscale_all_dim=float(yarn["mscale_all_dim"]),
        hc_mult=cfg["hc_mult"], hc_sinkhorn_iters=cfg["hc_sinkhorn_iters"],
        hc_eps=float(cfg["hc_eps"]),
        hc_res_clamp=(float(cfg["mhc_h_res_clamp_min"]),
                      float(cfg["mhc_h_res_clamp_max"])), **extra)


def build_model(cfg: Dict, max_seq_len: int, mesh=None,
                extra: Optional[Dict] = None):
    from ray_tpu.models import model_for
    return model_for(program_config(cfg, max_seq_len, extra), mesh=mesh)


ATTENTION = {"attn_norm": "attn_norm", "wq_a": "q_a_proj",
             "q_norm": "q_a_layernorm", "wq_b": "q_b_proj",
             "wkv_a": "kv_a_proj", "kv_norm": "kv_a_layernorm",
             "wo": "o_proj", "mlp_norm": "mlp_norm"}
DENSE = {"w_gate": "gate", "w_up": "up", "w_down": "down"}


def _stack(layers, names: Dict):
    """One stack of the system's layers under the reference's names:
    ``kv_b_proj`` [L, R, H, nope + v] is the system's two halves of it a
    head side by side; a sublayer's ``Phi`` [L, n d, 2n + n^2] is the
    system's ``phi`` (held transposed: ``ops/mhc.py``) turned back."""
    import jax.numpy as jnp

    out = {new: layers[old] for old, new in {**ATTENTION, **names}.items()}
    out["kv_b_proj"] = jnp.concatenate(
        [jnp.moveaxis(layers["w_uk"], 3, 1), jnp.moveaxis(layers["w_uv"], 2, 1)],
        axis=-1)
    for sub in ("attn", "mlp"):
        hc = layers[sub + "_hc"]
        out[sub + "_hc_phi"] = jnp.swapaxes(hc["phi"], 1, 2)
        out[sub + "_hc_alpha"] = hc["alpha"]
        out[sub + "_hc_bias"] = hc["bias"]
    return out


def reference_params(cfg: Dict, params):
    """The system's own arrays under the reference's names: the layer
    stacks as they are (the reference cuts its layers' slices itself), so
    nothing but ``kv_b_proj`` (8 MB a layer) and ``Phi`` (1.4 MB a
    sublayer) is held twice."""
    moe = {name: name for name in (
        "router", "router_bias", "e_gate", "e_up", "e_down", "s_gate", "s_up",
        "s_down")}
    return {"embed": params["embed"],
            "dense_layers": ({} if "leading_layers" not in params
                             else _stack(params["leading_layers"], DENSE)),
            "moe_layers": _stack(params["layers"], moe),
            "norm_f": params["norm_f"], "lm_head": params["lm_head"]}


def reference_kwargs(cfg: Dict) -> Dict:
    yarn = cfg["rope_scaling"]
    return dict(
        hc_mult=cfg["hc_mult"], hc_sinkhorn_iters=cfg["hc_sinkhorn_iters"],
        hc_eps=float(cfg["hc_eps"]),
        hc_res_clamp=(float(cfg["mhc_h_res_clamp_min"]),
                      float(cfg["mhc_h_res_clamp_max"])),
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        kv_lora_rank=cfg["kv_lora_rank"],
        rope_theta=float(cfg["rope_theta"]),
        yarn=(float(yarn["factor"]), yarn["original_max_position_embeddings"],
              float(yarn["beta_fast"]), float(yarn["beta_slow"])),
        mscale_all_dim=float(yarn["mscale_all_dim"]),
        rms_norm_eps=float(cfg["rms_norm_eps"]),
        top_k=cfg["num_experts_per_tok"],
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        norm_topk_prob=bool(cfg["norm_topk_prob"]))


def reference_forward(cfg: Dict, fault: Optional[str] = None):
    """``f(system_params, tokens)`` through ``benchmark/reference/
    xing.py``: the float32 logits as ``RowsOfLogits``, which computes the
    rows the harness slices out of it. ``fault``: one of the reference's
    deliberate departures, for the controls."""
    from benchmark.reference import xing

    def forward(params, tokens, **kw):
        return xing.forward_rows(
            reference_params(cfg, params), tokens, **reference_kwargs(cfg),
            fault=fault, **kw)

    return forward
