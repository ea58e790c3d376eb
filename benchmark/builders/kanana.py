"""Published kanana-2 (``deepseek_v3``) keys -> the program's ``MLAModel``
(latent attention on the expert block, a sigmoid router with a selection
bias, shared experts, leading dense layers: ``ray_tpu/models/mla.py``,
``ray_tpu/models/moe.py``), and the reference to compare with."""

from __future__ import annotations

from typing import Dict, Optional

REFERENCE = "kanana"


def program_config(cfg: Dict, max_seq_len: int, extra: Optional[Dict] = None):
    from ray_tpu.models.mla import MLAConfig

    unsupported = {
        "q_lora_rank": None, "rope_scaling": None, "n_group": 1,
        "topk_group": 1, "topk_method": "noaux_tc", "scoring_func": "sigmoid",
        "moe_layer_freq": 1, "attention_bias": False, "hidden_act": "silu",
        "rope_interleave": True}
    for key, only in unsupported.items():
        if cfg.get(key) != only:
            raise ValueError(
                f"models/mla.py and models/moe.py have {key} = {only!r} "
                f"alone, got {cfg.get(key)!r}")
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
        norm_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        num_experts=cfg["n_routed_experts"],
        expert_top_k=cfg["num_experts_per_tok"],
        norm_topk_prob=bool(cfg["norm_topk_prob"]), router_kind="sigmoid",
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        router_bias_init_std=float(cfg["router_bias_init_std"]),
        shared_ffn_dim=cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        leading_layers=cfg["first_k_dense_replace"],
        leading_ffn_dim=cfg["intermediate_size"],
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], **extra)


def build_model(cfg: Dict, max_seq_len: int, mesh=None,
                extra: Optional[Dict] = None):
    from ray_tpu.models import model_for
    return model_for(program_config(cfg, max_seq_len, extra), mesh=mesh)


ATTENTION = {"attn_norm": "attn_norm", "wq": "q_proj", "wkv_a": "kv_a_proj",
             "kv_norm": "kv_a_layernorm", "wo": "o_proj",
             "mlp_norm": "mlp_norm"}
DENSE = {"w_gate": "gate", "w_up": "up", "w_down": "down"}


def _stack(layers, names: Dict):
    """One stack of the system's layers under the reference's names;
    ``kv_b_proj`` [L, R, H, nope + v] is the system's two halves of it a
    head (``w_uk`` [L, H, nope, R], ``w_uv`` [L, H, R, v]) side by side."""
    import jax.numpy as jnp

    out = {new: layers[old] for old, new in {**ATTENTION, **names}.items()}
    out["kv_b_proj"] = jnp.concatenate(
        [jnp.moveaxis(layers["w_uk"], 3, 1), jnp.moveaxis(layers["w_uv"], 2, 1)],
        axis=-1)
    return out


def reference_params(cfg: Dict, params):
    """The system's own arrays under the reference's names: the layer
    stacks as they are (the reference cuts its layers' slices itself, one
    layer at a time), so nothing but ``kv_b_proj`` (8 MB a layer) is held
    twice."""
    moe = {name: name for name in (
        "router", "router_bias", "e_gate", "e_up", "e_down", "s_gate", "s_up",
        "s_down")}
    head = (params["embed"].T if cfg["tie_word_embeddings"]
            else params["lm_head"])
    return {"embed": params["embed"],
            "dense_layers": ({} if "leading_layers" not in params
                             else _stack(params["leading_layers"], DENSE)),
            "moe_layers": _stack(params["layers"], moe),
            "norm_f": params["norm_f"], "lm_head": head}


def reference_forward(cfg: Dict, fault: Optional[str] = None):
    """``f(system_params, tokens)`` through ``benchmark/reference/
    kanana.py``: the float32 logits as ``RowsOfLogits``, which computes
    the rows the harness slices out of it (``[:, a:b]``, ``[0, a:b]``) and
    no [S, 128,256] array. ``fault``: one of the reference's deliberate
    departures, for the controls."""
    from benchmark.reference import kanana

    def forward(params, tokens, **kw):
        return kanana.forward_rows(
            reference_params(cfg, params), tokens,
            qk_nope_head_dim=cfg["qk_nope_head_dim"],
            qk_rope_head_dim=cfg["qk_rope_head_dim"],
            kv_lora_rank=cfg["kv_lora_rank"],
            rope_theta=float(cfg["rope_theta"]),
            rms_norm_eps=float(cfg["rms_norm_eps"]),
            top_k=cfg["num_experts_per_tok"],
            routed_scaling_factor=float(cfg["routed_scaling_factor"]),
            norm_topk_prob=bool(cfg["norm_topk_prob"]), fault=fault, **kw)

    return forward
