"""Published DeepSeek-V3.2 (``deepseek_v32``) keys -> the program's
``MLAModel`` with a compressed query, YaRN, a lightning indexer, the
group-limited sigmoid router and one chip's share of the experts
(``ray_tpu/models/mla.py``, ``ray_tpu/models/moe.py``), and the reference
to compare with.

The configuration's ``n_routed_experts`` is the experts HELD here;
``router_experts`` is the router's published width and
``experts_held_first`` where the held range starts."""

from __future__ import annotations

from typing import Dict, Optional

REFERENCE = "deepseek_v32"


def held(cfg: Dict):
    """(first, count) of the router's experts this configuration holds."""
    return int(cfg.get("experts_held_first", 0)), int(cfg["n_routed_experts"])


def program_config(cfg: Dict, max_seq_len: int, extra: Optional[Dict] = None):
    from ray_tpu.models.mla import MLAConfig
    from ray_tpu.ops.rope import YarnScaling

    only = {"topk_method": "noaux_tc", "scoring_func": "sigmoid",
            "moe_layer_freq": 1, "attention_bias": False,
            "hidden_act": "silu", "num_nextn_predict_layers": 0}
    for key, value in only.items():
        if cfg.get(key) != value:
            raise ValueError(
                f"models/mla.py and models/moe.py have {key} = {value!r} "
                f"alone, got {cfg.get(key)!r}")
    yarn = cfg["rope_scaling"]
    if yarn["type"] != "yarn" or yarn["mscale"] != yarn["mscale_all_dim"]:
        raise ValueError(
            "the latent path's RoPE is YaRN with cos and sin unscaled "
            f"(mscale = mscale_all_dim), got {yarn}")
    extra = dict(extra or {})
    if cfg.get("compute_dtype") == "float32":      # the --tiny-cpu widths
        import jax.numpy as jnp
        extra.setdefault("dtype", jnp.float32)
    first, count = held(cfg)
    return MLAConfig(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        ffn_dim=cfg["moe_intermediate_size"], max_seq_len=max_seq_len,
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        num_experts=cfg["router_experts"], experts_held=count,
        first_expert_held=first,
        router_n_group=cfg["n_group"], router_topk_group=cfg["topk_group"],
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
        index_n_heads=cfg["index_n_heads"],
        index_head_dim=cfg["index_head_dim"], index_topk=cfg["index_topk"],
        **extra)


def build_model(cfg: Dict, max_seq_len: int, mesh=None,
                extra: Optional[Dict] = None):
    from ray_tpu.models import model_for
    return model_for(program_config(cfg, max_seq_len, extra), mesh=mesh)


ATTENTION = {"attn_norm": "attn_norm", "wq_a": "q_a_proj",
             "q_norm": "q_a_layernorm", "wq_b": "q_b_proj",
             "wkv_a": "kv_a_proj", "kv_norm": "kv_a_layernorm",
             "wo": "o_proj", "mlp_norm": "mlp_norm",
             "idx_wq": "indexer_wq_b", "idx_wk": "indexer_wk",
             "idx_k_norm": "indexer_k_norm", "idx_ww": "indexer_weights_proj"}
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
    stacks as they are (the reference cuts its layers' slices itself),
    so nothing but ``kv_b_proj`` is held twice."""
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


def reference_kwargs(cfg: Dict) -> Dict:
    yarn = cfg["rope_scaling"]
    return dict(
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        kv_lora_rank=cfg["kv_lora_rank"],
        rope_theta=float(cfg["rope_theta"]),
        yarn=(float(yarn["factor"]), yarn["original_max_position_embeddings"],
              float(yarn["beta_fast"]), float(yarn["beta_slow"])),
        mscale_all_dim=float(yarn["mscale_all_dim"]),
        rms_norm_eps=float(cfg["rms_norm_eps"]),
        index_topk=cfg["index_topk"],
        top_k=cfg["num_experts_per_tok"], n_group=cfg["n_group"],
        topk_group=cfg["topk_group"],
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        norm_topk_prob=bool(cfg["norm_topk_prob"]),
        experts_held=held(cfg))


def reference_forward(cfg: Dict, fault: Optional[str] = None):
    """``f(system_params, tokens)`` through ``benchmark/reference/
    deepseek_v32.py``: the float32 logits as ``RowsOfLogits``, which
    computes the rows the harness slices out of it. ``fault``: one of the
    reference's deliberate departures, for the controls."""
    from benchmark.reference import deepseek_v32

    def forward(params, tokens, **kw):
        return deepseek_v32.forward_rows(
            reference_params(cfg, params), tokens, **reference_kwargs(cfg),
            fault=fault, **kw)

    return forward
