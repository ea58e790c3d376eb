"""Published Mellum keys -> the program's ``MoEModel`` with layer kinds
(window and full attention layers, each kind on its own rotary table;
``ray_tpu/models/llama.py``, ``ray_tpu/models/moe.py``), and the
reference to compare with."""

from __future__ import annotations

from typing import Dict, Optional

REFERENCE = "mellum"


def layer_types(cfg: Dict):
    """The kinds of the layers that are held: the published pattern's
    first ``num_hidden_layers`` entries (whole periods of it)."""
    return tuple(cfg["layer_types"][:cfg["num_hidden_layers"]])


def program_config(cfg: Dict, max_seq_len: int, extra: Optional[Dict] = None):
    from ray_tpu.models.moe import MoEConfig
    from ray_tpu.ops.rope import YarnScaling

    if cfg.get("attention_bias"):
        raise ValueError("models/llama.py has no attention bias")
    kinds = layer_types(cfg)
    if set(cfg["mlp_layer_types"][:len(kinds)]) != {"sparse"}:
        raise ValueError("models/moe.py has no dense layer among sparse ones")
    ropes = cfg["rope_parameters"]
    thetas = {float(r["rope_theta"]) for r in ropes.values()}
    if len(thetas) != 1:
        raise ValueError("models/llama.py turns every kind by one rope_theta")
    scaling = []
    for kind, r in ropes.items():
        if r["rope_type"] == "yarn":
            scaling.append((kind, YarnScaling(
                factor=float(r["factor"]),
                original_max_position=r["original_max_position_embeddings"],
                beta_fast=float(r["beta_fast"]),
                beta_slow=float(r["beta_slow"]),
                attention_factor=r.get("attention_factor"))))
        elif r["rope_type"] != "default":
            raise ValueError(f"ops/rope.py has no rope_type {r['rope_type']!r}")
    extra = dict(extra or {})
    if cfg.get("compute_dtype") == "float32":      # the --tiny-cpu widths
        import jax.numpy as jnp
        extra.setdefault("dtype", jnp.float32)
    return MoEConfig(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        ffn_dim=cfg["moe_intermediate_size"], max_seq_len=max_seq_len,
        rope_theta=thetas.pop(), norm_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        num_experts=cfg["num_experts"],
        expert_top_k=cfg["num_experts_per_tok"],
        norm_topk_prob=bool(cfg["norm_topk_prob"]), qk_norm=False,
        layer_types=kinds,
        sliding_window=(cfg["sliding_window"]
                        if cfg.get("use_sliding_window", True) else None),
        rope_scaling=tuple(scaling), **extra)


def build_model(cfg: Dict, max_seq_len: int, mesh=None,
                extra: Optional[Dict] = None):
    from ray_tpu.models import model_for
    return model_for(program_config(cfg, max_seq_len, extra), mesh=mesh)


def reference_params(cfg: Dict, params):
    """The system's own arrays under the reference's names: the layer
    stacks as they are (the reference cuts its layers' slices itself,
    one layer at a time), so nothing is held twice."""
    head = (params["embed"].T if cfg["tie_word_embeddings"]
            else params["lm_head"])
    return {"embed": params["embed"], "layers": params["layers"],
            "norm_f": params["norm_f"], "lm_head": head}


def reference_forward(cfg: Dict):
    """``f(system_params, tokens) -> float32 logits`` through
    ``benchmark/reference/mellum.py``."""
    from benchmark.reference import mellum

    def forward(params, tokens, **kw):
        return mellum.forward(
            reference_params(cfg, params), tokens,
            layer_types=layer_types(cfg),
            sliding_window=cfg["sliding_window"],
            rope_parameters=cfg["rope_parameters"],
            rms_norm_eps=float(cfg["rms_norm_eps"]),
            top_k=cfg["num_experts_per_tok"],
            norm_topk_prob=bool(cfg["norm_topk_prob"]), **kw)

    return forward
