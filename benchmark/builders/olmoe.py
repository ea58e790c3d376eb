"""Published OLMoE keys -> the program's ``MoEModel`` (the Llama block
with the expert FFN and QK-norm, ``ray_tpu/models/moe.py``), and the
reference to compare with."""

from __future__ import annotations

from typing import Dict, Optional

REFERENCE = "olmoe"


def program_config(cfg: Dict, max_seq_len: int, extra: Optional[Dict] = None):
    from ray_tpu.models.moe import MoEConfig

    if cfg.get("clip_qkv") is not None or cfg.get("attention_bias"):
        raise ValueError("models/moe.py has no clip_qkv and no attention bias")
    extra = dict(extra or {})
    if cfg.get("compute_dtype") == "float32":      # the --tiny-cpu widths
        import jax.numpy as jnp
        extra.setdefault("dtype", jnp.float32)
    return MoEConfig(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        ffn_dim=cfg["intermediate_size"], max_seq_len=max_seq_len,
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        num_experts=cfg["num_experts"],
        expert_top_k=cfg["num_experts_per_tok"],
        norm_topk_prob=bool(cfg["norm_topk_prob"]), qk_norm=True,
        **extra)


def build_model(cfg: Dict, max_seq_len: int, mesh=None,
                extra: Optional[Dict] = None):
    from ray_tpu.models import model_for
    return model_for(program_config(cfg, max_seq_len, extra), mesh=mesh)


def reference_params(cfg: Dict, params):
    """The system's stacked layer arrays under the reference's names,
    sliced by layer (inside a trace no second copy of the weights is
    kept)."""
    layers = [{k: v[i] for k, v in params["layers"].items()}
              for i in range(cfg["num_hidden_layers"])]
    head = (params["embed"].T if cfg["tie_word_embeddings"]
            else params["lm_head"])
    return {"embed": params["embed"], "layers": layers,
            "norm_f": params["norm_f"], "lm_head": head}


def reference_forward(cfg: Dict):
    """``f(system_params, tokens) -> float32 logits`` through
    ``benchmark/reference/olmoe.py``."""
    from benchmark.reference import olmoe

    def forward(params, tokens, **kw):
        return olmoe.forward(
            reference_params(cfg, params), tokens,
            rope_theta=float(cfg["rope_theta"]),
            rms_norm_eps=float(cfg["rms_norm_eps"]),
            top_k=cfg["num_experts_per_tok"],
            norm_topk_prob=bool(cfg["norm_topk_prob"]), **kw)

    return forward
