"""Published EvaByte keys -> the program's ``LlamaModel`` with EVA
attention layers (``ray_tpu/models/llama.py``, ``ray_tpu/ops/eva.py``),
and the reference to compare with."""

from __future__ import annotations

from typing import Dict, Optional

REFERENCE = "evabyte"


def program_config(cfg: Dict, max_seq_len: int, extra: Optional[Dict] = None):
    from ray_tpu.models.llama import EVA_KIND, LlamaConfig

    if cfg["attention_class"] != "eva":
        raise ValueError(
            f"this builder maps attention_class 'eva', got "
            f"{cfg['attention_class']!r}")
    if cfg.get("attention_bias"):
        raise ValueError("models/llama.py has no attention bias")
    if cfg.get("rope_scaling"):
        raise ValueError("an EVA layer turns by the default rotary table")
    if not cfg.get("fp32_logits", True):
        raise ValueError("models/llama.py returns float32 logits")
    extra = dict(extra or {})
    if cfg.get("compute_dtype") == "float32":      # the --tiny-cpu widths
        import jax.numpy as jnp
        extra.setdefault("dtype", jnp.float32)
    return LlamaConfig(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        ffn_dim=cfg["intermediate_size"], max_seq_len=max_seq_len,
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        layer_types=(EVA_KIND,) * cfg["num_hidden_layers"],
        eva_window=cfg["window_size"], eva_chunk=cfg["chunk_size"],
        norm_add_unit_offset=bool(cfg["norm_add_unit_offset"]),
        fp32_residual=bool(cfg["fp32_skip_add"]),
        num_pred_heads=cfg["num_pred_heads"], **extra)


def build_model(cfg: Dict, max_seq_len: int, mesh=None,
                extra: Optional[Dict] = None):
    from ray_tpu.models import model_for
    return model_for(program_config(cfg, max_seq_len, extra), mesh=mesh)


def reference_forward(cfg: Dict):
    """``f(system_params, tokens) -> float32 logits`` through
    ``benchmark/reference/evabyte.py``: head 0's ``vocab_size`` logits,
    the next byte's, which is what the serving programs return;
    ``every_head=True`` for all ``num_pred_heads x vocab_size``
    (``apply``). The system's own arrays go in as they are (the
    reference cuts its layers' slices itself)."""
    from benchmark.reference import evabyte

    def forward(params, tokens, every_head: bool = False):
        head = params["lm_head"]
        if not every_head:
            head = head[:, :cfg["vocab_size"]]
        return evabyte.forward(
            {"embed": params["embed"], "layers": params["layers"],
             "norm_f": params["norm_f"], "lm_head": head},
            tokens, rope_theta=float(cfg["rope_theta"]),
            rms_norm_eps=float(cfg["rms_norm_eps"]),
            window=cfg["window_size"], chunk=cfg["chunk_size"])

    return forward
