"""Published Mistral/Llama-style keys -> the program's ``LlamaModel``.

The repo's only large decoder block is ``models/llama.py``; a model of
another family with the same block (Mistral: RMSNorm, GQA, RoPE, SwiGLU,
untied head, no sliding window in v0.3) runs through it at its own
published sizes.
"""

from __future__ import annotations

from typing import Dict, Optional

REFERENCE = "mistral"


def program_config(cfg: Dict, max_seq_len: int, extra: Optional[Dict] = None):
    from ray_tpu.models.llama import LlamaConfig

    if cfg.get("sliding_window"):
        raise ValueError("models/llama.py has no sliding-window attention")
    return LlamaConfig(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        ffn_dim=cfg["intermediate_size"], max_seq_len=max_seq_len,
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]), **(extra or {}))


def build_model(cfg: Dict, max_seq_len: int, mesh=None,
                extra: Optional[Dict] = None):
    from ray_tpu.models.llama import LlamaModel
    return LlamaModel(program_config(cfg, max_seq_len, extra), mesh=mesh)


def reference_forward(cfg: Dict):
    """``f(system_params, tokens) -> float32 logits`` through
    ``benchmark/reference/mistral.py``; the stacked layer arrays are
    sliced inside the trace, so no second copy of the weights is kept."""
    from benchmark.reference import mistral

    def forward(params, tokens):
        layers = [{k: v[i] for k, v in params["layers"].items()}
                  for i in range(cfg["num_hidden_layers"])]
        head = (params["embed"].T if cfg["tie_word_embeddings"]
                else params["lm_head"])
        return mistral.forward(
            {"embed": params["embed"], "layers": layers,
             "norm_f": params["norm_f"], "lm_head": head},
            tokens, rope_theta=float(cfg["rope_theta"]),
            rms_norm_eps=float(cfg["rms_norm_eps"]))

    return forward
