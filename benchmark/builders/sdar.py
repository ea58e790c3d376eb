"""Published SDAR (``sdar_moe``) keys -> the program's ``MoEModel`` with
QK-norm a head and generation by diffusion over blocks
(``ray_tpu/models/moe.py``, ``ray_tpu/models/llama.py``,
``ray_tpu/ops/block_diffusion.py``), and the reference to compare with.
The generation settings are no keys of the published ``config.json``:
they are the configuration file's ``generation`` group (its ``assumed``
says where each comes from)."""

from __future__ import annotations

from typing import Dict, Optional

REFERENCE = "sdar"


def program_config(cfg: Dict, max_seq_len: int, extra: Optional[Dict] = None):
    from ray_tpu.models.moe import MoEConfig

    only = {"attention_bias": False, "decoder_sparse_step": 1,
            "mlp_only_layers": [], "hidden_act": "silu",
            "rope_scaling": None, "use_sliding_window": False,
            "tie_word_embeddings": False}
    for key, value in only.items():
        if cfg.get(key) != value:
            raise ValueError(
                f"models/moe.py has {key} = {value!r} alone for this "
                f"builder, got {cfg.get(key)!r}")
    gen = cfg["generation"]
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
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]), tie_embeddings=False,
        num_experts=cfg["num_experts"],
        expert_top_k=cfg["num_experts_per_tok"],
        norm_topk_prob=bool(cfg["norm_topk_prob"]),
        qk_norm=True, qk_norm_per_head=True,
        block_length=gen["block_length"],
        denoising_steps=gen["denoising_steps"],
        remasking=gen["remasking_strategy"],
        confidence_threshold=float(gen["confidence_threshold"]),
        mask_token_id=gen["mask_token_id"], **extra)


def build_model(cfg: Dict, max_seq_len: int, mesh=None,
                extra: Optional[Dict] = None):
    from ray_tpu.models import model_for
    return model_for(program_config(cfg, max_seq_len, extra), mesh=mesh)


def reference_params(cfg: Dict, params):
    """The system's own arrays under the reference's names: the layer
    stacks as they are (the reference cuts its layers' and experts'
    slices itself), so nothing is held twice."""
    return {"embed": params["embed"], "layers": params["layers"],
            "norm_f": params["norm_f"], "lm_head": params["lm_head"]}


def reference_kwargs(cfg: Dict) -> Dict:
    return dict(rope_theta=float(cfg["rope_theta"]),
                rms_norm_eps=float(cfg["rms_norm_eps"]),
                top_k=cfg["num_experts_per_tok"],
                norm_topk_prob=bool(cfg["norm_topk_prob"]))


def generation_kwargs(cfg: Dict) -> Dict:
    gen = cfg["generation"]
    return dict(block_length=gen["block_length"],
                denoising_steps=gen["denoising_steps"],
                mask_id=gen["mask_token_id"],
                remasking=gen["remasking_strategy"],
                confidence_threshold=float(gen["confidence_threshold"]))


def reference_forward(cfg: Dict, fault: Optional[str] = None):
    """``f(system_params, tokens) -> float32 logits`` [B, S, V]: the
    block-causal full forward of ``benchmark/reference/sdar.py``."""
    from benchmark.reference import sdar

    def forward(params, tokens, **kw):
        return sdar.forward(
            reference_params(cfg, params), tokens,
            cfg["generation"]["block_length"], fault=fault,
            **reference_kwargs(cfg), **kw)

    return forward


def reference_denoise(cfg: Dict, fault: Optional[str] = None):
    """``f(system_params, prefix, block) -> logits`` [B, n, V] of one
    pass over ``block`` behind ``prefix``."""
    from benchmark.reference import sdar

    def denoise(params, prefix, block, **kw):
        return sdar.denoise_logits(reference_params(cfg, params), prefix,
                                   block, fault=fault,
                                   **reference_kwargs(cfg), **kw)

    return denoise


def reference_teacher_forced(cfg: Dict, fault: Optional[str] = None):
    """``f(system_params, clean, noised, start) -> hidden rows`` (the
    reference's ``teacher_forced``) and ``g(system_params, rows) ->
    logits`` (its head), for the check that runs every block of a
    sequence in several masked states at one forward's cost."""
    from benchmark.reference import sdar

    def rows(params, clean, noised, start, **kw):
        return sdar.teacher_forced(
            reference_params(cfg, params), clean, noised, start,
            cfg["generation"]["block_length"], fault=fault,
            **reference_kwargs(cfg), **kw)

    def logits(params, hidden):
        return sdar.logits_of(reference_params(cfg, params), hidden, fault)

    return rows, logits


def reference_generate(cfg: Dict):
    """``f(system_params, prompt, n_tokens) -> (tokens, passes)``: the
    published procedure, greedy, as the reference's Python loop."""
    from benchmark.reference import sdar

    def generate(params, prompt, n_tokens, **kw):
        return sdar.generate(reference_params(cfg, params), prompt, n_tokens,
                             **generation_kwargs(cfg),
                             **reference_kwargs(cfg), **kw)

    return generate
