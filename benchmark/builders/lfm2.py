"""Published ``lfm2_moe`` keys -> the program's ``Lfm2Model`` (runs of
gated-short-convolution layers and attention layers, dense SwiGLUs first
and routed experts after them: ``ray_tpu/models/lfm2.py``), and the
reference to compare with."""

from __future__ import annotations

from typing import Dict, Optional

REFERENCE = "lfm2"


def head_dim(cfg: Dict) -> int:
    """The family publishes none in this configuration: ``hidden_size //
    num_attention_heads`` (64 at 2048 / 32); the debug widths state
    their own."""
    return int(cfg.get("head_dim")
               or cfg["hidden_size"] // cfg["num_attention_heads"])


def program_config(cfg: Dict, max_seq_len: int, extra: Optional[Dict] = None):
    # first: a program without the model fails here, before any runtime
    from ray_tpu.models.lfm2 import Lfm2Config

    only = {"conv_bias": False, "use_expert_bias": True,
            "model_type": "lfm2_moe"}
    for key, value in only.items():
        if cfg.get(key) != value:
            raise ValueError(
                f"models/lfm2.py has {key} = {value!r} alone, got "
                f"{cfg.get(key)!r}")
    rope = cfg["rope_parameters"]
    if rope.get("rope_type") != "default":
        raise ValueError(f"the default rotary table alone, got {rope}")
    extra = dict(extra or {})
    if cfg.get("compute_dtype") == "float32":      # the --tiny-cpu widths
        import jax.numpy as jnp
        extra.setdefault("dtype", jnp.float32)
    return Lfm2Config(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=head_dim(cfg),
        ffn_dim=cfg["moe_intermediate_size"],
        dense_ffn_dim=cfg["intermediate_size"],
        num_dense_layers=cfg["num_dense_layers"],
        mixer_types=tuple(cfg["layer_types"]),
        conv_kernel=cfg["conv_L_cache"], max_seq_len=max_seq_len,
        norm_eps=float(cfg["norm_eps"]),
        rope_theta=float(rope["rope_theta"]),
        num_experts=cfg["num_experts"],
        expert_top_k=cfg["num_experts_per_tok"],
        norm_topk_prob=bool(cfg["norm_topk_prob"]),
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        router_bias_init_std=float(cfg["router_bias_init_std"]),
        tie_embeddings=True, **extra)


def build_model(cfg: Dict, max_seq_len: int, mesh=None,
                extra: Optional[Dict] = None):
    from ray_tpu.models import model_for
    return model_for(program_config(cfg, max_seq_len, extra), mesh=mesh)


# the program's leaf -> the reference's (the family's own names)
NAMES = {"norm": "operator_norm", "ffn_norm": "ffn_norm",
         "w_in": "in_proj", "conv_w": "conv_weight", "w_out": "out_proj",
         "wq": "q_proj", "wk": "k_proj", "wv": "v_proj", "wo": "out_proj",
         "q_norm": "q_layernorm", "k_norm": "k_layernorm",
         "w_gate": "w1", "w_up": "w3", "w_down": "w2",
         "router": "gate", "router_bias": "expert_bias",
         "e_gate": "experts_w1", "e_up": "experts_w3", "e_down": "experts_w2"}
KINDS = ("conv_dense", "conv_moe", "attn_dense", "attn_moe")


def reference_params(cfg: Dict, params):
    """The system's own arrays under the reference's names: a stack a
    kind; the attention's projections with their heads flattened ([L, d,
    H, hd] as [L, d, H*hd]: a view)."""
    out = {"embed": params["embed"], "embedding_norm": params["norm_f"]}
    for kind in KINDS:
        if kind not in params:
            continue
        stack = {NAMES[old]: a for old, a in params[kind].items()}
        if kind.startswith("attn"):
            for name in ("q_proj", "k_proj", "v_proj"):
                stack[name] = stack[name].reshape(*stack[name].shape[:2], -1)
            stack["out_proj"] = stack["out_proj"].reshape(
                stack["out_proj"].shape[0], -1, stack["out_proj"].shape[-1])
        out[kind] = stack
    return out


def reference_kwargs(cfg: Dict) -> Dict:
    return dict(
        layer_types=tuple(cfg["layer_types"]),
        num_dense_layers=cfg["num_dense_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=head_dim(cfg),
        top_k=cfg["num_experts_per_tok"],
        norm_topk_prob=bool(cfg["norm_topk_prob"]),
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        rope_theta=float(cfg["rope_parameters"]["rope_theta"]),
        eps=float(cfg["norm_eps"]))


def reference_forward(cfg: Dict, fault: Optional[str] = None):
    """``f(system_params, tokens)`` through ``benchmark/reference/
    lfm2.py``: the float32 logits as ``RowsOfLogits``, which computes the
    rows the harness slices out of it. The system's leaves go in as the
    engine holds them (bf16 matmul weights) and are upcast a layer, and
    an expert, at a time inside the reference's scans. ``fault``: one of
    the reference's deliberate departures, for the controls."""
    from benchmark.reference import lfm2

    def forward(params, tokens, **kw):
        return lfm2.forward_rows(reference_params(cfg, params), tokens,
                                 **reference_kwargs(cfg), fault=fault, **kw)

    return forward


def reference_first_state(cfg: Dict):
    """``f(system_params, tokens [1, S])`` -> the FIRST layer's state
    after the S tokens, [K-1, D] float32: the last ``conv_L_cache - 1``
    rows of ``g = B * x``, by the reference's own pieces (the embedding
    and that one mixer). The first layer must be a conv layer. Nothing
    cascades and nothing accumulates: bf16 against float32 reads a few
    1e-3 here."""
    from benchmark.reference import lfm2 as R

    if cfg["layer_types"][0] != "conv":
        raise ValueError("the first layer's state is held only where the "
                         "first layer is a conv layer")
    rows = cfg["conv_L_cache"] - 1

    def first_state(params, tokens):
        g = R.first_state(reference_params(cfg, params), tokens,
                          eps=float(cfg["norm_eps"]))
        return g[0, -rows:]

    return first_state
