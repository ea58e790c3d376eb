"""Published ``jamba`` keys -> the program's ``JambaModel`` (runs of
Mamba-1 layers and attention layers, every layer with a dense SwiGLU:
``ray_tpu/models/jamba.py``), and the reference to compare with.

``num_experts`` is 1 in the one configuration of this family the
benchmark holds: every feed-forward is the dense SwiGLU and
``expert_layer_period`` / ``expert_layer_offset`` / ``num_experts_per_tok``
are read by nothing; a configuration with more experts is refused here."""

from __future__ import annotations

from typing import Dict, Optional

REFERENCE = "jamba"


def program_config(cfg: Dict, max_seq_len: int, extra: Optional[Dict] = None):
    # first: a program without the model fails here, before any runtime
    from ray_tpu.models.jamba import JambaConfig

    only = {"hidden_act": "silu", "mamba_conv_bias": True,
            "mamba_proj_bias": False, "num_experts": 1,
            "tie_word_embeddings": True, "sliding_window": None}
    for key, value in only.items():
        if cfg.get(key) != value:
            raise ValueError(
                f"models/jamba.py has {key} = {value!r} alone, got "
                f"{cfg.get(key)!r}")
    extra = dict(extra or {})
    if cfg.get("compute_dtype") == "float32":      # the --tiny-cpu widths
        import jax.numpy as jnp
        extra.setdefault("dtype", jnp.float32)
    return JambaConfig(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=head_dim(cfg),
        ffn_dim=cfg["intermediate_size"], max_seq_len=max_seq_len,
        norm_eps=float(cfg["rms_norm_eps"]), tie_embeddings=True,
        attn_period=cfg["attn_layer_period"],
        attn_offset=cfg["attn_layer_offset"],
        mamba_expand=cfg["mamba_expand"], ssm_state=cfg["mamba_d_state"],
        conv_kernel=cfg["mamba_d_conv"], dt_rank=cfg["mamba_dt_rank"],
        **extra)


def head_dim(cfg: Dict) -> int:
    """The family publishes none: ``hidden_size // num_attention_heads``
    (128 at 2560 / 20); the debug widths state their own."""
    return int(cfg.get("head_dim")
               or cfg["hidden_size"] // cfg["num_attention_heads"])


def build_model(cfg: Dict, max_seq_len: int, mesh=None,
                extra: Optional[Dict] = None):
    from ray_tpu.models import model_for
    return model_for(program_config(cfg, max_seq_len, extra), mesh=mesh)


SUBLAYERS = {"norm": "input_layernorm", "ffn_norm": "pre_ff_layernorm",
             "w_gate": "gate_proj", "w_up": "up_proj", "w_down": "down_proj"}
NAMES = {
    "mamba": {**SUBLAYERS, "w_in": "in_proj", "conv_w": "conv1d_weight",
              "conv_b": "conv1d_bias", "w_x": "x_proj",
              "dt_norm": "dt_layernorm", "b_norm": "b_layernorm",
              "c_norm": "c_layernorm", "w_dt": "dt_proj_weight",
              "b_dt": "dt_proj_bias", "A_log": "A_log", "D": "D",
              "w_out": "out_proj"},
    "attn": {**SUBLAYERS, "wq": "q_proj", "wk": "k_proj", "wv": "v_proj",
             "wo": "o_proj"}}


def reference_params(cfg: Dict, params):
    """The system's own arrays under the reference's names: ``A_log`` as
    the family publishes it, [inner, N] (the program holds [N, inner]: a
    transpose of 82 K numbers a layer); the attention's projections with
    their heads flattened ([L, d, H, hd] as [L, d, H*hd]: a view)."""
    import jax.numpy as jnp

    out = {"embed": params["embed"], "final_layernorm": params["norm_f"]}
    for stack, names in NAMES.items():
        if stack in params:
            out[stack] = {new: params[stack][old]
                          for old, new in names.items()}
    if "mamba" in out:
        out["mamba"]["A_log"] = jnp.swapaxes(out["mamba"]["A_log"], 1, 2)
    if "attn" in out:
        a = out["attn"]
        for name in ("q_proj", "k_proj", "v_proj"):
            a[name] = a[name].reshape(*a[name].shape[:2], -1)
        a["o_proj"] = a["o_proj"].reshape(a["o_proj"].shape[0], -1,
                                          a["o_proj"].shape[-1])
    return out


def reference_kwargs(cfg: Dict) -> Dict:
    return dict(
        n_layers=cfg["num_hidden_layers"],
        attn_layer_period=cfg["attn_layer_period"],
        attn_layer_offset=cfg["attn_layer_offset"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=head_dim(cfg),
        d_state=cfg["mamba_d_state"], dt_rank=cfg["mamba_dt_rank"],
        eps=float(cfg["rms_norm_eps"]))


def reference_forward(cfg: Dict, fault: Optional[str] = None):
    """``f(system_params, tokens)`` through ``benchmark/reference/
    jamba.py``: the float32 logits as ``RowsOfLogits``, which computes the
    rows the harness slices out of it. The system's leaves go in as the
    engine holds them (bf16 matmul weights) and are upcast a layer at a
    time inside the reference's layer scans: the float32 model whole
    (12.1 GB) is never alive. ``fault``: one of the reference's
    deliberate departures, for the controls."""
    from benchmark.reference import jamba

    def forward(params, tokens, **kw):
        return jamba.forward_rows(reference_params(cfg, params), tokens,
                                  **reference_kwargs(cfg), fault=fault, **kw)

    return forward


def reference_first_state(cfg: Dict):
    """``f(system_params, tokens [1, S])`` -> (``S`` as
    ``JambaModel.state_heads`` lays it out, [N, inner, 1]: a "head" a
    state index; the convolution's last inputs [K-1, inner]), float32:
    what the FIRST layer, which must be a Mamba layer, holds after the S
    tokens, by the reference's own pieces (the embedding and that one
    mixer, the recurrence a position at a time). Nothing cascades: bf16
    against float32 reads a few 1e-3 here."""
    import jax.numpy as jnp

    from benchmark.reference import jamba as R

    if cfg["attn_layer_offset"] % cfg["attn_layer_period"] == 0:
        raise ValueError("the first layer's state is held only where the "
                         "first layer is a Mamba layer")
    kwargs = reference_kwargs(cfg)
    keys = {k: kwargs[k] for k in ("d_state", "dt_rank", "eps")}

    def first_state(params, tokens):
        state, conv_in = R.first_state(reference_params(cfg, params), tokens,
                                       **keys)
        return (jnp.swapaxes(state[0], 0, 1)[..., None],
                conv_in[0, -(cfg["mamba_d_conv"] - 1):])

    return first_state
