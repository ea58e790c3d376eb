"""Published ``nemotron_h`` keys -> the program's ``NemotronHModel`` (a
pattern over Mamba-2, attention and latent-expert layers, one chip's
share of the experts: ``ray_tpu/models/nemotron_h.py``), and the
reference to compare with.

The configuration's ``n_routed_experts`` is the experts HELD here;
``router_experts`` is the router's published width and
``experts_held_first`` where the held range starts.
``hybrid_override_pattern`` is the slice of the published pattern that
is run, ``num_hidden_layers`` its length."""

from __future__ import annotations

from typing import Dict, Optional

REFERENCE = "nemotron_h"


def held(cfg: Dict):
    """(first, count) of the router's experts this configuration holds."""
    return int(cfg.get("experts_held_first", 0)), int(cfg["n_routed_experts"])


def program_config(cfg: Dict, max_seq_len: int, extra: Optional[Dict] = None):
    # first: a program without the model fails here, before any runtime
    from ray_tpu.models.nemotron_h import NemotronHConfig

    only = {"mlp_hidden_act": "relu2", "mamba_hidden_act": "silu",
            "n_group": 1, "topk_group": 1, "attention_bias": False,
            "mamba_proj_bias": False, "mlp_bias": False,
            "use_conv_bias": True, "residual_in_fp32": False,
            "tie_word_embeddings": False, "n_shared_experts": 1,
            "num_nextn_predict_layers": 0}
    for key, value in only.items():
        if cfg.get(key) != value:
            raise ValueError(
                f"models/nemotron_h.py has {key} = {value!r} alone, got "
                f"{cfg.get(key)!r}")
    pattern = cfg["hybrid_override_pattern"]
    if len(pattern) != cfg["num_hidden_layers"]:
        raise ValueError(
            f"hybrid_override_pattern has {len(pattern)} letters for "
            f"num_hidden_layers = {cfg['num_hidden_layers']}")
    if cfg["mamba_num_heads"] * cfg["mamba_head_dim"] != (
            cfg["expand"] * cfg["hidden_size"]):
        raise ValueError("mamba heads x head_dim is expand x hidden_size")
    extra = dict(extra or {})
    if cfg.get("compute_dtype") == "float32":      # the --tiny-cpu widths
        import jax.numpy as jnp
        extra.setdefault("dtype", jnp.float32)
    first, count = held(cfg)
    return NemotronHConfig(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        pattern=pattern, n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        ffn_dim=cfg["moe_intermediate_size"], max_seq_len=max_seq_len,
        norm_eps=float(cfg["norm_eps"]), tie_embeddings=False,
        mamba_heads=cfg["mamba_num_heads"],
        mamba_head_dim=cfg["mamba_head_dim"], ssm_groups=cfg["n_groups"],
        ssm_state=cfg["ssm_state_size"], conv_kernel=cfg["conv_kernel"],
        scan_chunk=cfg["chunk_size"], latent_dim=cfg["moe_latent_size"],
        num_experts=cfg["router_experts"], experts_held=count,
        first_expert_held=first, expert_top_k=cfg["num_experts_per_tok"],
        norm_topk_prob=bool(cfg["norm_topk_prob"]),
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        router_bias_init_std=float(cfg["router_bias_init_std"]),
        shared_ffn_dim=(cfg["n_shared_experts"]
                        * cfg["moe_shared_expert_intermediate_size"]),
        dt_init=(float(cfg["time_step_min"]), float(cfg["time_step_max"])),
        **extra)


def build_model(cfg: Dict, max_seq_len: int, mesh=None,
                extra: Optional[Dict] = None):
    from ray_tpu.models import model_for
    return model_for(program_config(cfg, max_seq_len, extra), mesh=mesh)


NAMES = {
    "mamba": {"norm": "norm", "w_in": "in_proj", "conv_w": "conv1d_weight",
              "conv_b": "conv1d_bias", "dt_bias": "dt_bias",
              "A_log": "A_log", "D": "D", "gnorm": "mixer_norm",
              "w_out": "out_proj"},
    "attn": {"norm": "norm", "wq": "q_proj", "wk": "k_proj", "wv": "v_proj",
             "wo": "o_proj"},
    "moe": {"norm": "norm", "router": "gate",
            "router_bias": "e_score_correction_bias",
            "w_lat_down": "fc1_latent_proj", "w_lat_up": "fc2_latent_proj",
            "e_up": "up_proj", "e_down": "down_proj", "s_up": "shared_up",
            "s_down": "shared_down"}}


def reference_params(cfg: Dict, params):
    """The system's own arrays under the reference's names; the
    attention's projections with their heads flattened ([L, d, H, hd] as
    [L, d, H*hd]: a view)."""
    out = {"embed": params["embed"], "norm_f": params["norm_f"],
           "lm_head": params["lm_head"]}
    for stack, names in NAMES.items():
        if stack in params:
            out[stack] = {new: params[stack][old]
                          for old, new in names.items()}
    if "attn" in out:
        a = out["attn"]
        for name in ("q_proj", "k_proj", "v_proj"):
            a[name] = a[name].reshape(*a[name].shape[:2], -1)
        a["o_proj"] = a["o_proj"].reshape(a["o_proj"].shape[0], -1,
                                          a["o_proj"].shape[-1])
    return out


def reference_kwargs(cfg: Dict) -> Dict:
    return dict(
        pattern=cfg["hybrid_override_pattern"],
        mamba_heads=cfg["mamba_num_heads"],
        mamba_head_dim=cfg["mamba_head_dim"], n_groups=cfg["n_groups"],
        ssm_state=cfg["ssm_state_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        top_k=cfg["num_experts_per_tok"],
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        norm_topk_prob=bool(cfg["norm_topk_prob"]),
        eps=float(cfg["norm_eps"]), experts_held=held(cfg))


def reference_forward(cfg: Dict, fault: Optional[str] = None):
    """``f(system_params, tokens)`` through ``benchmark/reference/
    nemotron_h.py``: the float32 logits as ``RowsOfLogits``, which
    computes the rows the harness slices out of it. ``fault``: one of the
    reference's deliberate departures, for the controls."""
    from benchmark.reference import nemotron_h

    def forward(params, tokens, **kw):
        return nemotron_h.forward_rows(
            reference_params(cfg, params), tokens, **reference_kwargs(cfg),
            fault=fault, **kw)

    return forward


def reference_first_state(cfg: Dict):
    """``f(system_params, tokens [1, S])`` -> (``S`` [H, P, N], the
    convolution's last inputs [K-1, C]), float32: what the FIRST layer,
    which must be a Mamba layer, holds after the S tokens, by the
    reference's own pieces (the embedding and that one mixer, the
    recurrence a position at a time). No router stands before it and
    nothing cascades: bf16 against float32 reads a few 1e-3 here."""
    import jax

    from benchmark.reference import nemotron_h as R

    if cfg["hybrid_override_pattern"][0] != "M":
        raise ValueError("the first layer's state is held only where the "
                         "pattern starts with a Mamba layer")
    eps = float(cfg["norm_eps"])

    def first_state(params, tokens):
        rp = reference_params(cfg, params)
        kept = []
        with jax.default_matmul_precision("highest"):
            x = R._f32(rp["embed"][tokens])
            lp = {name: a[0] for name, a in rp["mamba"].items()}
            R._mamba(R._rms_norm(x, R._f32(lp["norm"]), eps), lp,
                     heads=cfg["mamba_num_heads"],
                     head_dim=cfg["mamba_head_dim"], groups=cfg["n_groups"],
                     state=cfg["ssm_state_size"], eps=eps, fault=None,
                     keep=kept)
        return (kept[0]["state"][0],
                kept[0]["conv_in"][0, -(cfg["conv_kernel"] - 1):])

    return first_state
