"""Published GPT-2 keys -> the program's ``GPT2Model``."""

from __future__ import annotations

from typing import Dict, Optional

REFERENCE = "gpt2"


def program_config(cfg: Dict, max_seq_len: int, extra: Optional[Dict] = None):
    from ray_tpu.models.gpt2 import GPT2Config

    if max_seq_len > cfg["n_positions"]:
        raise ValueError(f"GPT-2 has {cfg['n_positions']} learned "
                         f"positions, asked for {max_seq_len}")
    # the position table keeps its published length whatever the cell's
    # sequence length: it is a parameter, not a buffer
    return GPT2Config(
        vocab_size=cfg["vocab_size"], dim=cfg["n_embd"],
        n_layers=cfg["n_layer"], n_heads=cfg["n_head"],
        max_seq_len=cfg["n_positions"],
        norm_eps=float(cfg["layer_norm_epsilon"]), **(extra or {}))


def build_model(cfg: Dict, max_seq_len: int, mesh=None,
                extra: Optional[Dict] = None):
    from ray_tpu.models.gpt2 import GPT2Model
    return GPT2Model(program_config(cfg, max_seq_len, extra), mesh=mesh)


def reference_forward(cfg: Dict):
    from benchmark.reference import gpt2

    def forward(params, tokens):
        layers = [{k: v[i] for k, v in params["layers"].items()}
                  for i in range(cfg["n_layer"])]
        return gpt2.forward(
            {**{k: params[k] for k in ("wte", "wpe", "lnf_w", "lnf_b")},
             "layers": layers},
            tokens, layer_norm_epsilon=float(cfg["layer_norm_epsilon"]))

    return forward
