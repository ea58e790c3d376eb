"""GPT-2 family — BASELINE.md config 2 (GPT-2 125M, 4-worker DP).

Reference capability: trained via TorchTrainer+DDP in the reference's
release tests; here a pjit data/tensor-parallel functional model (pre-LN,
learned positions, tied embeddings, GELU MLP).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import attention, use_flash_on
from ray_tpu.ops.norms import layer_norm

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50_257
    dim: int = 768
    n_layers: int = 12
    n_heads: int = 12
    max_seq_len: int = 1024
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    remat: bool = True

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def ffn_dim(self) -> int:
        return 4 * self.dim

    @staticmethod
    def gpt2_125m() -> "GPT2Config":
        return GPT2Config()

    @staticmethod
    def debug() -> "GPT2Config":
        return GPT2Config(vocab_size=256, dim=64, n_layers=2, n_heads=4,
                          max_seq_len=128, remat=False)

    def num_params(self) -> int:
        d, f = self.dim, self.ffn_dim
        per_layer = 4 * d * d + 2 * d * f + 4 * d + d + f + 2 * d
        return (self.vocab_size * d + self.max_seq_len * d
                + self.n_layers * per_layer + 2 * d)


def param_logical_axes(cfg: GPT2Config) -> Params:
    return {
        "wte": ("vocab", "embed_in"),
        "wpe": (None, "embed_in"),
        "layers": {
            "ln1_w": (None, "embed_in"), "ln1_b": (None, "embed_in"),
            "wqkv": (None, "embed_in", None, "heads", None),
            "bqkv": (None, None, "heads", None),
            "wo": (None, "heads", None, "embed_in"),
            "bo": (None, "embed_in"),
            "ln2_w": (None, "embed_in"), "ln2_b": (None, "embed_in"),
            "w_up": (None, "embed_in", "mlp"), "b_up": (None, "mlp"),
            "w_down": (None, "mlp", "embed_in"),
            "b_down": (None, "embed_in"),
        },
        "lnf_w": ("embed_in",), "lnf_b": ("embed_in",),
    }


class GPT2Model:
    def __init__(self, cfg: GPT2Config, mesh=None,
                 rules: Optional[Dict] = None):
        self.cfg = cfg
        self.mesh = mesh
        self.rules = rules
        self._use_flash = use_flash_on(mesh)

    def init(self, rng: jax.Array) -> Params:
        cfg = self.cfg
        d, hd, L = cfg.dim, cfg.head_dim, cfg.n_layers
        k = iter(jax.random.split(rng, 8))

        def dense(key, shape, fan_in):
            return jax.random.normal(key, shape, jnp.float32) * (
                fan_in ** -0.5)

        return {
            "wte": dense(next(k), (cfg.vocab_size, d), d),
            "wpe": dense(next(k), (cfg.max_seq_len, d), d) * 0.1,
            "layers": {
                "ln1_w": jnp.ones((L, d)), "ln1_b": jnp.zeros((L, d)),
                "wqkv": dense(next(k), (L, d, 3, cfg.n_heads, hd), d),
                "bqkv": jnp.zeros((L, 3, cfg.n_heads, hd)),
                "wo": dense(next(k), (L, cfg.n_heads, hd, d), d),
                "bo": jnp.zeros((L, d)),
                "ln2_w": jnp.ones((L, d)), "ln2_b": jnp.zeros((L, d)),
                "w_up": dense(next(k), (L, d, cfg.ffn_dim), d),
                "b_up": jnp.zeros((L, cfg.ffn_dim)),
                "w_down": dense(next(k), (L, cfg.ffn_dim, d), cfg.ffn_dim),
                "b_down": jnp.zeros((L, d)),
            },
            "lnf_w": jnp.ones((d,)), "lnf_b": jnp.zeros((d,)),
        }

    def param_shardings(self):
        from ray_tpu.parallel.mesh import named_sharding
        axes = param_logical_axes(self.cfg)
        return jax.tree.map(
            lambda names: named_sharding(self.mesh, *names,
                                         rules=self.rules),
            axes, is_leaf=lambda x: isinstance(x, tuple))

    def _block(self, x, layer):
        cfg = self.cfg
        dt = cfg.dtype
        with jax.named_scope("norm_residual"):
            h = layer_norm(x, layer["ln1_w"], layer["ln1_b"],
                           eps=cfg.norm_eps)
        with jax.named_scope("attention"):
            qkv = jnp.einsum("bsd,dthk->bsthk", h, layer["wqkv"].astype(dt))
            qkv = qkv + layer["bqkv"].astype(dt)
            q, kk, vv = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
            # a Mosaic call carries no partitioning rule: under a mesh
            # the reference, off one what the dispatcher finds tiles
            o = attention(q, kk, vv, causal=True,
                          use_flash=self._use_flash)
            o = jnp.einsum("bshk,hkd->bsd", o, layer["wo"].astype(dt))
        with jax.named_scope("norm_residual"):
            x = x + o + layer["bo"].astype(dt)
            h = layer_norm(x, layer["ln2_w"], layer["ln2_b"],
                           eps=cfg.norm_eps)
        with jax.named_scope("mlp"):
            up = jnp.einsum("bsd,df->bsf", h, layer["w_up"].astype(dt))
            up = jax.nn.gelu(up + layer["b_up"].astype(dt))
            down = jnp.einsum("bsf,fd->bsd", up, layer["w_down"].astype(dt))
        with jax.named_scope("norm_residual"):
            return x + down + layer["b_down"].astype(dt)

    def apply(self, params: Params, tokens: jax.Array) -> jax.Array:
        cfg = self.cfg
        B, S = tokens.shape
        with jax.named_scope("embed"):
            x = params["wte"].astype(cfg.dtype)[tokens]
            x = x + params["wpe"].astype(cfg.dtype)[:S][None]

        block = self._block
        if cfg.remat:
            block = jax.checkpoint(block)

        def scan_body(x, layer):
            return block(x, layer), None

        x, _ = jax.lax.scan(scan_body, x, params["layers"])
        with jax.named_scope("logits"):
            x = layer_norm(x, params["lnf_w"], params["lnf_b"],
                           eps=cfg.norm_eps)
            logits = jnp.einsum("bsd,vd->bsv", x,
                                params["wte"].astype(cfg.dtype))  # tied head
            return logits.astype(jnp.float32)

    def loss(self, params: Params, tokens: jax.Array,
             targets: jax.Array) -> jax.Array:
        logits = self.apply(params, tokens)
        with jax.named_scope("loss"):
            logp = jax.nn.log_softmax(logits, axis=-1)
            return -jnp.mean(jnp.take_along_axis(
                logp, targets[..., None], axis=-1))
