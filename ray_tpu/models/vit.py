"""Vision Transformer — BASELINE.md config 4 (ImageNet streaming →
ViT-L/16 with HBM-prefetching data ingest).

Patch embedding is a reshape + one matmul (not a conv) — identical math,
lands directly on the MXU with no im2col.
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
class ViTConfig:
    image_size: int = 224
    patch_size: int = 16
    num_classes: int = 1000
    dim: int = 1024
    n_layers: int = 24
    n_heads: int = 16
    ffn_dim: int = 4096
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    remat: bool = True

    @property
    def num_patches(self) -> int:
        return (self.image_size // self.patch_size) ** 2

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @staticmethod
    def vit_l16() -> "ViTConfig":
        return ViTConfig()

    @staticmethod
    def debug() -> "ViTConfig":
        return ViTConfig(image_size=32, patch_size=8, num_classes=10,
                         dim=64, n_layers=2, n_heads=4, ffn_dim=128,
                         remat=False)


class ViTModel:
    def __init__(self, cfg: ViTConfig, mesh=None,
                 rules: Optional[Dict] = None):
        self.cfg = cfg
        self.mesh = mesh
        self.rules = rules
        self._use_flash = use_flash_on(mesh)

    def init(self, rng: jax.Array) -> Params:
        cfg = self.cfg
        d, hd, L = cfg.dim, cfg.head_dim, cfg.n_layers
        patch_dim = 3 * cfg.patch_size ** 2
        k = iter(jax.random.split(rng, 10))

        def dense(key, shape, fan_in):
            return jax.random.normal(key, shape, jnp.float32) * (
                fan_in ** -0.5)

        return {
            "patch_w": dense(next(k), (patch_dim, d), patch_dim),
            "patch_b": jnp.zeros((d,)),
            "cls": jnp.zeros((1, 1, d)),
            "pos": dense(next(k), (cfg.num_patches + 1, d), d) * 0.1,
            "layers": {
                "ln1_w": jnp.ones((L, d)), "ln1_b": jnp.zeros((L, d)),
                "wqkv": dense(next(k), (L, d, 3, cfg.n_heads, hd), d),
                "wo": dense(next(k), (L, cfg.n_heads, hd, d), d),
                "ln2_w": jnp.ones((L, d)), "ln2_b": jnp.zeros((L, d)),
                "w_up": dense(next(k), (L, d, cfg.ffn_dim), d),
                "b_up": jnp.zeros((L, cfg.ffn_dim)),
                "w_down": dense(next(k), (L, cfg.ffn_dim, d), cfg.ffn_dim),
                "b_down": jnp.zeros((L, d)),
            },
            "lnf_w": jnp.ones((d,)), "lnf_b": jnp.zeros((d,)),
            "head_w": dense(next(k), (d, cfg.num_classes), d),
            "head_b": jnp.zeros((cfg.num_classes,)),
        }

    def _patchify(self, images: jax.Array) -> jax.Array:
        """[B, H, W, 3] -> [B, N, patch_dim] via reshape (MXU-friendly)."""
        cfg = self.cfg
        B, H, W, C = images.shape
        p = cfg.patch_size
        x = images.reshape(B, H // p, p, W // p, p, C)
        x = x.transpose(0, 1, 3, 2, 4, 5)
        return x.reshape(B, (H // p) * (W // p), p * p * C)

    def _block(self, x, layer):
        cfg = self.cfg
        dt = cfg.dtype
        h = layer_norm(x, layer["ln1_w"], layer["ln1_b"], eps=cfg.norm_eps)
        qkv = jnp.einsum("bsd,dthk->bsthk", h, layer["wqkv"].astype(dt))
        q, kk, vv = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        # under a mesh the reference (a Mosaic call cannot be partitioned)
        o = attention(q, kk, vv, causal=False,
                      use_flash=self._use_flash)
        x = x + jnp.einsum("bshk,hkd->bsd", o, layer["wo"].astype(dt))
        h = layer_norm(x, layer["ln2_w"], layer["ln2_b"], eps=cfg.norm_eps)
        up = jax.nn.gelu(
            jnp.einsum("bsd,df->bsf", h, layer["w_up"].astype(dt))
            + layer["b_up"].astype(dt))
        down = jnp.einsum("bsf,fd->bsd", up, layer["w_down"].astype(dt))
        return x + down + layer["b_down"].astype(dt)

    def apply(self, params: Params, images: jax.Array) -> jax.Array:
        """images [B, H, W, 3] float → logits [B, num_classes]."""
        cfg = self.cfg
        patches = self._patchify(images.astype(cfg.dtype))
        x = patches @ params["patch_w"].astype(cfg.dtype) \
            + params["patch_b"].astype(cfg.dtype)
        cls = jnp.broadcast_to(params["cls"].astype(cfg.dtype),
                               (x.shape[0], 1, cfg.dim))
        x = jnp.concatenate([cls, x], axis=1)
        x = x + params["pos"].astype(cfg.dtype)[None]

        block = self._block
        if cfg.remat:
            block = jax.checkpoint(block)

        def scan_body(x, layer):
            return block(x, layer), None

        x, _ = jax.lax.scan(scan_body, x, params["layers"])
        x = layer_norm(x[:, 0], params["lnf_w"], params["lnf_b"],
                       eps=cfg.norm_eps)
        logits = x @ params["head_w"].astype(cfg.dtype) + params["head_b"]
        return logits.astype(jnp.float32)

    def loss(self, params: Params, images: jax.Array,
             labels: jax.Array) -> jax.Array:
        logits = self.apply(params, images)
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(logp, labels[:, None],
                                             axis=-1))

    def accuracy(self, params: Params, images, labels) -> jax.Array:
        return jnp.mean(jnp.argmax(self.apply(params, images), -1)
                        == labels)
