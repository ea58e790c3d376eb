"""Latent attention (MLA: DeepSeek-V2/V3's multi-head latent attention)
on the one decoder layer, with the expert block of ``models/moe.py``.

``MLAModel`` is ``MoEModel`` with the attention half of the layer
overridden; the layer body, the four programs, the layer scans, the
engine and Serve are the family's own (``LlamaModel._layer``). With h the
normed residual stream:

- ``q = h Wq`` -> H heads x (``qk_nope_head_dim`` + ``qk_rope_head_dim``),
  no query compression; ``[c~ | k~_pe] = h Wkv_a`` -> ``kv_lora_rank`` +
  rope lanes; ``c = RMSNorm(c~; kv_norm)``; ``k_pe = RoPE(k~_pe)`` is ONE
  key part shared by every head; ``q_pe = RoPE(q_pe)``. RoPE turns
  ADJACENT pairs of lanes (``rope_interleave``: the published weights are
  laid out so); q and k are de-interleaved the same way before the
  rotate-half the family uses, which leaves every score as it was.
- THE CACHE ROW is ``c`` and ``k_pe``: ``kv_row_shapes`` puts ``c``
  ``[kv_lora_rank]`` under ``"k"`` and ``k_pe``, zero-padded to a lane
  tile (``ops.mla_attention.PE_LANES``), under ``"v"``. No head axis:
  ``[..., bs, 512]`` and ``[..., bs, 128]`` tile as they are, where
  ``[..., bs, 1, 512]`` would pad every row to a sublane tile. (The pad
  is for the kernel's page copies; a latent width that fills no lane
  tile, which the kernel cannot copy on the chip anyway, is not padded:
  the debug widths' rows are ``c`` and ``k_pe`` as they are.)
- TWO FORMS, one set of weights. ``W_uk`` [H, nope, R] and ``W_uv`` [H, R,
  v] are the two halves of the published ``kv_b_proj`` a head, held once.
  The prefills and training (``_attend_rows``) EXPAND: keys ``[c W_uk |
  k_pe]`` and values ``c W_uv`` of every row, then the family's masked
  attention at scale ``1/sqrt(nope + rope)``. The decode step
  (``_attend_pages``) ABSORBS: ``q_lat = q_nope W_uk^T``, the paged kernel
  over the latent rows (``ops/mla_attention.py``), ``o = o_lat W_uv``;
  no row of the cache is ever up-projected there.
- ``out = o Wo`` ([H, v, d]).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models.llama import Params
from ray_tpu.models.moe import MoEConfig, MoEModel
from ray_tpu.ops.attention import reference_attention
from ray_tpu.ops.mla_attention import (PE_LANES, default_impl,
                                       mla_decode_attention)
from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.rope import _rotate, yarn_inv_freq


# Queries a call of the expanded form scores at once (``_attend_rows``):
# the engine's prefills (chunks and buckets of at most 512 tokens) are
# one block; a prefill of thousands of tokens goes a block at a time.
QUERY_BLOCK = 1024


@dataclasses.dataclass(frozen=True)
class MLAConfig(MoEConfig):
    """``head_dim`` is derived: the q.k width, nope + rope. ``n_kv_heads``
    is unused (the cache has no head axis)."""
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128

    def __post_init__(self):
        object.__setattr__(self, "head_dim",
                           self.qk_nope_head_dim + self.qk_rope_head_dim)
        super().__post_init__()
        if self.qk_rope_head_dim > PE_LANES or self.qk_rope_head_dim % 2:
            raise ValueError(
                f"qk_rope_head_dim ({self.qk_rope_head_dim}) is even and "
                f"fits the pool row's {PE_LANES} rotary lanes")
        if self.layer_types is not None or self.qk_norm:
            raise ValueError("latent attention has no layer kinds and no "
                             "QK-norm")

    def attention_params(self) -> int:
        d, H, R = self.dim, self.n_heads, self.kv_lora_rank
        return (d * H * self.head_dim + d * (R + self.qk_rope_head_dim) + R
                + H * R * (self.qk_nope_head_dim + self.v_head_dim)
                + H * self.v_head_dim * d)

    @staticmethod
    def debug_kanana(vocab_size: int = 512, max_seq_len: int = 128,
                     **overrides) -> "MLAConfig":
        """kanana-2-30b-a3b's block at debug widths: one leading dense
        layer and two expert layers, 8 experts top-3 by a sigmoid router
        with a DRAWN selection bias, two shared experts' width, latent
        rows of 32 + 16 lanes."""
        return MLAConfig(**{**dict(
            vocab_size=vocab_size, dim=64, n_layers=3, n_heads=4,
            n_kv_heads=4, ffn_dim=32, max_seq_len=max_seq_len, remat=False,
            rope_theta=1e6, norm_eps=1e-6, num_experts=8, expert_top_k=3,
            norm_topk_prob=True, router_kind="sigmoid",
            routed_scaling_factor=2.448, router_bias_init_std=0.1,
            shared_ffn_dim=64, leading_layers=1, leading_ffn_dim=96,
            kv_lora_rank=32, qk_nope_head_dim=32, qk_rope_head_dim=16,
            v_head_dim=32), **overrides})


class MLAModel(MoEModel):
    """The expert model with latent attention in every layer."""

    MATMUL_LAYER_LEAVES = MoEModel.MATMUL_LAYER_LEAVES + (
        "wkv_a", "w_uk", "w_uv")       # ``kv_norm`` stays float32

    def __init__(self, cfg: MLAConfig, mesh=None,
                 rules: Optional[Dict] = None):
        if mesh is not None:
            raise NotImplementedError(
                "latent attention carries no partitioning rules yet")
        super().__init__(cfg, mesh=mesh, rules=rules)
        # the parent's table is for a head of ``head_dim`` lanes; the
        # rotary part's angles come from the positions (no constant of
        # max_seq x lanes in every program: ``ops/rope.py``)
        del self._angles
        self._pe_inv_freq = yarn_inv_freq(cfg.qk_rope_head_dim,
                                          cfg.rope_theta, None)
        # lanes of a cache row's rotary part (module docstring)
        self.pe_lanes = (PE_LANES if cfg.kv_lora_rank % PE_LANES == 0
                         else cfg.qk_rope_head_dim)

    def _init_attention(self, k, L: int) -> Params:
        cfg: MLAConfig = self.cfg
        d, H, R, dense = cfg.dim, cfg.n_heads, cfg.kv_lora_rank, self._dense
        nope, rope, v = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                         cfg.v_head_dim)
        return {"wq": dense(next(k), (L, d, H, nope + rope), d),
                "wkv_a": dense(next(k), (L, d, R + rope), d),
                "kv_norm": jnp.ones((L, R), jnp.float32),
                "w_uk": dense(next(k), (L, H, nope, R), R),
                "w_uv": dense(next(k), (L, H, R, v), R),
                "wo": dense(next(k), (L, H, v, d), H * v)}

    # -- the cache row -------------------------------------------------------
    def kv_row_shapes(self) -> Tuple[Tuple[int, ...], Tuple[int, ...]]:
        """``"k"``: the latent row ``c``; ``"v"``: ``k_pe`` in the first
        lanes of a zero lane tile."""
        return (self.cfg.kv_lora_rank,), (self.pe_lanes,)

    def _rope_pe(self, x, positions):
        """The rotary part x [B, T, heads, rope] turned by its
        positions: adjacent pairs of lanes, written as the de-interleave
        (evens, then odds) and the family's rotate-half. q and k are
        permuted alike, so their product is the published one."""
        x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
        if positions is None:
            positions = jnp.arange(x.shape[-3])
        ang = positions.astype(jnp.float32)[..., None] * self._pe_inv_freq
        return _rotate(x, ang[..., :, None, :])

    # -- the layer's attention half ------------------------------------------
    def _qkv(self, h, layer: Params, positions, kind, pin):
        cfg: MLAConfig = self.cfg
        dt, R, nope = cfg.dtype, cfg.kv_lora_rank, cfg.qk_nope_head_dim
        with jax.named_scope("mla_q_proj"):
            q = jnp.einsum("bsd,dhk->bshk", h, layer["wq"].astype(dt))
            q = pin(q, "batch", "seq", "heads", None)
            q = jnp.concatenate(
                [q[..., :nope], self._rope_pe(q[..., nope:], positions)],
                axis=-1)
        with jax.named_scope("mla_kv_down"):
            down = jnp.einsum("bsd,dr->bsr", h, layer["wkv_a"].astype(dt))
            c = rms_norm(down[..., :R], layer["kv_norm"], eps=cfg.norm_eps)
            k_pe = self._rope_pe(down[..., None, R:], positions)[..., 0, :]
            k_pe = jnp.pad(k_pe, ((0, 0), (0, 0),
                                  (0, self.pe_lanes - cfg.qk_rope_head_dim)))
        return q, c, k_pe

    def _attend_rows(self, q, c, k_pe, layer: Params, positions_q,
                     positions_k, window=None):
        """The EXPANDED form: every row's keys and values up-projected
        from its latent part, then the family's masked attention."""
        cfg: MLAConfig = self.cfg
        dt = cfg.dtype
        with jax.named_scope("mla_kv_up"):
            k_nope = jnp.einsum("bsr,hnr->bshn", c, layer["w_uk"].astype(dt))
            v = jnp.einsum("bsr,hrv->bshv", c, layer["w_uv"].astype(dt))
            k = jnp.concatenate([k_nope, jnp.broadcast_to(
                k_pe[:, :, None, :cfg.qk_rope_head_dim],
                k_nope.shape[:3] + (cfg.qk_rope_head_dim,))], axis=-1)
        with jax.named_scope("mla_attention"):
            B, T = q.shape[:2]
            tb = max(b for b in range(1, min(T, QUERY_BLOCK) + 1)
                     if T % b == 0)
            if tb == T:
                return reference_attention(
                    q, k, v, positions_q=positions_q,
                    positions_k=positions_k, window=window)

            # a long prefill: the float32 scores of a block of queries
            # at a time, [B, H, tb, S] and not [B, H, T, S]
            if positions_q is None:        # training: 0..T-1
                positions_q = jnp.arange(T)

            def blocks(a):
                return jnp.moveaxis(
                    a.reshape(B, T // tb, tb, *a.shape[2:]), 1, 0)

            o = jax.lax.map(
                lambda qp: reference_attention(
                    qp[0], k, v, positions_q=qp[1], positions_k=positions_k,
                    window=window),
                (blocks(q), blocks(jnp.broadcast_to(positions_q, (B, T)))))
            return jnp.moveaxis(o, 0, 1).reshape(B, T, *o.shape[3:])

    def _attention(self, q, c, k_pe, positions, window=None, layer=None):
        return self._attend_rows(q, c, k_pe, layer, positions, positions,
                                 window)

    def paged_decode_impl(self) -> str:
        """"mla_pallas" (the Mosaic kernel of ``ops/mla_attention.py``) or
        "mla_xla" (its twin): what the configuration forces, else the
        platform's."""
        return "mla_" + (self.cfg.decode_attention or default_impl())

    def _attend_pages(self, q, c_pool, pe_pool, layer: Params, block_tables,
                      lengths, *, impl, starts=None, first_block=0,
                      num_blocks=None):
        """The ABSORBED form: q [B, H, nope + rope] against the latent
        pages; no row of the cache is up-projected."""
        cfg: MLAConfig = self.cfg
        dt, nope = cfg.dtype, cfg.qk_nope_head_dim
        with jax.named_scope("mla_q_absorb"):
            q_lat = jnp.einsum("bhn,hnr->bhr", q[..., :nope],
                               layer["w_uk"].astype(dt))
            q_pe = jnp.pad(q[..., nope:], ((0, 0), (0, 0), (
                0, self.pe_lanes - cfg.qk_rope_head_dim)))
        with jax.named_scope("mla_attention"):
            o_lat = mla_decode_attention(
                q_lat, q_pe, c_pool, pe_pool, block_tables, lengths,
                impl=impl.removeprefix("mla_"), scale=cfg.head_dim ** -0.5,
                first_block=first_block)
        with jax.named_scope("mla_v_up"):
            return jnp.einsum("bhr,hrv->bhv", o_lat, layer["w_uv"].astype(dt))
