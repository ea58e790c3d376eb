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
- THE CACHE ROW is ``c | k_pe``, ONE row: ``kv_row_shapes`` puts ``c``
  in lanes ``[0, kv_lora_rank)`` of ``"k"`` and ``k_pe``, zero-padded to
  a lane tile (``ops.mla_attention.PE_LANES``), in the lanes after it;
  ``"v"`` is a zero-width row, which the engine, the harness and the
  prefix gather carry like any other (since PR 45; ``c`` under ``"k"``
  and ``k_pe`` under ``"v"`` before: the same 1,280 B a token at the
  published widths). Why one row: the decode kernel then fetches a page
  with ONE copy, and a copy costs its kernel the descriptor's start, not
  the bytes (``ops/mla_attention.py`` has the readings: 1.362 -> 0.987
  ms a layer at the cell's shape). ``_rows_of`` / ``_row_parts`` are the
  only places that know the split. No head axis: ``[..., bs, 640]``
  tiles as it is, where ``[..., bs, 1, 640]`` would pad every row to a
  sublane tile. (The pad is for the kernel's page copies; a latent width
  that fills no lane tile, which the kernel cannot copy on the chip
  anyway, is not padded: the debug widths' row is ``c | k_pe`` as they
  are, 48 lanes.)
- TWO FORMS, one set of weights. ``W_uk`` [H, nope, R] and ``W_uv`` [H, R,
  v] are the two halves of the published ``kv_b_proj`` a head, held once.
  The prefills and training (``_attend_rows``) EXPAND: keys ``[c W_uk |
  k_pe]`` and values ``c W_uv`` of every row, then the family's masked
  attention at scale ``1/sqrt(nope + rope)``. The decode step
  (``_attend_pages``) ABSORBS: ``q_lat = q_nope W_uk^T``, the paged kernel
  over the latent rows (``ops/mla_attention.py``), ``o = o_lat W_uv``;
  no row of the cache is ever up-projected there.
- ``out = o Wo`` ([H, v, d]).

Three mechanisms a configuration may add, each by its own fields and
none changing a program of a model without it:

- A COMPRESSED QUERY (``q_lora_rank``): ``qr = RMSNorm(h Wq_a; q_norm)``,
  ``q = qr Wq_b``.
- YaRN (``yarn``) on the rotary lanes' frequencies, cos and sin unscaled,
  and with ``yarn_mscale_all_dim`` its factor squared on the softmax
  scale: ``m = 0.1 mscale_all_dim ln(factor) + 1``.
- LEARNED SPARSE ATTENTION (``index_n_heads`` > 0; ``ops/dsa.py``): a
  lightning indexer of its own weights scores every cached row for a
  query, ``I[t, s] = sum_j w[t, j] relu(q_I[t, j] . k_I[s])`` with ``q_I =
  qr W_qI``, ``k_I = LayerNorm(h W_kI)`` (ONE key a token), ``w = h W_w /
  sqrt(heads x width)``, the first rotary-width lanes of ``q_I`` and
  ``k_I`` turned in the HALF-SPLIT form, and the attention reads the
  ``index_topk`` rows of largest ``I`` alone (every row while there are
  no more). THE CACHE ROW gains a third part and is laid out by its
  own rule: ``"k"`` holds ``c``, ``"v"`` holds ``k_pe`` in its first
  lane tile and ``k_I`` in the lanes after it. In bf16 at
  widths that fill lane tiles (the chip's) the row is held AS 32-BIT
  WORDS, two numbers a word, and ALL OF IT UNDER ``"k"``: [4, 128]
  uint32 = the sub-row of ``k_pe | k_I``, ``c``'s two, one spare;
  ``"v"`` is a zero-width row (``word_rows``; ``ops/dsa.py`` says why:
  a kernel can then copy what the attention reads of ONE row by its
  number with ONE copy, and a copy's cost is its start, not its bytes;
  XLA lays a run of sub-rows out token by token only where they are a
  power of two, hence the spare: 2,048 B a token for 1,536). The prefills
  select as a MASK over the expanded form's rows (a block of queries at
  a time: index scores, the exact k-th largest as a threshold, the
  masked softmax); the decode step computes ``I`` over each slot's pages,
  takes the top-k's row numbers, gathers those rows and runs the
  absorbed form over them. ``_qkv`` hands the layer ``(q, q_I, w)`` as
  its query; the rest of the layer never looks inside.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models.llama import Params
from ray_tpu.models.moe import MoEConfig, MoEModel
from ray_tpu.ops import dsa
from ray_tpu.ops.attention import NEG_INF, reference_attention
from ray_tpu.ops.mla_attention import (PE_LANES, default_impl,
                                       mla_decode_attention)
from ray_tpu.ops.norms import layer_norm, rms_norm
from ray_tpu.ops.rope import YarnScaling, _rotate, yarn_inv_freq


# Queries a call of the expanded form scores at once (``_attend_rows``):
# the engine's prefills (chunks and buckets of at most 512 tokens) are
# one block; a prefill of thousands of tokens goes a block at a time.
QUERY_BLOCK = 1024
# ... of a layer with an indexer: a block holds the index heads' scores
# and every head's masked scores in float32, [64 + 128, block, S] at the
# published widths (1.6 GB at 128 queries over 16,896 rows)
INDEXED_QUERY_BLOCK = 128


@dataclasses.dataclass(frozen=True)
class MLAConfig(MoEConfig):
    """``head_dim`` is derived: the q.k width, nope + rope. ``n_kv_heads``
    is unused (the cache has no head axis)."""
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    # the query through a latent of its own (module docstring); None: one
    # projection
    q_lora_rank: Optional[int] = None
    # YaRN on the rotary lanes (not ``rope_scaling``, which is a kind's);
    # ``yarn_mscale_all_dim`` > 0 puts its factor squared on the softmax
    # scale
    yarn: Optional[YarnScaling] = None
    yarn_mscale_all_dim: float = 0.0
    # the lightning indexer: heads (0: none, dense attention), their
    # width, and the rows a query's attention reads
    index_n_heads: int = 0
    index_head_dim: int = 128
    index_topk: int = 2048

    def __post_init__(self):
        object.__setattr__(self, "head_dim",
                           self.qk_nope_head_dim + self.qk_rope_head_dim)
        super().__post_init__()
        if self.qk_rope_head_dim > PE_LANES or self.qk_rope_head_dim % 2:
            raise ValueError(
                f"qk_rope_head_dim ({self.qk_rope_head_dim}) is even and "
                f"fits the pool row's {PE_LANES} rotary lanes")
        if self.layer_types is not None or self.qk_norm or self.rope_scaling:
            raise ValueError("latent attention has no layer kinds (YaRN is "
                             "``yarn``) and no QK-norm")
        if self.index_n_heads and (
                not self.q_lora_rank or self.index_topk < 1
                or self.index_head_dim < self.qk_rope_head_dim):
            raise ValueError(
                "an indexer projects the compressed query (q_lora_rank) to "
                "heads no narrower than the rotary part and selects "
                "index_topk >= 1 rows")

    @property
    def softmax_scale(self) -> float:
        m = 1.0
        if self.yarn is not None and self.yarn_mscale_all_dim:
            m = (0.1 * self.yarn_mscale_all_dim * math.log(self.yarn.factor)
                 + 1.0)
        return self.head_dim ** -0.5 * m * m

    def attention_params(self) -> int:
        d, H, R = self.dim, self.n_heads, self.kv_lora_rank
        r, Hi, Di = self.q_lora_rank, self.index_n_heads, self.index_head_dim
        query = (d * H * self.head_dim if r is None
                 else d * r + r + r * H * self.head_dim)
        indexer = Hi and r * Hi * Di + d * Di + 2 * Di + d * Hi
        return (query + indexer + d * (R + self.qk_rope_head_dim) + R
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

    @staticmethod
    def debug_deepseek_v32(vocab_size: int = 512, max_seq_len: int = 128,
                           **overrides) -> "MLAConfig":
        """DeepSeek-V3.2's block at debug widths: a compressed query, an
        indexer of 4 heads x 16 that keeps 12 rows (contexts of 40-100
        rows select), YaRN that bites from position 16 on with its scale
        on the softmax, 16 experts in 4 groups of which 2 stay, top-4,
        one shared expert, one leading dense layer and two expert ones,
        a DRAWN selection bias."""
        return MLAConfig.debug_kanana(**{**dict(
            vocab_size=vocab_size, max_seq_len=max_seq_len, rope_theta=1e4,
            num_experts=16, expert_top_k=4, routed_scaling_factor=2.5,
            router_n_group=4, router_topk_group=2, shared_ffn_dim=32,
            q_lora_rank=24, index_n_heads=4, index_head_dim=16,
            index_topk=12, yarn=YarnScaling(
                factor=8.0, original_max_position=16, beta_fast=4.0,
                beta_slow=1.0, attention_factor=1.0),
            yarn_mscale_all_dim=1.0), **overrides})

    @staticmethod
    def debug_xing(vocab_size: int = 512, max_seq_len: int = 128,
                   **overrides) -> "MLAConfig":
        """Xing4.0's block at debug widths: FOUR residual streams mixed by
        manifold-constrained hyper-connections (20 Sinkhorn rounds)
        around ``debug_deepseek_v32``'s attention WITHOUT the indexer (a
        compressed query, YaRN with its scale on the softmax, the dense
        absorbed kernel), 8 experts top-3 with no group limit, one
        shared expert."""
        return MLAConfig.debug_deepseek_v32(**{**dict(
            vocab_size=vocab_size, max_seq_len=max_seq_len, num_experts=8,
            expert_top_k=3, routed_scaling_factor=2.0, router_n_group=1,
            router_topk_group=1, index_n_heads=0, hc_mult=4), **overrides})


class MLAModel(MoEModel):
    """The expert model with latent attention in every layer."""

    # ``kv_norm``, ``q_norm`` and the indexer's LayerNorm stay float32
    MATMUL_LAYER_LEAVES = MoEModel.MATMUL_LAYER_LEAVES + (
        "wkv_a", "w_uk", "w_uv", "wq_a", "wq_b", "idx_wq", "idx_wk",
        "idx_ww")

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
                                          cfg.rope_theta, cfg.yarn)
        # lanes of a cache row's rotary part (module docstring)
        self.pe_lanes = (PE_LANES if cfg.kv_lora_rank % PE_LANES == 0
                         else cfg.qk_rope_head_dim)
        self.indexed = bool(cfg.index_n_heads)
        # an indexed model's cache row as 32-bit words (module docstring):
        # where the Mosaic kernels of ``ops/dsa.py`` can read it
        self.word_rows = (
            self.indexed and jnp.dtype(cfg.dtype) == jnp.bfloat16
            and cfg.kv_lora_rank % (2 * dsa.WORD_LANES) == 0
            and self.pe_lanes == 2 * dsa.PE_WORDS
            and cfg.qk_rope_head_dim <= dsa.PE_WORDS
            and cfg.index_head_dim == 2 * (dsa.WORD_LANES - dsa.PE_WORDS))

    def _init_attention(self, k, L: int) -> Params:
        cfg: MLAConfig = self.cfg
        d, H, R, dense = cfg.dim, cfg.n_heads, cfg.kv_lora_rank, self._dense
        nope, rope, v = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                         cfg.v_head_dim)
        r, Hi, Di = cfg.q_lora_rank, cfg.index_n_heads, cfg.index_head_dim
        if r is None:
            query = {"wq": dense(next(k), (L, d, H, nope + rope), d)}
        else:
            query = {"wq_a": dense(next(k), (L, d, r), d),
                     "q_norm": jnp.ones((L, r), jnp.float32),
                     "wq_b": dense(next(k), (L, r, H, nope + rope), r)}
        if self.indexed:
            # the LayerNorm's scale and bias are one leaf [L, 2, Di]
            query.update(
                idx_wq=dense(next(k), (L, r, Hi, Di), r),
                idx_wk=dense(next(k), (L, d, Di), d),
                idx_k_norm=jnp.stack([jnp.ones((L, Di), jnp.float32),
                                      jnp.zeros((L, Di), jnp.float32)], 1),
                idx_ww=dense(next(k), (L, d, Hi), d))
        return {**query,
                "wkv_a": dense(next(k), (L, d, R + rope), d),
                "kv_norm": jnp.ones((L, R), jnp.float32),
                "w_uk": dense(next(k), (L, H, nope, R), R),
                "w_uv": dense(next(k), (L, H, R, v), R),
                "wo": dense(next(k), (L, H, v, d), H * v)}

    # -- the cache row -------------------------------------------------------
    def kv_row_shapes(self) -> Tuple[Tuple[int, ...], Tuple[int, ...]]:
        """Without an indexer ONE row under ``"k"``, ``c`` then ``k_pe``
        in its (padded) lanes, and ``"v"`` holds nothing. With one,
        ``"k"``: the latent row ``c``; ``"v"``: ``k_pe`` in the first
        lanes of a zero lane tile and the index key ``k_I`` in the lanes
        after it; as words the row is ONE run of sub-rows under ``"k"``
        (``k_pe | k_I``'s, then ``c``'s, zeros up to a power of two) and
        ``"v"`` holds nothing."""
        cfg: MLAConfig = self.cfg
        if not self.indexed:
            return (cfg.kv_lora_rank + self.pe_lanes,), (0,)
        if self.word_rows:
            return ((dsa.word_row_subrows(cfg.kv_lora_rank), dsa.WORD_LANES),
                    (0,))
        return (cfg.kv_lora_rank,), (self.pe_lanes + cfg.index_head_dim,)

    @property
    def kv_dtype(self):
        return jnp.uint32 if self.word_rows else self.cfg.dtype

    def _rows_of(self, c, k_pe, k_idx=None):
        """The cache rows ``("k", "v")`` of what they hold: ``c | k_pe``
        as one row of ``"k"``; with an indexer the third part rides
        behind ``k_pe``'s lane tile, in ``"v"`` or, where the row is
        held as words, in the first sub-row of ``"k"``."""
        lead = c.shape[:-1]
        if not self.indexed:
            return (jnp.concatenate([c, k_pe], axis=-1),
                    jnp.zeros((*lead, 0), c.dtype))
        if not self.word_rows:
            return c, jnp.concatenate([k_pe, k_idx], axis=-1)
        row, _ = self.kv_row_shapes()
        words = jnp.concatenate([dsa.pack_words(k_pe), dsa.pack_words(k_idx),
                                 dsa.pack_words(c)], axis=-1)
        spare = math.prod(row) - words.shape[-1]
        return (jnp.pad(words, [(0, 0)] * len(lead) + [(0, spare)]
                        ).reshape(*lead, *row),
                jnp.zeros((*lead, 0), jnp.uint32))

    def _c_of(self, k_rows):
        """``c`` [..., R] out of rows of ``"k"``."""
        if not self.word_rows:
            return k_rows
        n_sub = self.cfg.kv_lora_rank // (2 * dsa.WORD_LANES)
        c = k_rows[..., 1:1 + n_sub, :]
        return dsa.unpack_words(c.reshape(*c.shape[:-2], -1), self.cfg.dtype)

    def _keys_of(self, rows):
        """``(k_pe [..., pe_lanes], k_I [..., Di])`` out of rows of the
        array that holds them: ``"k"`` where the row is words, else
        ``"v"``."""
        if not self.word_rows:
            return rows[..., :self.pe_lanes], rows[..., self.pe_lanes:]
        dt, keys = self.cfg.dtype, rows[..., 0, :]
        return (dsa.unpack_words(keys[..., :dsa.PE_WORDS], dt),
                dsa.unpack_words(keys[..., dsa.PE_WORDS:], dt))

    def _row_parts(self, k_rows, v_rows):
        """``_rows_of``'s inverse: ``(c, k_pe, k_I)`` in the compute
        dtype (``k_I`` None without an indexer)."""
        if not self.indexed:
            R = self.cfg.kv_lora_rank
            return k_rows[..., :R], k_rows[..., R:], None
        return (self._c_of(k_rows),
                *self._keys_of(k_rows if self.word_rows else v_rows))

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

    def _rope_idx(self, x, positions):
        """The indexer's rotary lanes, the FIRST ``qk_rope_head_dim`` of
        x [B, T, heads, Di], turned in the half-split form (lane ``i``
        with lane ``i + rope/2``) by the same frequencies; the other
        lanes are not turned."""
        rope = self.cfg.qk_rope_head_dim
        if positions is None:
            positions = jnp.arange(x.shape[-3])
        ang = positions.astype(jnp.float32)[..., None] * self._pe_inv_freq
        return jnp.concatenate(
            [_rotate(x[..., :rope], ang[..., :, None, :]), x[..., rope:]],
            axis=-1)

    # -- the layer's attention half ------------------------------------------
    def _qkv(self, h, layer: Params, positions, kind, pin):
        cfg: MLAConfig = self.cfg
        dt, R, nope = cfg.dtype, cfg.kv_lora_rank, cfg.qk_nope_head_dim
        scope, source, wq = "mla_q_proj", h, layer.get("wq")
        if cfg.q_lora_rank is not None:
            with jax.named_scope("mla_q_down"):
                qr = rms_norm(
                    jnp.einsum("bsd,dr->bsr", h, layer["wq_a"].astype(dt)),
                    layer["q_norm"], eps=cfg.norm_eps)
            scope, source, wq = "mla_q_up", qr, layer["wq_b"]
        with jax.named_scope(scope):
            q = jnp.einsum("bsd,dhk->bshk", source, wq.astype(dt))
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
        if not self.indexed:
            return q, *self._rows_of(c, k_pe)
        Hi, Di = cfg.index_n_heads, cfg.index_head_dim
        with jax.named_scope("dsa_indexer_q"):
            q_idx = self._rope_idx(jnp.einsum(
                "bsr,rhk->bshk", qr, layer["idx_wq"].astype(dt)), positions)
            # float32: it weighs float32 scores (``ops.dsa.index_scores``)
            w = jnp.einsum("bsd,dh->bsh", h, layer["idx_ww"].astype(dt),
                           preferred_element_type=jnp.float32
                           ) * (Hi ** -0.5 * Di ** -0.5)
        with jax.named_scope("dsa_indexer_k"):
            k_idx = layer_norm(
                jnp.einsum("bsd,dk->bsk", h, layer["idx_wk"].astype(dt)),
                layer["idx_k_norm"][0], layer["idx_k_norm"][1], eps=1e-6)
            k_idx = self._rope_idx(k_idx[..., None, :], positions)[..., 0, :]
        return (q, q_idx, w), *self._rows_of(c, k_pe, k_idx)

    def _attend_rows(self, q, k_rows, v_rows, layer: Params, positions_q,
                     positions_k, window=None):
        """The EXPANDED form: every cache row's keys and values
        up-projected from its latent part, then the family's masked
        attention."""
        cfg: MLAConfig = self.cfg
        dt = cfg.dtype
        # None where the configuration leaves the scale as it was
        scale = cfg.softmax_scale if cfg.yarn_mscale_all_dim else None
        if self.indexed:
            q, q_idx, w_idx = q
        c, k_pe, k_idx = self._row_parts(k_rows, v_rows)
        with jax.named_scope("mla_kv_up"):
            k_nope = jnp.einsum("bsr,hnr->bshn", c, layer["w_uk"].astype(dt))
            v = jnp.einsum("bsr,hrv->bshv", c, layer["w_uv"].astype(dt))
            k = jnp.concatenate([k_nope, jnp.broadcast_to(
                k_pe[:, :, None, :cfg.qk_rope_head_dim],
                k_nope.shape[:3] + (cfg.qk_rope_head_dim,))], axis=-1)
        if self.indexed:
            return self._attend_selected_rows(
                (q, q_idx, w_idx), k, v, k_idx, positions_q, positions_k)
        with jax.named_scope("mla_attention"):
            B, T = q.shape[:2]
            tb = max(b for b in range(1, min(T, QUERY_BLOCK) + 1)
                     if T % b == 0)
            if tb == T:
                return reference_attention(
                    q, k, v, positions_q=positions_q,
                    positions_k=positions_k, window=window, scale=scale)

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
                    window=window, scale=scale),
                (blocks(q), blocks(jnp.broadcast_to(positions_q, (B, T)))))
            return jnp.moveaxis(o, 0, 1).reshape(B, T, *o.shape[3:])

    def _attend_selected_rows(self, query, k, v, k_idx, positions_q,
                              positions_k):
        """The expanded form under the indexer's selection, a block of
        queries at a time: the block's index scores against every row,
        the ``index_topk`` largest of the rows at or before each query
        as a mask (``ops.dsa.topk_mask``: exact, ties to the earlier
        row), softmax over the masked scores."""
        cfg: MLAConfig = self.cfg
        B, T = query[0].shape[:2]
        S = k.shape[1]
        tb = max(b for b in range(1, min(T, INDEXED_QUERY_BLOCK) + 1)
                 if T % b == 0)
        if positions_q is None:            # training: 0..T-1
            positions_q = jnp.arange(T)
        if positions_k is None:
            positions_k = jnp.arange(S)
        positions_k = jnp.broadcast_to(positions_k, (B, S))

        def block(args):
            (q, q_idx, w_idx), pos_q = args
            seen = pos_q[:, :, None] >= positions_k[:, None, :]   # [B,tb,S]
            with jax.named_scope("dsa_indexer_scores"):
                scores = dsa.index_scores(q_idx, w_idx, k_idx)
            with jax.named_scope("dsa_select"):
                mask = dsa.topk_mask(scores, seen, cfg.index_topk)
            with jax.named_scope("dsa_masked_attention"):
                s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                               preferred_element_type=jnp.float32
                               ) * cfg.softmax_scale
                p = jax.nn.softmax(
                    jnp.where(mask[:, None], s, NEG_INF), axis=-1)
                return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)

        def blocks(a):
            return jnp.moveaxis(
                a.reshape(B, T // tb, tb, *a.shape[2:]), 1, 0)

        o = jax.lax.map(block, (
            jax.tree.map(blocks, query),
            blocks(jnp.broadcast_to(positions_q, (B, T)))))
        return jnp.moveaxis(o, 0, 1).reshape(B, T, *o.shape[3:])

    def _attention(self, q, k_rows, v_rows, positions, window=None,
                   layer=None):
        return self._attend_rows(q, k_rows, v_rows, layer, positions,
                                 positions, window)

    def paged_decode_impl(self) -> str:
        """"mla_pallas" (the Mosaic kernel of ``ops/mla_attention.py``) or
        "mla_xla" (its twin): what the configuration forces, else the
        platform's. With an indexer "dsa_pallas" / "dsa_xla": the two
        Mosaic kernels of ``ops/dsa.py`` (the index scores through the
        block table; the absorbed attention over the gathered selected
        rows) or their twins; ``sparse_decode_plan`` names each piece."""
        if not self.indexed:
            return "mla_" + (self.cfg.decode_attention or default_impl())
        # the kernels read rows held as words; any other row, the twins
        return "dsa_" + ((self.cfg.decode_attention or default_impl())
                         if self.word_rows else "xla")

    def sparse_decode_plan(self) -> Dict:
        if not self.indexed:
            return super().sparse_decode_plan()
        cfg: MLAConfig = self.cfg
        side = self.paged_decode_impl().removeprefix("dsa_")
        return {"index_topk": cfg.index_topk,
                "kv_index_row_bytes": (cfg.index_head_dim
                                       * jnp.dtype(cfg.dtype).itemsize),
                "decode_indexer_impl": "dsa_indexer_" + side,
                # the selection is XLA's top-k on every platform
                "decode_select_impl": "xla_top_k"}

    def _attend_pages(self, q, k_pool, v_pool, layer: Params, block_tables,
                      lengths, *, impl, starts=None, first_block=0,
                      num_blocks=None, run=1):
        """The ABSORBED form: q [B, H, nope + rope] against the latent
        pages (the pools of ``"k"`` and ``"v"``); no row of the cache is
        up-projected. Its kernels copy a block a page (``run`` 1:
        ``paged_run_blocks`` says so to the engine)."""
        if run != 1:
            raise NotImplementedError(
                "the latent pages' kernels copy single blocks")
        cfg: MLAConfig = self.cfg
        dt, nope = cfg.dtype, cfg.qk_nope_head_dim
        if self.indexed:
            q, q_idx, w_idx = q
        with jax.named_scope("mla_q_absorb"):
            q_lat = jnp.einsum("bhn,hnr->bhr", q[..., :nope],
                               layer["w_uk"].astype(dt))
            q_pe = jnp.pad(q[..., nope:], ((0, 0), (0, 0), (
                0, self.pe_lanes - cfg.qk_rope_head_dim)))
        if self.indexed:
            side = impl.removeprefix("dsa_")
            with jax.named_scope("dsa_indexer_scores"):
                scores = dsa.indexer_scores(
                    q_idx, w_idx, k_pool if self.word_rows else v_pool,
                    block_tables, lengths, impl=side,
                    key_of=lambda rows: self._keys_of(rows)[1],
                    first_block=first_block)
            with jax.named_scope("dsa_select"):
                rows, count = dsa.select_topk(scores, lengths,
                                              cfg.index_topk)
            with jax.named_scope("dsa_attention"):
                o_lat = dsa.sparse_decode_attention(
                    q_lat, q_pe, k_pool, v_pool, block_tables, rows, count,
                    impl=side, scale=cfg.softmax_scale,
                    parts_of=lambda k, v: self._row_parts(k, v)[:2],
                    first_block=first_block)
            with jax.named_scope("mla_v_up"):
                return jnp.einsum("bhr,hrv->bhv", o_lat,
                                  layer["w_uv"].astype(dt))
        with jax.named_scope("mla_attention"):
            o_lat = mla_decode_attention(
                q_lat, q_pe, k_pool, block_tables, lengths,
                impl=impl.removeprefix("mla_"), scale=cfg.softmax_scale,
                first_block=first_block)
        with jax.named_scope("mla_v_up"):
            return jnp.einsum("bhr,hrv->bhv", o_lat, layer["w_uv"].astype(dt))
