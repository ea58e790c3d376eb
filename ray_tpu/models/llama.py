"""Llama-3 family, TPU-native.

Reference capability: Ray trains Llama via TorchTrainer+FSDP wrappers
(`release/train_tests/benchmark/train_benchmark.py`) and serves it via vLLM
(`python/ray/llm`) — the model itself lives outside the reference tree. Here
it is in-tree and TPU-first:

- params are a pytree of stacked-layer arrays; the transformer stack is a
  single ``lax.scan`` (one compiled block regardless of depth);
- every param/activation carries logical axis names resolved to the 6-axis
  mesh (dp/fsdp/pp/tp/sp/ep) by ``ray_tpu.parallel.mesh`` rules —
  Megatron-style TP, ZeRO-style fsdp sharding, ring-attention SP all come
  from the same annotations;
- compute dtype bfloat16 (MXU-native), params/optimizer f32;
- ``remat`` on each layer trades FLOPs for HBM (the standard TPU recipe).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import attention
from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.ring_attention import ring_attention
from ray_tpu.ops.rope import apply_rope, rope_frequencies

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128_256
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    ffn_dim: int = 14_336
    max_seq_len: int = 8192
    rope_theta: float = 500_000.0
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    dtype: Any = jnp.bfloat16
    remat: bool = True
    # remat policy: "full" recomputes everything (min HBM, +2N FLOPs);
    # "dots" saves matmul outputs (recompute only elementwise — near-6N
    # useful FLOPs at higher HBM); the standard TPU MFU/memory dial.
    remat_policy: str = "full"
    # Attention implementation (SURVEY §5.7):
    # "ring" = ppermute K/V rotation CP (any head count, O(S/sp) memory);
    # "ulysses" = all-to-all head/seq swap CP (needs n_heads % sp == 0,
    # local full-sequence attention so any local kernel applies);
    # "flash" = single-device Pallas flash kernel (ops/attention.py) —
    # the MFU path for sp==1 (bench default); interpret-mode on CPU;
    # "xla" = blockwise online-softmax in pure XLA (O(S·block) memory)
    # — the A/B baseline the Pallas kernel must beat.
    attention_impl: str = "ring"
    # Pallas flash tile sizes (the per-grid-step overhead vs VMEM dial)
    flash_block_q: int = 128
    flash_block_k: int = 128
    # KV-cache decode attention. None (the default): the paged decode
    # program takes the Mosaic kernel on a TPU backend and the XLA
    # reference elsewhere (``LlamaModel.paged_decode_impl``,
    # ops/paged_attention.py); "xla" / "pallas" force a side (tests,
    # chip_smoke.py). ``forward_step``'s T == 1 branch takes its
    # slot-major kernel (ops/decode_attention.py, which does not lower
    # for the v5e) only on an explicit "pallas".
    decode_attention: Optional[str] = None

    def __post_init__(self):
        if self.attention_impl not in ("ring", "ulysses", "flash", "xla"):
            raise ValueError(
                f"attention_impl must be 'ring', 'ulysses', 'flash' or "
                f"'xla', got {self.attention_impl!r}")
        if self.remat_policy not in ("full", "dots"):
            raise ValueError(
                f"remat_policy must be 'full' or 'dots', "
                f"got {self.remat_policy!r}")
        if self.decode_attention not in (None, "xla", "pallas"):
            raise ValueError(
                f"decode_attention must be None, 'xla' or 'pallas', "
                f"got {self.decode_attention!r}")

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    def num_params(self) -> int:
        d, f, v = self.dim, self.ffn_dim, self.vocab_size
        kv = self.n_kv_heads * self.head_dim
        per_layer = d * d + 2 * d * kv + d * d + 3 * d * f + 2 * d
        heads = 0 if self.tie_embeddings else v * d
        return v * d + self.n_layers * per_layer + d + heads

    # -- presets (sizes match the public Llama-3 family) --
    @staticmethod
    def llama3_8b() -> "LlamaConfig":
        return LlamaConfig()

    @staticmethod
    def llama3_1b() -> "LlamaConfig":
        return LlamaConfig(dim=2048, n_layers=16, n_heads=32, n_kv_heads=8,
                           ffn_dim=8192)

    @staticmethod
    def bench_400m(max_seq_len: int = 2048) -> "LlamaConfig":
        """~440M params: sized so f32 params+adam+grads fit a 16GB chip.

        head_dim=128 (MXU tile width) so the Pallas flash kernel — the
        bench default — tiles cleanly onto the systolic array.
        """
        return LlamaConfig(vocab_size=32_000, dim=1024, n_layers=24,
                           n_heads=8, n_kv_heads=4, ffn_dim=4096,
                           max_seq_len=max_seq_len, attention_impl="flash")

    @staticmethod
    def debug(vocab_size: int = 256, max_seq_len: int = 128) -> "LlamaConfig":
        return LlamaConfig(vocab_size=vocab_size, dim=64, n_layers=2,
                           n_heads=4, n_kv_heads=2, ffn_dim=128,
                           max_seq_len=max_seq_len, remat=False)


# Logical axis names per param leaf (see parallel/mesh.py DEFAULT_RULES).
def param_logical_axes(cfg: LlamaConfig) -> Params:
    axes = {
        "embed": ("vocab", "embed_in"),
        "layers": {
            "attn_norm": (None, "embed_in"),
            "wq": (None, "embed_in", "heads", None),
            "wk": (None, "embed_in", "kv_heads", None),
            "wv": (None, "embed_in", "kv_heads", None),
            "wo": (None, "heads", None, "embed_in"),
            "mlp_norm": (None, "embed_in"),
            "w_gate": (None, "embed_in", "mlp"),
            "w_up": (None, "embed_in", "mlp"),
            "w_down": (None, "mlp", "embed_in"),
        },
        "norm_f": ("embed_in",),
    }
    if not cfg.tie_embeddings:
        axes["lm_head"] = ("embed_in", "vocab")
    return axes


class LlamaModel:
    """Functional model: ``init`` makes params, ``apply`` runs the forward.

    ``mesh``/``rules`` (optional) activate sharding constraints on
    activations and select ring attention when the sp axis is >1.
    """

    def __init__(self, cfg: LlamaConfig, mesh=None,
                 rules: Optional[Dict] = None):
        self.cfg = cfg
        self.mesh = mesh
        self.rules = rules
        self._sp = 1 if mesh is None else mesh.shape.get("sp", 1)
        if self._sp > 1 and cfg.attention_impl == "flash":
            raise ValueError(
                "attention_impl='flash' is a single-device kernel; with an "
                "sp>1 mesh use 'ring' or 'ulysses' context parallelism")
        self._angles = rope_frequencies(cfg.head_dim, cfg.max_seq_len,
                                        theta=cfg.rope_theta)

    # -- init ---------------------------------------------------------------
    def init(self, rng: jax.Array) -> Params:
        cfg = self.cfg
        d, hd = cfg.dim, cfg.head_dim
        k = iter(jax.random.split(rng, 16))

        def dense(key, shape, fan_in):
            return (jax.random.normal(key, shape, jnp.float32)
                    * (fan_in ** -0.5))

        L = cfg.n_layers
        params: Params = {
            "embed": dense(next(k), (cfg.vocab_size, d), d),
            "layers": {
                "attn_norm": jnp.ones((L, d), jnp.float32),
                "wq": dense(next(k), (L, d, cfg.n_heads, hd), d),
                "wk": dense(next(k), (L, d, cfg.n_kv_heads, hd), d),
                "wv": dense(next(k), (L, d, cfg.n_kv_heads, hd), d),
                "wo": dense(next(k), (L, cfg.n_heads, hd, d), d),
                "mlp_norm": jnp.ones((L, d), jnp.float32),
                "w_gate": dense(next(k), (L, d, cfg.ffn_dim), d),
                "w_up": dense(next(k), (L, d, cfg.ffn_dim), d),
                "w_down": dense(next(k), (L, cfg.ffn_dim, d), cfg.ffn_dim),
            },
            "norm_f": jnp.ones((d,), jnp.float32),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = dense(next(k), (d, cfg.vocab_size), d)
        return params

    # -- sharding helpers ---------------------------------------------------
    def _constrain(self, x, *names):
        if self.mesh is None:
            return x
        from ray_tpu.parallel.mesh import shard_constraint
        return shard_constraint(x, self.mesh, *names, rules=self.rules)

    def param_shardings(self):
        """NamedSharding pytree for params (pass to jit in_shardings)."""
        from ray_tpu.parallel.mesh import named_sharding
        axes = param_logical_axes(self.cfg)
        return jax.tree.map(
            lambda names: named_sharding(self.mesh, *names,
                                         rules=self.rules),
            axes, is_leaf=lambda x: isinstance(x, tuple))

    # -- forward ------------------------------------------------------------
    def _embed_lookup(self, table: jax.Array, tokens: jax.Array) -> jax.Array:
        """Vocab-parallel embedding lookup.

        The table is vocab-sharded over tp; a plain gather forces XLA into
        "involuntary full rematerialization" (replicate + repartition) of
        the table. Megatron-style instead: each tp shard looks up only
        tokens in its vocab range and a psum combines — communication is
        one all-reduce of [B,S,D] activations, never the table.
        """
        mesh = self.mesh
        if mesh is None or mesh.shape.get("tp", 1) == 1:
            return table[tokens]
        from jax.sharding import PartitionSpec as P

        present = set(mesh.shape.keys())
        sp = mesh.shape.get("sp", 1)
        # decode steps carry T=1 (or odd prefill lengths): only shard the
        # seq dim when it actually divides over sp
        seq_ax = ("sp" if "sp" in present and sp > 1
                  and tokens.shape[1] % sp == 0 else None)
        # The table keeps BOTH its shardings inside the shard_map (vocab
        # over tp, embed dim over fsdp) so no table bytes ever move; each
        # fsdp rank looks up its D-slice for the dp batch shard, and the
        # follow-up _constrain reshards only the [B,S,D] activations.
        dp_ax = "dp" if "dp" in present else None
        fsdp_ax = "fsdp" if "fsdp" in present else None
        vshard = self.cfg.vocab_size // mesh.shape["tp"]

        def lookup(table_local, tok):
            start = jax.lax.axis_index("tp") * vshard
            local = tok - start
            valid = (local >= 0) & (local < vshard)
            safe = jnp.where(valid, local, 0)
            out = table_local[safe] * valid[..., None].astype(
                table_local.dtype)
            return jax.lax.psum(out, "tp")

        fn = jax.shard_map(
            lookup, mesh=mesh,
            in_specs=(P("tp", fsdp_ax), P(dp_ax, seq_ax)),
            out_specs=P(dp_ax, seq_ax, fsdp_ax), check_vma=False)
        return fn(table, tokens)

    def _attention(self, q, k, v, positions):
        if self._sp > 1:
            if positions is not None:
                raise NotImplementedError(
                    "explicit positions are not supported with sp>1: the "
                    "context-parallel causal mask assumes contiguous "
                    "0..S-1")
            # Inside pjit the arrays are globally-shaped; shard_map splits
            # them per-device and runs the collective scheme over ICI.
            if self.cfg.attention_impl == "ulysses":
                from ray_tpu.ops.ulysses import ulysses_attention_sharded
                return ulysses_attention_sharded(q, k, v, self.mesh,
                                                 causal=True)
            from ray_tpu.ops.ring_attention import ring_attention_sharded
            return ring_attention_sharded(q, k, v, self.mesh, causal=True)
        # sp==1: "flash" forces the Pallas kernel (interpret-mode
        # off-TPU) with the config's tile sizes; "xla" forces the
        # blockwise online-softmax fallback; otherwise the dispatcher
        # auto-selects by platform/shape.
        cfg = self.cfg
        if cfg.attention_impl == "flash" and positions is None:
            from ray_tpu.ops.attention import flash_attention
            # positional: custom_vjp functions reject keyword args
            return flash_attention(q, k, v, True, cfg.flash_block_q,
                                   cfg.flash_block_k)
        if cfg.attention_impl == "xla" and positions is None:
            from ray_tpu.ops.attention import blockwise_attention
            return blockwise_attention(q, k, v, causal=True)
        return attention(q, k, v, causal=True, positions_q=positions,
                         positions_k=positions, use_flash=None)

    # -- the two places a model of this family may differ from the dense
    # block; every copy of the block (``_block`` and the three serving
    # closures below) goes through them ------------------------------------
    def _qk_norm(self, q, k, layer: Params):
        """Between the q/k projections and RoPE. q [B, T, H, hd], k
        [B, T, Hkv, hd]; the dense block does nothing here."""
        return q, k

    def _ffn(self, h, layer: Params, live=None, constrain: bool = False):
        """The feed-forward half: h [B, T, D] (already normed) ->
        ``(out [B, T, D], extra)``. ``extra`` is whatever the model
        wants carried out of the layer scan (``None`` here); ``live``
        [B] bool marks the rows it should count (all, if ``None``);
        ``constrain`` (the training block) pins the inner activation's
        sharding to the mesh."""
        dt = self.cfg.dtype
        with jax.named_scope("mlp"):
            gate = jnp.einsum("bsd,df->bsf", h, layer["w_gate"].astype(dt))
            up = jnp.einsum("bsd,df->bsf", h, layer["w_up"].astype(dt))
            ff = jax.nn.silu(gate) * up
            if constrain:
                ff = self._constrain(ff, "batch", "seq", "mlp")
            down = jnp.einsum("bsf,fd->bsd", ff, layer["w_down"].astype(dt))
        return down, None

    def _block(self, x, layer: Params, positions):
        """-> (x, the layer's ``_ffn`` extra)."""
        cfg = self.cfg
        dt = cfg.dtype
        with jax.named_scope("norm_residual"):
            h = rms_norm(x, layer["attn_norm"], eps=cfg.norm_eps)
        with jax.named_scope("attention"):
            q = jnp.einsum("bsd,dhk->bshk", h, layer["wq"].astype(dt))
            kk = jnp.einsum("bsd,dhk->bshk", h, layer["wk"].astype(dt))
            vv = jnp.einsum("bsd,dhk->bshk", h, layer["wv"].astype(dt))
            q, kk = self._qk_norm(q, kk, layer)
            q = self._constrain(q, "batch", "seq", "heads", None)
            q = apply_rope(q, self._angles, positions)
            kk = apply_rope(kk, self._angles, positions)
            o = self._attention(q, kk, vv, positions)
            o = jnp.einsum("bshk,hkd->bsd", o, layer["wo"].astype(dt))
        with jax.named_scope("norm_residual"):
            x = x + self._constrain(o, "batch", "seq", "embed")
            h = rms_norm(x, layer["mlp_norm"], eps=cfg.norm_eps)
        down, extra = self._ffn(h, layer, constrain=True)
        with jax.named_scope("norm_residual"):
            return x + self._constrain(down, "batch", "seq", "embed"), extra

    def apply(self, params: Params, tokens: jax.Array,
              positions: Optional[jax.Array] = None) -> jax.Array:
        """tokens [B, S] int32 -> logits [B, S, V] (f32)."""
        return self._apply_with_extras(params, tokens, positions)[0]

    def _apply_with_extras(self, params: Params, tokens: jax.Array,
                           positions: Optional[jax.Array] = None):
        """``apply`` and, stacked over layers, each layer's ``_ffn``
        extra (``None`` for the dense block)."""
        cfg = self.cfg
        with jax.named_scope("embed"):
            x = self._embed_lookup(params["embed"].astype(cfg.dtype), tokens)
            x = self._constrain(x, "batch", "seq", "embed")

        block = self._block
        if cfg.remat:
            if cfg.remat_policy == "dots":
                block = jax.checkpoint(
                    block, policy=jax.checkpoint_policies.dots_saveable)
            else:
                block = jax.checkpoint(block, static_argnums=())

        def scan_body(x, layer):
            return block(x, layer, positions)

        x, extras = jax.lax.scan(scan_body, x, params["layers"])
        with jax.named_scope("logits"):
            x = rms_norm(x, params["norm_f"], eps=cfg.norm_eps)
            head = (params["embed"].T if cfg.tie_embeddings
                    else params["lm_head"])
            logits = jnp.einsum("bsd,dv->bsv", x, head.astype(cfg.dtype))
            logits = self._constrain(logits, "batch", "seq", "vocab")
            return logits.astype(jnp.float32), extras

    # -- KV-cache inference path (serving; BASELINE.md config 5) ----------
    def init_kv_cache(self, batch: int, max_seq: int) -> Params:
        """Slot-major cache: [L, B, S, Hkv, D] per k/v, bf16 in HBM."""
        cfg = self.cfg
        shape = (cfg.n_layers, batch, max_seq, cfg.n_kv_heads, cfg.head_dim)
        return {"k": jnp.zeros(shape, cfg.dtype),
                "v": jnp.zeros(shape, cfg.dtype)}

    def forward_step(self, params: Params, tokens: jax.Array,
                     cache: Params, offsets: jax.Array
                     ) -> Tuple[jax.Array, Params]:
        """Unified prefill/decode step with KV cache.

        tokens  [B, T] — T = padded prompt length (prefill) or 1 (decode)
        offsets [B]    — how many tokens each slot has already cached
        Returns (logits [B, T, V], updated cache). Static shapes: the same
        jit specialization serves every request of a given (B, T, S).
        """
        cfg = self.cfg
        B, T = tokens.shape
        S = cache["k"].shape[2]
        q_pos = offsets[:, None] + jnp.arange(T)[None, :]        # [B, T]
        with jax.named_scope("embed"):
            x = self._embed_lookup(params["embed"].astype(cfg.dtype), tokens)

        batch_idx = jnp.arange(B)[:, None]

        def block(carry, layer_and_cache):
            x = carry
            layer, k_cache, v_cache = layer_and_cache
            dt = cfg.dtype
            with jax.named_scope("norm_residual"):
                h = rms_norm(x, layer["attn_norm"], eps=cfg.norm_eps)
            with jax.named_scope("attention"):
                q = jnp.einsum("bsd,dhk->bshk", h, layer["wq"].astype(dt))
                k_new = jnp.einsum("bsd,dhk->bshk", h,
                                   layer["wk"].astype(dt))
                v_new = jnp.einsum("bsd,dhk->bshk", h,
                                   layer["wv"].astype(dt))
                q, k_new = self._qk_norm(q, k_new, layer)
                q = apply_rope(q, self._angles, q_pos)
                k_new = apply_rope(k_new, self._angles, q_pos)
            with jax.named_scope("kv_update"):
                # scatter new k/v into the cache at each slot's write
                # offsets
                k_cache = k_cache.at[batch_idx, q_pos].set(k_new)
                v_cache = v_cache.at[batch_idx, q_pos].set(v_new)
            with jax.named_scope("attention"):
                if T == 1 and cfg.decode_attention == "pallas":
                    # single-token decode: ragged kernel skips KV blocks
                    # past each slot's live length
                    from ray_tpu.ops.decode_attention import \
                        ragged_decode_attention_pallas
                    o = ragged_decode_attention_pallas(
                        q[:, 0], k_cache, v_cache, q_pos[:, 0] + 1)[:, None]
                else:
                    # attend over cache positions <= own position
                    from ray_tpu.ops.attention import NEG_INF, _repeat_kv
                    kk = _repeat_kv(k_cache, cfg.n_heads)
                    vv = _repeat_kv(v_cache, cfg.n_heads)
                    s = jnp.einsum("bthd,bshd->bhts", q, kk,
                                   preferred_element_type=jnp.float32)
                    s = s * (cfg.head_dim ** -0.5)
                    mask = (jnp.arange(S)[None, None, :]
                            <= q_pos[:, :, None])
                    s = jnp.where(mask[:, None], s, NEG_INF)
                    p = jax.nn.softmax(s, axis=-1)
                    o = jnp.einsum("bhts,bshd->bthd", p.astype(dt), vv)
                o = jnp.einsum("bshk,hkd->bsd", o, layer["wo"].astype(dt))
            with jax.named_scope("norm_residual"):
                x = x + o
                h = rms_norm(x, layer["mlp_norm"], eps=cfg.norm_eps)
            down, _ = self._ffn(h, layer)
            with jax.named_scope("norm_residual"):
                return x + down, (k_cache, v_cache)

        x, (k_out, v_out) = jax.lax.scan(
            block, x, (params["layers"], cache["k"], cache["v"]))
        with jax.named_scope("logits"):
            x = rms_norm(x, params["norm_f"], eps=cfg.norm_eps)
            head = (params["embed"].T if cfg.tie_embeddings
                    else params["lm_head"])
            logits = jnp.einsum("bsd,dv->bsv", x, head.astype(cfg.dtype))
            return logits.astype(jnp.float32), {"k": k_out, "v": v_out}

    # -- paged KV-cache path (llm/engine.py + llm/paged_cache.py) ---------
    def init_kv_pool(self, num_blocks: int, block_size: int) -> Params:
        """Block-pool cache: k/v [L, num_blocks, block_size, Hkv, D],
        bf16 in HBM, shared by every slot via per-slot block tables."""
        cfg = self.cfg
        shape = (cfg.n_layers, num_blocks, block_size, cfg.n_kv_heads,
                 cfg.head_dim)
        return {"k": jnp.zeros(shape, cfg.dtype),
                "v": jnp.zeros(shape, cfg.dtype)}

    def paged_decode_impl(self) -> str:
        """The attention ``decode_step_paged`` is built with, and the one
        place that decides it: what the configuration forces; else under
        a mesh the XLA reference (the kernel is one chip's program and
        carries no partitioning rule); else the platform's
        (``ops.paged_attention.default_impl``: the Mosaic kernel on a
        TPU backend)."""
        from ray_tpu.ops.paged_attention import default_impl
        if self.cfg.decode_attention is not None:
            return self.cfg.decode_attention
        if self.mesh is not None:
            return "xla"
        return default_impl(self.cfg.head_dim, self.cfg.n_kv_heads)

    def decode_step_paged(self, params: Params, tokens: jax.Array,
                          pool: Params, block_tables: jax.Array,
                          offsets: jax.Array
                          ) -> Tuple[jax.Array, Params]:
        """One decode step for every slot against the block pool.

        tokens [B] int32 (each slot's last sampled token)
        pool   k/v [L, NB, bs, Hkv, D]
        block_tables [B, MAXB] int32 physical ids (logical order)
        offsets [B] tokens already cached per slot
        Returns (logits [B, V], updated pool). Slots whose table rows
        point at garbage simply compute garbage that the engine masks.
        """
        return self.decode_step_paged_counted(
            params, tokens, pool, block_tables, offsets)[:2]

    def ffn_load_shape(self) -> Optional[Tuple[int, int]]:
        """Shape of the per-layer counts ``decode_step_paged_counted``
        reports third (under ``"load"``), or ``None`` where the FFN
        counts nothing, as the dense one."""
        return None

    def decode_step_paged_counted(self, params: Params, tokens: jax.Array,
                                  pool: Params, block_tables: jax.Array,
                                  offsets: jax.Array,
                                  live: Optional[jax.Array] = None):
        """``decode_step_paged`` and, third, each layer's ``_ffn`` extra
        stacked over layers (``None`` for the dense block), counted over
        the slots ``live`` [B] bool marks.

        The pool lives through the step WHOLE: the layer scan carries
        the stack ``[L*NB, bs, Hkv, D]`` (the pool with its two leading
        dimensions merged, the same bytes) and never a layer's slice of
        it, so a caller that donates the pool gets it back written in
        place. Layer ``l``'s page ``p`` is page ``l*NB + p`` of the
        stack: the layer writes its B rows at ``(l*NB + dest_block,
        dest_off)`` and attention, kernel and reference alike, reads
        through the block table plus ``l*NB``. (Handed to the scan as
        ``xs``/``ys`` the pool cost two whole copies a step and a slice
        out and back a layer: PERF.md, PR 27.)"""
        cfg = self.cfg
        L, NB, bs = pool["k"].shape[:3]
        dest_block = jnp.take_along_axis(
            block_tables, (offsets // bs)[:, None], axis=1)[:, 0]  # [B]
        dest_off = offsets % bs
        lengths = offsets + 1
        q_pos = offsets[:, None]                                   # [B, 1]
        with jax.named_scope("embed"):
            x = self._embed_lookup(params["embed"].astype(cfg.dtype),
                                   tokens[:, None])                # [B,1,D]
        impl = self.paged_decode_impl()
        from ray_tpu.ops.paged_attention import paged_decode_attention

        def block(carry, layer_and_base):
            x, k_pool, v_pool = carry
            # ``base``: where this layer's blocks start in the stack
            layer, base = layer_and_base
            dt = cfg.dtype
            with jax.named_scope("norm_residual"):
                h = rms_norm(x, layer["attn_norm"], eps=cfg.norm_eps)
            with jax.named_scope("attention"):
                q = jnp.einsum("bsd,dhk->bshk", h, layer["wq"].astype(dt))
                k_new = jnp.einsum("bsd,dhk->bshk", h,
                                   layer["wk"].astype(dt))
                v_new = jnp.einsum("bsd,dhk->bshk", h,
                                   layer["wv"].astype(dt))
                q, k_new = self._qk_norm(q, k_new, layer)
                q = apply_rope(q, self._angles, q_pos)
                k_new = apply_rope(k_new, self._angles, q_pos)
            with jax.named_scope("kv_update"):
                # each slot writes its own private tail block (refcount
                # 1 — shared prefix blocks are never write targets)
                k_pool = k_pool.at[base + dest_block, dest_off].set(
                    k_new[:, 0].astype(dt))
                v_pool = v_pool.at[base + dest_block, dest_off].set(
                    v_new[:, 0].astype(dt))
            with jax.named_scope("attention"):
                o = paged_decode_attention(q[:, 0], k_pool, v_pool,
                                           block_tables, lengths, impl=impl,
                                           first_block=base, num_blocks=NB)
                o = jnp.einsum("bhk,hkd->bd", o, layer["wo"].astype(dt))
            with jax.named_scope("norm_residual"):
                x = x + o[:, None]
                h = rms_norm(x, layer["mlp_norm"], eps=cfg.norm_eps)
            down, extra = self._ffn(h, layer, live)
            with jax.named_scope("norm_residual"):
                return (x + down, k_pool, v_pool), extra

        stack = (L * NB,) + pool["k"].shape[2:]
        (x, k_out, v_out), extras = jax.lax.scan(
            block,
            (x, pool["k"].reshape(stack), pool["v"].reshape(stack)),
            (params["layers"], jnp.arange(L, dtype=jnp.int32) * NB))
        pool = {"k": k_out.reshape(pool["k"].shape),
                "v": v_out.reshape(pool["v"].shape)}
        with jax.named_scope("logits"):
            x = rms_norm(x, params["norm_f"], eps=cfg.norm_eps)
            head = (params["embed"].T if cfg.tie_embeddings
                    else params["lm_head"])
            logits = jnp.einsum("bsd,dv->bsv", x, head.astype(cfg.dtype))
            return logits[:, 0].astype(jnp.float32), pool, extras

    def prefill_with_prefix(self, params: Params, tokens: jax.Array,
                            prefix_k: jax.Array, prefix_v: jax.Array,
                            prefix_len: jax.Array, lengths: jax.Array
                            ) -> Tuple[jax.Array, Params]:
        """Suffix prefill attending over a cached (shared) prefix.

        tokens   [N, Tb] suffix tokens (right-padded)
        prefix_k/v [L, N, Pmax, Hkv, D] dense prefix K/V gathered from
                 the pool, right-padded past ``prefix_len``
        prefix_len [N] valid prefix tokens
        lengths  [N] valid suffix tokens
        Returns (last-token logits [N, V], suffix K/V [L, N, Tb, Hkv, D])
        — the caller scatters the suffix K/V into fresh pool blocks; the
        prefix blocks are never copied or rewritten (prefix-reuse skips
        their FLOPs entirely).
        """
        cfg = self.cfg
        N, Tb = tokens.shape
        Pmax = prefix_k.shape[2]
        dt = cfg.dtype
        # absolute positions: suffix token t sits at prefix_len + t;
        # padded prefix rows get a position PAST every query so the
        # causal mask drops them
        pos_q = prefix_len[:, None] + jnp.arange(Tb)[None, :]       # [N,Tb]
        far = jnp.int32(2 ** 30)
        pos_prefix = jnp.where(
            jnp.arange(Pmax)[None, :] < prefix_len[:, None],
            jnp.arange(Pmax)[None, :], far)                          # [N,Pmax]
        with jax.named_scope("embed"):
            x = self._embed_lookup(params["embed"].astype(dt), tokens)

        from ray_tpu.ops.attention import NEG_INF, _repeat_kv

        def block(carry, layer_and_prefix):
            x = carry
            layer, kp, vp = layer_and_prefix       # kp/vp [N, Pmax, Hkv, D]
            with jax.named_scope("norm_residual"):
                h = rms_norm(x, layer["attn_norm"], eps=cfg.norm_eps)
            with jax.named_scope("attention"):
                q = jnp.einsum("bsd,dhk->bshk", h, layer["wq"].astype(dt))
                k_new = jnp.einsum("bsd,dhk->bshk", h,
                                   layer["wk"].astype(dt))
                v_new = jnp.einsum("bsd,dhk->bshk", h,
                                   layer["wv"].astype(dt))
                q, k_new = self._qk_norm(q, k_new, layer)
                q = apply_rope(q, self._angles, pos_q)
                k_new = apply_rope(k_new, self._angles, pos_q)
                k_all = jnp.concatenate([kp.astype(dt), k_new], axis=1)
                v_all = jnp.concatenate([vp.astype(dt), v_new], axis=1)
                pos_k = jnp.concatenate(
                    [pos_prefix, pos_q], axis=1)                    # [N,P+Tb]
                # per-row positions (prefix_len varies by row) — masked
                # attention inline; padded prefix rows have pos_k=2^30 so
                # the causal test drops them
                kk = _repeat_kv(k_all, cfg.n_heads)
                vv = _repeat_kv(v_all, cfg.n_heads)
                s = jnp.einsum("bqhd,bkhd->bhqk", q, kk,
                               preferred_element_type=jnp.float32)
                s = s * (cfg.head_dim ** -0.5)
                mask = pos_q[:, None, :, None] >= pos_k[:, None, None, :]
                s = jnp.where(mask, s, NEG_INF)
                p = jax.nn.softmax(s, axis=-1)
                o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(dt), vv)
                o = jnp.einsum("bshk,hkd->bsd", o, layer["wo"].astype(dt))
            with jax.named_scope("norm_residual"):
                x = x + o
                h = rms_norm(x, layer["mlp_norm"], eps=cfg.norm_eps)
            down, _ = self._ffn(h, layer)
            with jax.named_scope("norm_residual"):
                return x + down, (k_new, v_new)

        x, (k_out, v_out) = jax.lax.scan(
            block, x, (params["layers"], prefix_k, prefix_v))
        with jax.named_scope("logits"):
            x = rms_norm(x, params["norm_f"], eps=cfg.norm_eps)
            head = (params["embed"].T if cfg.tie_embeddings
                    else params["lm_head"])
            last = jnp.take_along_axis(x, (lengths - 1)[:, None, None],
                                       axis=1)[:, 0]                # [N, D]
            logits = jnp.einsum("bd,dv->bv", last, head.astype(dt))
            return logits.astype(jnp.float32), {"k": k_out, "v": v_out}

    def loss(self, params: Params, tokens: jax.Array,
             targets: jax.Array,
             mask: Optional[jax.Array] = None) -> jax.Array:
        """Mean next-token cross-entropy."""
        # by class: ``PipelinedLlama`` borrows this method
        return LlamaModel._cross_entropy(self.apply(params, tokens), targets,
                                         mask)

    @staticmethod
    def _cross_entropy(logits, targets, mask):
        with jax.named_scope("loss"):
            logp = jax.nn.log_softmax(logits, axis=-1)
            nll = -jnp.take_along_axis(logp, targets[..., None],
                                       axis=-1).squeeze(-1)
            if mask is not None:
                return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1)
            return jnp.mean(nll)
