"""Llama-3 family, TPU-native.

Reference capability: Ray trains Llama via TorchTrainer+FSDP wrappers
(`release/train_tests/benchmark/train_benchmark.py`) and serves it via vLLM
(`python/ray/llm`) — the model itself lives outside the reference tree. Here
it is in-tree and TPU-first:

- params are a pytree of stacked-layer arrays; the transformer stack is a
  single ``lax.scan`` (one compiled block regardless of depth);
- the decoder layer is written ONCE (``LlamaModel._layer``). The four
  programs — training ``apply``, the slot-cache ``forward_step`` (bucket
  prefill, the tests' dense oracle), ``decode_step_paged`` and
  ``prefill_with_prefix`` — are a scan over it each, and differ in the
  one thing they hand it: how this call's K/V are written and the
  earlier ones read (``attend``);
- every param/activation carries logical axis names resolved to the 6-axis
  mesh (dp/fsdp/pp/tp/sp/ep) by ``ray_tpu.parallel.mesh`` rules —
  Megatron-style TP, ZeRO-style fsdp sharding, ring-attention SP all come
  from the same annotations;
- compute dtype bfloat16 (MXU-native). ``init`` and training keep
  params/optimizer f32 and the layer casts each matmul weight at its use;
  the serving engine stores those weights already cast
  (``serving_params``: bf16 ``embed``/``lm_head``/``wq``/``wk``/``wv``/
  ``wo`` and FFN stacks, f32 norms), so its programs read them as they
  are and the same casts lower to nothing;
- ``remat`` on each layer trades FLOPs for HBM (the standard TPU recipe);
- layers may be of two KINDS (``LlamaConfig.layer_types``): full causal
  attention, and SLIDING-WINDOW attention in which a query at ``i`` sees
  a key at ``j`` only if ``i - j < sliding_window``. Both kinds have the
  same parameters, so the stack and the one scan stay; each program's
  scan hands the layer body its kind, and the kind picks the rotary
  table (``rope_scaling``: a kind may turn by a YaRN table) and the
  window that the program's ``attend`` applies. A model with no sliding
  layer has no kinds (``layer_kinds`` is ``None``) and lowers to the
  programs it lowered to before kinds existed;
- a third kind, EVA ATTENTION (``"eva_attention"``, ``ops/eva.py``): an
  exact window of ``eva_window`` positions that RESETS at its every
  multiple, summaries of the chunks (``eva_chunk`` positions) of every
  earlier window, one softmax over both. Its layers hold two more
  leaves, ``eva_phi`` / ``eva_mu`` [L, Hkv, hd] (float32), the vectors a
  chunk is pooled by, and a serving engine keeps their K/V in TWO parts:
  exact rows of the current window and one summary row a chunk of
  everything before it (``init_kv_pools``). An EVA model is EVA in
  every layer (the kind is not mixed with the other two: its cache is
  another shape); ``model.eva`` says so, and no other model's programs
  differ for it;
- a model may carry MORE THAN ONE RESIDUAL STREAM (``LlamaConfig.hc_mult``
  > 1: manifold-constrained hyper-connections, ``ops/mhc.py``). What its
  layer scans carry is then not x [B, T, D] but X [B, T, n D], the streams
  side by side in the lanes; a sublayer reads its input out of them and
  writes its output back through ``_read_streams`` / ``_write_streams``,
  which for one stream ARE ``x`` and ``x + y``. Each sublayer has three more
  leaves (``attn_hc`` / ``mlp_hc``: ``phi``, ``alpha``, ``bias``,
  float32). The streams live and die inside a program: no cache, no
  engine and no slot knows of them.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.ops import mhc
from ray_tpu.ops.attention import (attention, reference_attention,
                                   use_flash_on)
from ray_tpu.ops.eva import (chunk_summaries, eva_attention,
                             merge_softmax_parts, visible_summaries)
from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.paged_attention import (pack_rows, packed_row,
                                         unpack_rows)
from ray_tpu.ops.ring_attention import ring_attention
from ray_tpu.ops.rope import (YarnScaling, apply_rope, apply_rope_of_kind,
                              rope_frequencies, yarn_inv_freq)

Params = Dict[str, Any]

# the kinds of layer, by the names published configs use; a kind's INDEX
# (what the scans hand the layer body) is its place here
LAYER_KINDS = ("full_attention", "sliding_attention")
FULL, SLIDING = 0, 1
# the third kind: a model of it is of it in every layer (module docstring)
EVA_KIND = "eva_attention"
# a full layer's window: past any position, so ``i - j < window`` holds
NO_WINDOW = 2 ** 30
REMASKING = ("low_confidence_dynamic", "low_confidence_static")


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
    # KV-cache decode attention. None (the default): the paged decode
    # program takes the Mosaic kernel on a TPU backend and the XLA
    # reference elsewhere (``LlamaModel.paged_decode_impl``,
    # ops/paged_attention.py); "xla" / "pallas" force a side (tests,
    # chip_smoke.py).
    decode_attention: Optional[str] = None
    # Width of one head; None: ``dim // n_heads``. A model may publish
    # another (q and o are then ``dim x n_heads*head_dim``). Filled in
    # here, so ``dataclasses.replace`` of ``dim`` or ``n_heads`` has to
    # pass ``head_dim=None`` to have it derived again.
    head_dim: Optional[int] = None
    # Each layer's kind, one of ``LAYER_KINDS`` a layer (None: every
    # layer full attention), and the sliding kind's window: a query at
    # ``i`` sees a key at ``j`` only if ``i - j < sliding_window``
    layer_types: Optional[Tuple[str, ...]] = None
    sliding_window: Optional[int] = None
    # (kind, YarnScaling) pairs: the kinds whose rotary table is YaRN's;
    # a kind not named turns by the default table of ``rope_theta``
    rope_scaling: Tuple[Tuple[str, YarnScaling], ...] = ()
    # the EVA kind's window (it resets at every multiple) and chunk (one
    # summary row a chunk of every earlier window)
    eva_window: Optional[int] = None
    eva_chunk: Optional[int] = None
    # RMSNorm scales stored as ``g`` and applied as ``1 + g``
    norm_add_unit_offset: bool = False
    # the residual stream in float32 (each block's input is normed into
    # the compute dtype, its output added in float32)
    fp32_residual: bool = False
    # output heads: head ``p`` of ``lm_head`` [d, heads * vocab] predicts
    # token ``t + 1 + p``. ``apply`` returns every head's logits; the
    # serving programs compute head 0's, the next token's
    num_pred_heads: int = 1
    # residual streams a token (1: the plain residual) and, where there
    # are more, how a sublayer's maps are made (``ops/mhc.py``): Sinkhorn
    # rounds, the term in their denominators, the clamp on ``m_res``
    # before ``exp``
    hc_mult: int = 1
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_res_clamp: Tuple[float, float] = (-30.0, 30.0)
    # GENERATION BY DIFFUSION OVER BLOCKS (``block_length`` > 1; 1: one
    # token a step, left to right). Key j is visible to query i iff
    # ``j // block_length <= i // block_length``, the logits of position
    # i score the token AT i, and a block of ``block_length`` positions
    # starts as ``mask_token_id`` and is filled over at most
    # ``denoising_steps`` passes (``block_unmask``): "low_confidence_
    # dynamic" unmasks what is surer than ``confidence_threshold``, or
    # the pass's quota of the surest; "low_confidence_static" the quota
    # alone. The engine reads these as it reads ``eva`` or the window
    block_length: int = 1
    denoising_steps: int = 1
    remasking: str = "low_confidence_dynamic"
    confidence_threshold: float = 0.9
    mask_token_id: Optional[int] = None

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
        if self.head_dim is None:
            object.__setattr__(self, "head_dim", self.dim // self.n_heads)
        if self.layer_types is not None:
            object.__setattr__(self, "layer_types", tuple(self.layer_types))
            unknown = set(self.layer_types) - set(LAYER_KINDS) - {EVA_KIND}
            if unknown or len(self.layer_types) != self.n_layers:
                raise ValueError(
                    f"layer_types must name one of {LAYER_KINDS} for each "
                    f"of the {self.n_layers} layers, got {self.layer_types}")
            if EVA_KIND in self.layer_types:
                self._check_eva()
            if (LAYER_KINDS[SLIDING] in self.layer_types
                    and not self.sliding_window):
                raise ValueError("a sliding_attention layer needs a "
                                 "sliding_window")
        object.__setattr__(self, "rope_scaling",
                           tuple(map(tuple, self.rope_scaling)))
        if {kind for kind, _ in self.rope_scaling} - set(LAYER_KINDS):
            raise ValueError(
                f"rope_scaling names kinds out of {LAYER_KINDS}, got "
                f"{self.rope_scaling}")
        object.__setattr__(self, "hc_res_clamp",
                           tuple(map(float, self.hc_res_clamp)))
        if self.hc_mult < 1 or self.hc_sinkhorn_iters < 0:
            raise ValueError(
                f"hc_mult ({self.hc_mult}) streams, at least one, and "
                f"hc_sinkhorn_iters ({self.hc_sinkhorn_iters}) rounds, "
                f"none or more")
        if self.block_length > 1:
            self._check_block_diffusion()

    def _check_block_diffusion(self) -> None:
        if self.remasking not in REMASKING:
            raise ValueError(
                f"remasking must be one of {REMASKING}, got "
                f"{self.remasking!r}")
        if (not 1 <= self.denoising_steps <= self.block_length
                or self.mask_token_id is None
                or not 0 <= self.mask_token_id < self.vocab_size):
            raise ValueError(
                f"a block of {self.block_length} positions is filled over 1 "
                f"to {self.block_length} denoising_steps (got "
                f"{self.denoising_steps}) from a mask_token_id among the "
                f"{self.vocab_size} ids (got {self.mask_token_id})")
        if (self.layer_types is not None or self.hc_mult > 1
                or self.num_pred_heads > 1):
            raise ValueError(
                "generation by diffusion over blocks is the plain decoder "
                "layer's: no layer kinds, streams or further heads")

    def _check_eva(self) -> None:
        if set(self.layer_types) != {EVA_KIND}:
            raise ValueError(
                f"{EVA_KIND} layers are not mixed with other kinds (their "
                f"K/V cache is of another shape), got {self.layer_types}")
        w, c = self.eva_window, self.eva_chunk
        if not w or not c or w % c:
            raise ValueError(
                f"an {EVA_KIND} layer needs an eva_window that its "
                f"eva_chunk divides, got window {w}, chunk {c}")
        if self.rope_scaling:
            raise ValueError(f"{EVA_KIND} layers turn by the default table")

    @property
    def eva(self) -> Optional[Tuple[int, int]]:
        """(window, chunk) of a model of EVA layers, None for any other."""
        if self.layer_types and EVA_KIND in self.layer_types:
            return self.eva_window, self.eva_chunk
        return None

    @property
    def hc_map_width(self) -> int:
        """Numbers in a sublayer's three maps a token: ``H_pre`` and
        ``H_post`` [n], ``H_res`` [n, n]."""
        return 2 * self.hc_mult + self.hc_mult ** 2

    def hc_params(self) -> int:
        """One layer's stream maps: ``phi``, ``alpha`` and ``bias`` of its
        two sublayers (none with one stream)."""
        if self.hc_mult == 1:
            return 0
        width = self.hc_map_width
        return 2 * (width * self.hc_mult * self.dim + 3 + width)

    def num_params(self) -> int:
        d, f, v = self.dim, self.ffn_dim, self.vocab_size
        q = self.n_heads * self.head_dim
        kv = self.n_kv_heads * self.head_dim
        per_layer = (d * q + 2 * d * kv + q * d + 3 * d * f + 2 * d
                     + self.hc_params())
        if self.eva:
            per_layer += 2 * kv                       # eva_phi, eva_mu
        heads = 0 if self.tie_embeddings else v * d * self.num_pred_heads
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
    if cfg.eva:
        axes["layers"]["eva_phi"] = (None, "kv_heads", None)
        axes["layers"]["eva_mu"] = (None, "kv_heads", None)
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
        self._use_flash = use_flash_on(mesh)
        self._sp = 1 if mesh is None else mesh.shape.get("sp", 1)
        if cfg.hc_mult > 1 and mesh is not None:
            raise NotImplementedError(
                "more than one residual stream carries no partitioning "
                "rules yet (the streams' axis has no logical name)")
        if self._sp > 1 and cfg.attention_impl == "flash":
            raise ValueError(
                "attention_impl='flash' is a single-device kernel; with an "
                "sp>1 mesh use 'ring' or 'ulysses' context parallelism")
        # ``layer_kinds``: each layer's index into ``LAYER_KINDS``, or
        # None for the plain model (every layer full attention on the
        # default rotary table), which then carries none of what follows
        types = cfg.layer_types or (LAYER_KINDS[FULL],) * cfg.n_layers
        # an EVA model: (window, chunk); its layers are all of that kind,
        # so its scans hand the layer body no kind (None elsewhere)
        self.eva: Optional[Tuple[int, int]] = cfg.eva
        yarn = dict(cfg.rope_scaling)
        if self.eva is not None:
            # no table either (a constant of max_S * D/2 floats, twice, in
            # every program that closes over it: 12.6 MB at 24,576
            # positions): the angles come from the positions, as below
            self.layer_kinds: Optional[Tuple[int, ...]] = None
            self._inv_freq = yarn_inv_freq(cfg.head_dim, cfg.rope_theta,
                                           None)[None]
            self._rope_scales = jnp.ones((1,), jnp.float32)
            self._windows = None
        elif LAYER_KINDS[SLIDING] not in types and not yarn:
            self.layer_kinds = None
            self._angles = rope_frequencies(cfg.head_dim, cfg.max_seq_len,
                                            theta=cfg.rope_theta)
            self._rope_scales = self._windows = None
        else:
            self.layer_kinds = tuple(LAYER_KINDS.index(t) for t in types)
            # inverse frequencies, a factor on cos and sin and a window
            # a KIND: [kinds, hd/2], [kinds], [kinds]; no table (the
            # angles are computed from the positions: ``ops/rope.py``)
            self._inv_freq = jnp.stack([
                yarn_inv_freq(cfg.head_dim, cfg.rope_theta, yarn.get(kind))
                for kind in LAYER_KINDS])
            self._rope_scales = jnp.asarray(
                [yarn[kind].cos_sin_scale if kind in yarn else 1.0
                 for kind in LAYER_KINDS], jnp.float32)
            self._windows = jnp.asarray(
                [NO_WINDOW, cfg.sliding_window or NO_WINDOW], jnp.int32)

    # -- init ---------------------------------------------------------------
    @property
    def _main_layers(self) -> int:
        """Layers in ``params["layers"]``: all, but for a model that
        holds leading layers of another tree apart (``_scan_layers``)."""
        return self.cfg.n_layers

    @staticmethod
    def _dense(key, shape, fan_in):
        return jax.random.normal(key, shape, jnp.float32) * fan_in ** -0.5

    def _norm_scale(self, shape):   # a scale of 1 (stored as 0 under
        return (jnp.zeros if self.cfg.norm_add_unit_offset  # ``1 + g``)
                else jnp.ones)(shape, jnp.float32)

    def _init_attention(self, k, L: int) -> Params:
        """A stack of ``L`` layers' attention weights, keys drawn from
        the iterator ``k``."""
        cfg, dense = self.cfg, self._dense
        d, hd = cfg.dim, cfg.head_dim
        return {"wq": dense(next(k), (L, d, cfg.n_heads, hd), d),
                "wk": dense(next(k), (L, d, cfg.n_kv_heads, hd), d),
                "wv": dense(next(k), (L, d, cfg.n_kv_heads, hd), d),
                "wo": dense(next(k), (L, cfg.n_heads, hd, d), d)}

    def _init_layers(self, k, L: int, ffn_dim: int) -> Params:
        """A stack of ``L`` dense decoder layers, SwiGLU ``ffn_dim``."""
        d, dense = self.cfg.dim, self._dense
        layers = {"attn_norm": self._norm_scale((L, d)),
                  **self._init_attention(k, L),
                  "mlp_norm": self._norm_scale((L, d)),
                  "w_gate": dense(next(k), (L, d, ffn_dim), d),
                  "w_up": dense(next(k), (L, d, ffn_dim), d)}
        down = next(k)
        layers["w_down"] = dense(down, (L, ffn_dim, d), ffn_dim)
        if self.cfg.hc_mult > 1:
            # (keys folded out of one the stack draws anyway: a model with
            # one stream draws what it drew before)
            for i, name in enumerate(("attn_hc", "mlp_hc")):
                layers[name] = self._init_stream_maps(
                    jax.random.fold_in(down, 1 + i), L)
        return layers

    def _init_stream_maps(self, key, L: int) -> Params:
        """One sublayer's map parameters a layer, float32: ``phi`` [L, 2n +
        n^2, n d] (``Phi`` transposed: ``ops/mhc.py``) normal and fan-in
        scaled, so that the dynamic part of ``m`` has unit variance;
        ``alpha`` ones; ``bias`` zero but for 3 on the diagonal of its
        ``H_res`` part (a trained model's values are its own, as a
        router's bias is): ``H_res`` starts near the identity and not at
        it, so the streams stay distinct through the layers."""
        cfg = self.cfg
        n, width = cfg.hc_mult, cfg.hc_map_width
        bias = jnp.concatenate([
            jnp.zeros((2 * n,), jnp.float32),
            3.0 * jnp.eye(n, dtype=jnp.float32).reshape(-1)])
        return {"phi": self._dense(key, (L, width, n * cfg.dim), n * cfg.dim),
                "alpha": jnp.ones((L, 3), jnp.float32),
                "bias": jnp.broadcast_to(bias, (L, width))}

    def init(self, rng: jax.Array) -> Params:
        cfg = self.cfg
        d, hd = cfg.dim, cfg.head_dim
        k = iter(jax.random.split(rng, 16))
        dense, norm_scale = self._dense, self._norm_scale

        L = self._main_layers
        params: Params = {
            "embed": dense(next(k), (cfg.vocab_size, d), d),
            "layers": self._init_layers(k, L, cfg.ffn_dim),
            "norm_f": norm_scale((d,)),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = dense(
                next(k), (d, cfg.num_pred_heads * cfg.vocab_size), d)
        if self.eva is not None:
            # drawn, not zero: a program that drops them must differ
            for name in ("eva_phi", "eva_mu"):
                params["layers"][name] = jax.random.normal(
                    next(k), (L, cfg.n_kv_heads, hd), jnp.float32)
        return params

    # -- the serving path's storage dtype -----------------------------------
    # The leaves of ``params["layers"]`` the layer body casts to the
    # compute dtype at each use (``embed`` and ``lm_head`` beside them);
    # ``MoEModel`` names its expert stacks instead of the dense FFN's.
    MATMUL_LAYER_LEAVES: Tuple[str, ...] = (
        "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")

    def serving_params(self, params: Params) -> Params:
        """``params`` as a serving engine stores them: every weight the
        layer body ``.astype(cfg.dtype)``s is held in ``cfg.dtype``, cast
        ONCE here instead of in every program that reads it (in float32
        those casts were three quarters of a decode step: PERF.md, PR 31).
        The values the matmuls see are bit for bit the same. Everything
        the body uses in float32 stays float32: the norms' scales (and
        ``MoEModel``'s QK-norm scales and router). A leaf already in the
        compute dtype is handed back as it is, so this is idempotent.
        Training keeps float32 master weights and never calls this."""
        dt = self.cfg.dtype

        def cast(a):
            return a if a.dtype == dt else a.astype(dt)

        out = {k: cast(v) if k in ("embed", "lm_head") else v
               for k, v in params.items()}
        for stack in ("layers", "leading_layers"):
            if stack in params:
                out[stack] = {
                    k: cast(v) if k in self.MATMUL_LAYER_LEAVES else v
                    for k, v in params[stack].items()}
        return out

    # The leaves of ``params["layers"]`` a SERVING program's layer scan
    # never hands its body a layer's slice of: the body closes over the
    # stack whole and addresses the layer's part of it (``_whole_leaves``).
    # None here: every dense matmul reads its slice in place (XLA fuses
    # the ``dynamic-slice`` into the dot). ``MoEModel`` names its expert
    # stacks, whose grouped matmuls cannot.
    WHOLE_LAYER_LEAVES: Tuple[str, ...] = ()

    def _whole_leaves(self, layers: Params):
        """What a serving program's layer scan does with
        ``params["layers"]``: ``(xs, stacks)``. ``xs`` is what it slices
        a layer at a time; ``stacks`` is what its body closes over and
        hands ``_layer``: ``WHOLE_LAYER_LEAVES`` with their two leading
        dimensions merged (``[L, E, ...]`` as ``[L*E, ...]``, the same
        bytes), and then ``xs`` carries each layer's number under
        ``"index"``, by which the body addresses the layer's part. A
        model that names no such leaf, or has a mesh (merging L with a
        sharded dimension would re-shard the stack), gets ``(layers,
        None)``: its scans are what they were."""
        names = () if self.mesh is not None else self.WHOLE_LAYER_LEAVES
        if not names:
            return layers, None
        xs = {k: v for k, v in layers.items() if k not in names}
        # the layer's number IN ITS STACK (a model with leading layers of
        # another tree holds them apart: ``_scan_layers``)
        xs["index"] = jnp.arange(len(layers[names[0]]), dtype=jnp.int32)
        return xs, {k: layers[k].reshape((-1,) + layers[k].shape[2:])
                    for k in names}

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

    def _kinds_xs(self) -> Optional[jax.Array]:
        """What a program's layer scan hands the layer body beside the
        layer's parameters: its kind's index [L], or None (no leaf: the
        plain model's scans are what they were)."""
        if self.layer_kinds is None:
            return None
        return jnp.asarray(self.layer_kinds, jnp.int32)

    def _window(self, kind):
        """The window of a layer of ``kind`` (a traced index), or None
        for the plain model."""
        return None if kind is None else self._windows[kind]

    def _mask_positions(self, positions):
        """The position a prefill takes a query's causal mask at: its
        own, or under ``block_length`` > 1 its block's last (every row
        of a block sees the whole block, and RoPE still turns by the true
        position). The one-token models get back what they handed in."""
        n = self.cfg.block_length
        if n == 1:
            return positions
        return positions // n * n + (n - 1)

    def _attention(self, q, k, v, positions, window=None, layer=None):
        """The training program's attention over this call's own rows
        (``layer``: for a model whose rows need its weights to be read)."""
        k, v = (unpack_rows(a, self.cfg.head_dim) for a in (k, v))
        if window is not None:
            # a layer of a model with kinds (training and the tests'
            # oracle; no cell trains one): the masked reference
            if self._sp > 1:
                raise NotImplementedError(
                    "sliding-window layers are not supported with sp>1")
            return reference_attention(q, k, v, positions_q=positions,
                                       positions_k=positions, window=window)
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
        # sp==1: "flash" forces ``flash_attention`` (the Pallas kernels,
        # interpreted off a TPU, with blocks by shape; the scan where a
        # head's sequence outgrows VMEM); "xla" forces the blockwise
        # online-softmax scan; otherwise the dispatcher selects by
        # platform and shape.
        cfg = self.cfg
        if cfg.attention_impl == "flash" and positions is None:
            from ray_tpu.ops.attention import flash_attention
            return flash_attention(q, k, v, True)
        if cfg.attention_impl == "xla" and positions is None:
            from ray_tpu.ops.attention import blockwise_attention
            return blockwise_attention(q, k, v, causal=True)
        # under a mesh the reference: a Mosaic call carries no
        # partitioning rule (``paged_decode_impl`` decides the same way)
        return attention(q, k, v, causal=True, positions_q=positions,
                         positions_k=positions,
                         use_flash=self._use_flash)

    # -- the decoder layer: ONE body (``_layer``) for every program. A
    # MODEL of the family differs in ``_qk_norm`` and ``_ffn`` (``MoEModel``
    # overrides both and nothing else), a PROGRAM in the ``attend`` it
    # hands the layer ------------------------------------------------------
    def _qk_norm(self, q, k, layer: Params):
        """Between the q/k projections and RoPE. q [B, T, H, hd], k
        [B, T, Hkv, hd]; the dense layer does nothing here."""
        return q, k

    def _ffn(self, h, layer: Params, live=None, constrain: bool = False,
             stacks: Optional[Params] = None):
        """The feed-forward half: h [B, T, D] (already normed) ->
        ``(out [B, T, D], extra)``. ``extra`` is whatever the model
        wants carried out of the layer scan (``None`` here); ``live``
        [B] bool marks the rows it should count (all, if ``None``);
        ``constrain`` (the training program) pins the inner activation's
        sharding to the mesh; ``stacks`` is ``_whole_leaves``' (always
        ``None`` here: the dense model names no whole leaf)."""
        dt = self.cfg.dtype
        with jax.named_scope("mlp"):
            gate = jnp.einsum("bsd,df->bsf", h, layer["w_gate"].astype(dt))
            up = jnp.einsum("bsd,df->bsf", h, layer["w_up"].astype(dt))
            ff = jax.nn.silu(gate) * up
            if constrain:
                ff = self._constrain(ff, "batch", "seq", "mlp")
            down = jnp.einsum("bsf,fd->bsd", ff, layer["w_down"].astype(dt))
        return down, None

    def _norm(self, x, weight):
        """RMSNorm of a block's input: the stored scale (``1 + g`` under
        ``norm_add_unit_offset``), and out of a float32 residual stream
        into the compute dtype."""
        cfg = self.cfg
        if cfg.norm_add_unit_offset:
            weight = 1.0 + weight
        h = rms_norm(x, weight, eps=cfg.norm_eps)
        return h.astype(cfg.dtype) if cfg.fp32_residual else h

    def _rope(self, x, positions, kind):
        if self.eva is not None:
            kind = 0                   # its one row of frequencies
        elif kind is None:
            return apply_rope(x, self._angles, positions)
        return apply_rope_of_kind(x, self._inv_freq, self._rope_scales,
                                  kind, positions)

    def _qkv(self, h, layer: Params, positions, kind, pin):
        """The layer's projections of h [B, T, D] (normed), up to what
        ``attend`` takes: q [B, T, H, hd] and this call's K/V ROWS as the
        model's cache holds them, here k/v [B, T, Hkv, hd], q and k
        turned by RoPE. A model whose cache rows are something else
        (``MLAModel``: a latent row and one rotary key part) overrides
        this with ``kv_row_shapes``, ``_attend_rows`` and
        ``_attend_pages``; ``pin`` is ``_layer``'s. Its ``q`` may be a
        TREE of per-token arrays ``[B, T, ...]`` (an indexer's query
        beside the attention's): the layer hands it to ``attend`` as it
        is and the decode step takes row 0 of every leaf."""
        dt = self.cfg.dtype
        q = jnp.einsum("bsd,dhk->bshk", h, layer["wq"].astype(dt))
        k = jnp.einsum("bsd,dhk->bshk", h, layer["wk"].astype(dt))
        v = jnp.einsum("bsd,dhk->bshk", h, layer["wv"].astype(dt))
        q, k = self._qk_norm(q, k, layer)
        q = pin(q, "batch", "seq", "heads", None)
        q = self._rope(q, positions, kind)
        k = self._rope(k, positions, kind)
        if self.kv_lane_pack > 1:       # narrow heads: the cache's rows
            k, v = pack_rows(k), pack_rows(v)
        return q, k, v

    def _attend_rows(self, q, k, v, layer: Params, positions_q, positions_k,
                     window=None):
        """A prefill's attention of q [B, T, H, hd] over DENSE rows k, v
        [B, S, ...] (a slot cache, or a gathered prefix and the call's
        own rows) at ``positions_k``: -> o [B, T, H, hd]. (Rows of narrow
        heads, packed, are viewed back as heads.)"""
        hd = self.cfg.head_dim
        return reference_attention(q, unpack_rows(k, hd), unpack_rows(v, hd),
                                   positions_q=positions_q,
                                   positions_k=positions_k, window=window)

    def _attend_pages(self, q, k_pool, v_pool, layer: Params, block_tables,
                      lengths, **window):
        """A decode step's attention of q [B, H, hd] over the slots'
        pages of the pools (``ops.paged_attention.paged_decode_attention``
        and its keywords): -> o [B, H, hd]."""
        from ray_tpu.ops.paged_attention import paged_decode_attention
        return paged_decode_attention(q, k_pool, v_pool, block_tables,
                                      lengths, **window)

    def _layer(self, x, layer: Params, positions, attend, live=None,
               constrain: bool = False, kind=None,
               stacks: Optional[Params] = None):
        """One decoder layer. x [B, T, D]; ``positions`` what RoPE turns
        q and k by (``None``: 0..T-1), by the table of the layer's
        ``kind`` (its index, traced; None in the plain model);
        ``attend(q, k, v) -> (o, kv)``
        with q/o [B, T, H, hd] and k/v ``_qkv``'s rows ([B, T, Hkv, hd]),
        the calling program's own: it writes this call's K/V where it keeps
        them, reads the earlier ones, and hands back as ``kv`` whatever
        the program's layer scan carries on or stacks up. ``live`` and
        ``stacks`` are ``_ffn``'s; ``constrain`` (the training program
        alone) pins the activations' sharding to the mesh.
        -> (x, ``kv``, the layer's ``_ffn`` extra)."""
        cfg = self.cfg
        dt = cfg.dtype

        def pin(a, *names):
            return self._constrain(a, *names) if constrain else a

        with jax.named_scope("norm_residual"):
            h, maps = self._read_streams(x, layer.get("attn_hc"))
            h = self._norm(h, layer["attn_norm"])
        with jax.named_scope("attention"):
            q, k, v = self._qkv(h, layer, positions, kind, pin)
        o, kv = attend(q, k, v)
        with jax.named_scope("attention"):
            o = jnp.einsum("bshk,hkd->bsd", o, layer["wo"].astype(dt))
        with jax.named_scope("norm_residual"):
            x = self._write_streams(x, pin(o, "batch", "seq", "embed"), maps)
            h, maps = self._read_streams(x, layer.get("mlp_hc"))
            h = self._norm(h, layer["mlp_norm"])
        down, extra = self._ffn(h, layer, live, constrain, stacks)
        with jax.named_scope("norm_residual"):
            return (self._write_streams(
                x, pin(down, "batch", "seq", "embed"), maps), kv, extra)

    # -- the residual path: one vector a token, or ``hc_mult`` streams ------
    def _read_streams(self, x, hc: Optional[Params]):
        """A sublayer's input out of the residual: ``(h, maps)``. The
        plain residual IS its sublayers' input (``maps`` None). With
        streams (``hc``: the sublayer's ``phi``, ``alpha``, ``bias``) x is
        X [B, T, n D]: the sublayer's three maps are made from the streams
        as they stand, ``h = sum_i H_pre[i] X[i]``, and ``(H_post, H_res)``
        go on to ``_write_streams``."""
        if hc is None:
            return x, None
        cfg = self.cfg
        with jax.named_scope("mhc_maps"):
            h_pre, h_post, h_res = mhc.stream_maps(
                x, cfg.hc_mult, hc["phi"], hc["alpha"], hc["bias"],
                iters=cfg.hc_sinkhorn_iters, eps=cfg.hc_eps,
                norm_eps=cfg.norm_eps, clamp=cfg.hc_res_clamp)
        with jax.named_scope("mhc_mix"):
            return mhc.mix_in(x, h_pre), (h_post, h_res)

    def _write_streams(self, x, y, maps):
        """A sublayer's output ``y`` back into the residual: ``x + y``,
        or, with streams, ``X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y``
        by the maps its ``_read_streams`` made."""
        if maps is None:
            return x + y
        with jax.named_scope("mhc_mix"):
            return mhc.mix_out(x, y, *maps)

    def _embed(self, params: Params, tokens: jax.Array,
               constrain: bool = False) -> jax.Array:
        """tokens [B, T] -> x [B, T, D] in the compute dtype; for a model
        with streams X [B, T, n D]."""
        with jax.named_scope("embed"):
            x = self._embed_lookup(params["embed"].astype(self.cfg.dtype),
                                   tokens)
            if self.cfg.fp32_residual:
                x = x.astype(jnp.float32)
            if self.cfg.hc_mult > 1:
                # every stream starts as the embedding (Hyper-Connections,
                # Alg. 2)
                return jnp.tile(x, (1, 1, self.cfg.hc_mult))
            return (self._constrain(x, "batch", "seq", "embed") if constrain
                    else x)

    def _head(self, params: Params, x: jax.Array,
              last: Optional[jax.Array] = None,
              constrain: bool = False, every_head: bool = False
              ) -> jax.Array:
        """Final norm and LM head: x [B, T, D] (or a model's streams [B, T,
        n D]: they leave as their sum) -> f32 logits [B, T, V];
        with ``last`` [B], of row ``last[b]`` of each sequence alone
        ([B, 1, V]: the other rows never meet the head). A model with
        ``num_pred_heads`` > 1 gives head 0's V logits, the next
        token's, unless ``every_head`` (``apply``: [B, T, heads * V])."""
        cfg = self.cfg
        if cfg.hc_mult > 1:
            with jax.named_scope("mhc_mix"):
                x = mhc.sum_streams(x, cfg.hc_mult)
        with jax.named_scope("logits"):
            x = self._norm(x, params["norm_f"])
            if last is not None:
                x = jnp.take_along_axis(x, last[:, None, None], axis=1)
            head = (params["embed"].T if cfg.tie_embeddings
                    else params["lm_head"])
            if cfg.num_pred_heads > 1 and not every_head:
                head = head[:, :cfg.vocab_size]
            logits = jnp.einsum("bsd,dv->bsv", x, head.astype(cfg.dtype))
            if constrain:
                logits = self._constrain(logits, "batch", "seq", "vocab")
            return logits.astype(jnp.float32)

    def apply(self, params: Params, tokens: jax.Array,
              positions: Optional[jax.Array] = None) -> jax.Array:
        """tokens [B, S] int32 -> logits [B, S, V] (f32)."""
        return self._apply_with_extras(params, tokens, positions)[0]

    def _apply_with_extras(self, params: Params, tokens: jax.Array,
                           positions: Optional[jax.Array] = None):
        """``apply`` and, stacked over layers, each layer's ``_ffn``
        extra (``None`` for the dense layer)."""
        cfg = self.cfg
        if cfg.block_length > 1:
            raise NotImplementedError(
                "a block-diffusion model is trained on a doubled sequence "
                "(noised and clean) under a mask of its own; only its "
                "serving programs are built")

        def layer_fn(x, layer_and_kind, stacks=None):
            layer, kind = layer_and_kind

            def attend(q, k, v):        # training keeps no K/V
                if self.eva is not None:
                    if positions is not None or self._sp > 1:
                        raise NotImplementedError(
                            "EVA layers take contiguous positions 0..T-1 "
                            "on one sequence shard")
                    return self._eva_over_rows(
                        q, k, v, layer, jnp.arange(q.shape[1]))[0], None
                with jax.named_scope("attention"):
                    return self._attention(q, k, v, positions,
                                           self._window(kind), layer), None

            x, _, extra = self._layer(x, layer, positions, attend,
                                      constrain=True, kind=kind)
            return x, extra

        if cfg.remat:
            if cfg.remat_policy == "dots":
                layer_fn = jax.checkpoint(
                    layer_fn, policy=jax.checkpoint_policies.dots_saveable)
            else:
                layer_fn = jax.checkpoint(layer_fn)
        # training slices the layer's weights (``stacks`` None: moe.py)
        x, extras = self._scan_layers(
            layer_fn, self._embed(params, tokens, constrain=True), params,
            (params["layers"], None), (self._kinds_xs(),))
        return self._head(params, x, constrain=True, every_head=True), extras

    def _eva_over_rows(self, q, k, v, layer: Params, positions_q):
        """EVA attention of queries at ``positions_q`` over DENSE rows
        k, v [B, S, Hkv, hd] that stand at positions 0..S-1 (a whole
        sequence, a slot cache): the rows' whole chunks pooled as they
        stand (a query sees only those of the windows before its own,
        whose rows are all in; a tail chunk is summarised by no one),
        then the dense masked form. -> (o, k~, v~)."""
        window, chunk = self.eva
        S = k.shape[1]
        whole = S // chunk * chunk
        ks, vs = chunk_summaries(k[:, :whole], v[:, :whole],
                                 layer["eva_phi"], layer["eva_mu"], chunk)
        o = eva_attention(q, k, v, ks, vs, positions_q, jnp.arange(S),
                          jnp.arange(whole // chunk), window=window,
                          chunk=chunk)
        return o, ks, vs

    def _scan_layers(self, step, carry, params: Params, main, xs: tuple,
                     join: bool = False):
        """``jax.lax.scan`` of a program's layer body over the model's
        layers, A STACK AFTER ANOTHER: ``step(carry, (layer, *xs), stacks)
        -> (carry, ys)``. ``main`` is ``(layers, stacks)``: what the scan
        slices of ``params["layers"]`` and what its body closes over
        whole (``_whole_leaves``; training hands ``(params["layers"],
        None)``); ``xs`` what the program hands each layer beside its
        parameters (arrays over ALL the layers, or None).

        A model has one stack and this is one scan. A model whose first
        ``cfg.leading_layers`` layers are of ANOTHER PARAMETER TREE (dense
        layers before expert layers) holds them as a stack of their own,
        ``params["leading_layers"]``, scanned first by the same body: the
        one decoder layer asks the tree what it holds (``_ffn``). The K/V
        rows, bases and kinds in ``xs`` count through both. ``ys`` are
        the main stack's; with ``join`` both stacks', in layer order
        (a prefill's K/V rows)."""
        layers, stacks = main
        lead, ys_lead = params.get("leading_layers"), None
        if lead is not None:
            n = len(lead["attn_norm"])
            carry, ys_lead = jax.lax.scan(
                lambda c, x: step(c, x, None), carry,
                (lead, *jax.tree.map(lambda a: a[:n], xs)))
            xs = jax.tree.map(lambda a: a[n:], xs)
        carry, ys = jax.lax.scan(lambda c, x: step(c, x, stacks), carry,
                                 (layers, *xs))
        if join and ys_lead is not None:
            ys = jax.tree.map(lambda a, b: jnp.concatenate([a, b]),
                              ys_lead, ys)
        return carry, ys

    # -- KV-cache inference path (serving; BASELINE.md config 5) ----------
    @property
    def kv_lane_pack(self) -> int:
        """K/V heads ONE row of the cache holds: as many as fill the
        paged kernel's 128 lanes (``ops.paged_attention.packed_row``: 2
        at ``head_dim`` 64; 1 where no number of them fills the lanes:
        the kernel does not lower there), so that a pool of
        narrow heads lies in the rows the kernel reads and neither pads
        its lanes in HBM nor is re-tiled a layer a step. ``_qkv`` hands
        every program such rows (the same numbers in the same order: a
        reshape), the programs move them whatever they hold, and
        ``_attend_rows`` / ``_attention`` view them back as heads. 1 at 128 lanes and more,
        under a mesh (the kernel does not run there and the heads' axis
        is sharded), for an EVA model (its two parts are read as heads
        throughout) and for a model whose rows are its own
        (``MLAModel``)."""
        if (self.mesh is not None or self.eva is not None
                or type(self)._attend_pages is not LlamaModel._attend_pages):
            return 1
        cfg = self.cfg
        return cfg.n_kv_heads // packed_row(cfg.n_kv_heads, cfg.head_dim)[0]

    def kv_row_shapes(self) -> Tuple[Tuple[int, ...], Tuple[int, ...]]:
        """One position's row in each of the cache's two parts, ``"k"``
        and ``"v"``: what follows ``[L, B, S]`` in a slot cache and
        ``[L, NB, bs]`` in a pool: ``(Hkv, D)``, or narrow heads packed
        ``kv_lane_pack`` to a row. The engine and the harness move rows by
        these names whatever they hold (``llm/engine.py:_insert_impl``)."""
        cfg = self.cfg
        row = (cfg.n_kv_heads // self.kv_lane_pack,
               self.kv_lane_pack * cfg.head_dim)
        return row, row

    @property
    def kv_dtype(self):
        """What the cache holds a row in: the compute dtype, but for a
        model that packs its rows into words (``MLAModel.word_rows``)."""
        return self.cfg.dtype

    def _kv_zeros(self, *leading: int) -> Params:
        return {name: jnp.zeros(leading + row, self.kv_dtype)
                for name, row in zip(("k", "v"), self.kv_row_shapes())}

    def init_kv_cache(self, batch: int, max_seq: int) -> Params:
        """Slot-major cache: [L, B, S, Hkv, D] per k/v, bf16 in HBM."""
        return self._kv_zeros(self.cfg.n_layers, batch, max_seq)

    def forward_step(self, params: Params, tokens: jax.Array,
                     cache: Params, offsets: jax.Array
                     ) -> Tuple[jax.Array, Params]:
        """Unified prefill/decode step with KV cache.

        tokens  [B, T] — T = padded prompt length (prefill) or 1 (decode)
        offsets [B]    — how many tokens each slot has already cached
        Returns (logits [B, T, V], updated cache). Static shapes: the same
        jit specialization serves every request of a given (B, T, S).
        """
        B, T = tokens.shape
        S = cache["k"].shape[2]
        q_pos = offsets[:, None] + jnp.arange(T)[None, :]        # [B, T]
        mask_pos = self._mask_positions(q_pos)
        batch_idx = jnp.arange(B)[:, None]

        def step(x, layer_and_cache, stacks):
            layer, k_cache, v_cache, kind = layer_and_cache

            def attend(q, k_new, v_new):
                with jax.named_scope("kv_update"):
                    # scatter new k/v into the cache at each slot's write
                    # offsets
                    k_all = k_cache.at[batch_idx, q_pos].set(k_new)
                    v_all = v_cache.at[batch_idx, q_pos].set(v_new)
                if self.eva is not None:
                    o, ks, vs = self._eva_over_rows(q, k_all, v_all, layer,
                                                    q_pos)
                    return o, (k_all, v_all, ks, vs)
                with jax.named_scope("attention"):
                    # attend over cache positions <= own position
                    o = self._attend_rows(q, k_all, v_all, layer, mask_pos,
                                          jnp.arange(S), self._window(kind))
                return o, (k_all, v_all)

            x, kv, _ = self._layer(x, layer, q_pos, attend, kind=kind,
                                   stacks=stacks)
            return x, kv

        main = self._whole_leaves(params["layers"])
        x, kv = self._scan_layers(
            step, self._embed(params, tokens), params, main,
            (cache["k"], cache["v"], self._kinds_xs()), join=True)
        return self._head(params, x), self._kv_dict(kv)

    @staticmethod
    def _kv_dict(kv) -> Params:
        """What a prefill's layer scan stacked up, by name: k and v, and
        for an EVA model the chunk summaries of the same rows."""
        return dict(zip(("k", "v", "sk", "sv"), kv))

    # -- paged KV-cache path (llm/engine.py + llm/paged_cache.py) ---------
    def init_kv_pool(self, num_blocks: int, block_size: int) -> Params:
        """Block-pool cache: k/v [L, num_blocks, block_size, Hkv, D],
        bf16 in HBM, shared by every slot via per-slot block tables."""
        return self._kv_zeros(self.cfg.n_layers, num_blocks, block_size)

    def init_kv_pools(self, num_blocks: Tuple[int, ...],
                      block_size: int) -> Params:
        """A pool a KIND, for a model with kinds: a layer of kind ``i``
        gets ``num_blocks[i]`` blocks of its own, numbered from 0 by
        that kind's block tables (a sliding layer holds a window's
        worth a slot, not ``max_seq``: ``llm/engine.py``). On the device
        the pools are ONE stack, k/v ``[sum over layers, block_size,
        Hkv, D]``, the layers' windows one after another in layer
        order, with ``"bases"`` [L] int32, where each layer's begins:
        what ``decode_step_paged`` carries through its scan anyway (the
        uniform pool is the stack in which every window has NB blocks).
        """
        cfg = self.cfg
        if self.eva is not None:
            # an EVA model: ``num_blocks`` = (summary, exact). Every
            # layer holds both PARTS: exact rows of a slot's current
            # window in ``k``/``v``, one summary row a chunk of all
            # before it in ``sk``/``sv``, blocks of ``block_size`` rows
            # each, each part numbered from 0 by its own block table
            shape = (block_size, cfg.n_kv_heads, cfg.head_dim)
            summary, exact = ((cfg.n_layers, n) + shape for n in num_blocks)
            return {"k": jnp.zeros(exact, cfg.dtype),
                    "v": jnp.zeros(exact, cfg.dtype),
                    "sk": jnp.zeros(summary, cfg.dtype),
                    "sv": jnp.zeros(summary, cfg.dtype)}
        per_layer = [num_blocks[kind] for kind in self.layer_kinds]
        bases = [sum(per_layer[:i]) for i in range(len(per_layer))]
        return dict(self._kv_zeros(sum(per_layer), block_size),
                    bases=jnp.asarray(bases, jnp.int32))

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

    def paged_run_blocks(self, block_size: int) -> int:
        """Blocks of ``block_size`` rows the paged kernel should copy as
        one page (``ops.paged_attention.run_blocks``: more than 1 where
        a block of this model's K/V heads is a few KB), for an engine
        that lays the blocks so and hands the number back as ``run``. 1
        for a model whose pages another kernel reads, and for an EVA
        model, whose exact blocks go a window at a time."""
        from ray_tpu.ops.paged_attention import run_blocks
        if (self.eva is not None
                or type(self)._attend_pages is not LlamaModel._attend_pages):
            return 1
        return run_blocks(block_size, self.cfg.n_kv_heads, self.cfg.head_dim,
                          jnp.dtype(self.kv_dtype).itemsize)

    def decode_step_paged(self, params: Params, tokens: jax.Array,
                          pool: Params, block_tables: jax.Array,
                          offsets: jax.Array, run: int = 1
                          ) -> Tuple[jax.Array, Params]:
        """One decode step for every slot against the block pool.

        tokens [B] int32 (each slot's last sampled token)
        pool   k/v [L, NB, bs, Hkv, D], or a model with kinds' pool a
               kind (``init_kv_pools``)
        block_tables [B, MAXB] int32 physical ids (logical order); for
               a model with kinds also [kinds, B, MAXB], a table a kind
               (given one table, every kind reads it)
        offsets [B] tokens already cached per slot
        run    blocks the tables lay in aligned, contiguous runs of,
               which the kernel then copies as one page
               (``paged_decode_attention``); a sliding kind's table
               lays none, whatever ``run`` says
        Returns (logits [B, V], updated pool). Slots whose table rows
        point at garbage simply compute garbage that the engine masks.
        """
        return self.decode_step_paged_counted(
            params, tokens, pool, block_tables, offsets, run=run)[:2]

    def ffn_load_shape(self) -> Optional[Tuple[int, int]]:
        """Shape of the per-layer counts ``decode_step_paged_counted``
        reports third (under ``"load"``), or ``None`` where the FFN
        counts nothing, as the dense one."""
        return None

    def grouped_matmul_plan(self, tokens: int) -> Dict[str, str]:
        """What implements the FFN's grouped matmuls in a program of
        ``tokens`` tokens, for an engine's ``stats``: empty strings for
        the dense FFN, which has none (``MoEModel`` answers)."""
        return {"moe_grouped_impl": "", "moe_gmm_tiling_gate": "",
                "moe_gmm_tiling_up": "", "moe_gmm_tiling_down": ""}

    def sparse_decode_plan(self) -> Dict:
        """What a model whose decode attention reads a SELECTION of a
        slot's rows reports to an engine's ``stats`` (``MLAModel`` with
        an indexer answers): the rows a query keeps, the bytes of a
        row's index key, and what implements the index scores and the
        selection. 0 and empty strings for every other model."""
        return {"index_topk": 0, "kv_index_row_bytes": 0,
                "decode_indexer_impl": "", "decode_select_impl": ""}

    def decode_step_paged_counted(self, params: Params, tokens: jax.Array,
                                  pool: Params, block_tables: jax.Array,
                                  offsets: jax.Array,
                                  live: Optional[jax.Array] = None,
                                  run: int = 1):
        """``decode_step_paged`` and, third, each layer's ``_ffn`` extra
        stacked over layers (``None`` for the dense layer), counted over
        the slots ``live`` [B] bool marks.

        The pool lives through the step WHOLE: the layer scan carries
        the stack ``[L*NB, bs, Hkv, D]`` (the pool with its two leading
        dimensions merged, the same bytes) and never a layer's slice of
        it, so a caller that donates the pool gets it back written in
        place. Layer ``l``'s page ``p`` is page ``base[l] + p`` of the
        stack, ``base[l] = l*NB``: the layer writes its B rows at
        ``(base + dest_block, dest_off)`` and attention, kernel and
        reference alike, reads through the block table plus ``base``.
        (Handed to the scan as ``xs``/``ys`` the pool cost two whole
        copies a step and a slice out and back a layer: PERF.md, PR 27.)

        A model with KINDS runs the same body. Its scan hands each layer
        its kind beside its base, and the kind picks the table the layer
        writes and reads through and its first visible position
        (``offset + 1 - window``, 0 in a full layer): the kernel starts
        at that position's page and reads nothing older. Its pool may be
        the uniform one (one table: a sliding layer then keeps, and
        skips, the rows behind its window) or a pool a kind
        (``init_kv_pools``: the stack as it comes, ``bases`` beside it)
        with a table a kind.

        ``run`` > 1 (``decode_step_paged``): the tables lay blocks in
        runs and the kernel's page is a run. Where there are kinds only
        the FULL kind's table lays them so (a sliding layer's blocks go
        one at a time behind its window), and the layer's kind picks the
        call: the kernel over runs, or over single blocks.

        A model's ``WHOLE_LAYER_LEAVES`` (an expert model's stacks) live
        through the step whole too: the body closes over them and
        ``_ffn`` reads the layer's part in place (``_whole_leaves``), as
        in ``forward_step`` and ``prefill_with_prefix``.

        An EVA model has a body of its own (``_decode_step_eva``)."""
        if self.eva is not None:
            return self._decode_step_eva(params, tokens, pool, block_tables,
                                         offsets, live)
        if "bases" in pool:
            NB, bs = None, pool["k"].shape[1]
        else:
            L, NB, bs = pool["k"].shape[:3]

        def whole(a):           # [L, NB, bs, ...] as the stack [L*NB, ...]
            # (the size spelled out: a row may be empty, ``MLAModel``'s "v")
            return a if NB is None else a.reshape((L * NB,) + a.shape[2:])

        kinds = self._kinds_xs()
        if kinds is not None and block_tables.ndim == 2:
            block_tables = jnp.broadcast_to(
                block_tables, (len(LAYER_KINDS),) + block_tables.shape)
        # [B], or [kinds, B] where there are kinds
        at_block = (offsets // bs)[:, None]
        dest_block = jnp.take_along_axis(
            block_tables, at_block if kinds is None else at_block[None],
            axis=-1)[..., 0]
        dest_off = offsets % bs
        lengths = offsets + 1
        starts = None if kinds is None else jnp.maximum(
            lengths - self._windows[:, None], 0)
        q_pos = offsets[:, None]                                   # [B, 1]
        impl = self.paged_decode_impl()

        def step(carry, layer_base_kind, stacks):
            x, k_pool, v_pool = carry
            # ``base``: where this layer's blocks start in the stack
            layer, base, kind = layer_base_kind

            def own(a):       # the layer's kind's row of a per-kind array
                return a if kind is None else a[kind]

            def attend(q, k_new, v_new):
                with jax.named_scope("kv_update"):
                    # each slot writes its own private tail block (refcount
                    # 1 — shared prefix blocks are never write targets)
                    k_all = k_pool.at[base + own(dest_block), dest_off].set(
                        k_new[:, 0])
                    v_all = v_pool.at[base + own(dest_block), dest_off].set(
                        v_new[:, 0])
                def pages(blocks):       # a page: so many blocks
                    return self._attend_pages(
                        jax.tree.map(lambda a: a[:, 0], q), k_all, v_all,
                        layer, own(block_tables),
                        lengths, impl=impl, starts=own(starts),
                        first_block=base, num_blocks=NB, run=blocks)

                with jax.named_scope("attention"):
                    if run > 1 and kind is not None:
                        o = jax.lax.cond(kind == FULL, lambda: pages(run),
                                         lambda: pages(1))
                    else:
                        o = pages(run)
                return o[:, None], (k_all, v_all)

            x, (k_pool, v_pool), extra = self._layer(
                x, layer, q_pos, attend, live=live, kind=kind, stacks=stacks)
            return (x, k_pool, v_pool), extra

        main = self._whole_leaves(params["layers"])
        (x, k_out, v_out), extras = self._scan_layers(
            step,
            (self._embed(params, tokens[:, None]),                 # [B,1,D]
             whole(pool["k"]), whole(pool["v"])),
            params, main,
            (pool["bases"] if NB is None
             else jnp.arange(L, dtype=jnp.int32) * NB,
             kinds))
        pool = dict(pool, k=k_out.reshape(pool["k"].shape),
                    v=v_out.reshape(pool["v"].shape))
        return self._head(params, x)[:, 0], pool, extras

    def block_step_paged_counted(self, params: Params, tokens: jax.Array,
                                 pool: Params, block_tables: jax.Array,
                                 offsets: jax.Array,
                                 live: Optional[jax.Array] = None,
                                 behind: Optional[Tuple[jax.Array, ...]]
                                 = None, run: int = 1):
        """One pass over every slot's CURRENT BLOCK against the block
        pool (a model with ``block_length`` > 1): tokens [B, n] at
        positions ``offsets .. offsets + n - 1`` (``offsets`` [B]
        multiples of n; the page size is one too, so a block lies in ONE
        page). The block's K/V rows are written there, over whatever
        the pass before left (a denoising pass's rows stay only until
        the next pass; those of a pass over the CLEAN block are the ones
        later blocks read), and every one of its n x H query rows
        attends the slot's ``offsets + n`` rows: the whole block and all
        before it. -> (logits [B, n, V] of the block's OWN positions,
        the pool, the layers' ``_ffn`` extras), the pool carried whole
        and written in place as ``decode_step_paged_counted`` carries it.

        ``behind`` = (tokens [C, n], slots [C] int32, wanted [C] bool),
        C <= B: the same pass carries, for the slots that ``slots`` names
        and ``wanted`` marks, THE BLOCK BEHIND as well, clean, at
        ``offsets - n .. offsets - 1``: its rows are written there to
        stay (its commit) and its query rows see the rows before
        ``offsets``, their own among them, so the slot's current block
        reads the block behind as this very pass wrote it. The call then
        runs C + B rows of n, the blocks behind first: through the
        projections, the norms and the FFN as one batch, through the
        paged attention as C + B grid rows of ONE call over the slots'
        tables (``offsets`` rows for a block behind, ``offsets + n`` for
        a current one). A row not ``wanted`` is DEAD: it writes nothing,
        sees nothing (length 0), counts for nothing in ``live`` and is
        dropped with every other row behind before the head, which runs
        on the B current blocks alone. The extras are the C + B rows'.
        Without ``behind`` this is the program it was.

        The paged attention is the decode step's, kernel and reference
        alike: its head axis carries the block's rows (q [B, n, H, hd]
        laid KV-head-major as [B, Hkv * n * H/Hkv, hd]: all rows of a
        slot's block see one length); ``run`` is the decode step's."""
        cfg = self.cfg
        if type(self)._attend_pages is not LlamaModel._attend_pages:
            raise NotImplementedError(
                "a block's rows ride the head axis of the plain paged "
                "attention; this model's pages hold something else")
        L, NB, bs = pool["k"].shape[:3]
        B, n = tokens.shape
        if bs % n:
            raise ValueError(
                f"a page of {bs} rows does not hold whole blocks of {n}")

        def whole(a):
            return a.reshape((L * NB,) + a.shape[2:])

        lengths = offsets + n
        # a scatter's default; "drop" where dead rows point past the stack
        mode = None
        if behind is not None:
            tokens_behind, slots, wanted = behind

            def both(a, b):
                return jnp.concatenate([a, b])

            tokens = both(tokens_behind, tokens)
            lengths = both(jnp.where(wanted, offsets[slots], 0), lengths)
            # (a dead row's positions: anywhere RoPE has an angle for)
            offsets = both(jnp.maximum(offsets[slots] - n, 0), offsets)
            block_tables = both(block_tables[slots], block_tables)
            every = jnp.ones((B,), bool)
            live = both(wanted if live is None else wanted & live[slots],
                        every if live is None else live)
            mode = "drop"
        R = tokens.shape[0]
        # a block and the block behind it may lie in different pages
        dest_block = jnp.take_along_axis(
            block_tables, (offsets // bs)[:, None], axis=-1)       # [R, 1]
        if behind is not None:
            dest_block = jnp.where(both(wanted, every)[:, None], dest_block,
                                   L * NB)
        dest_off = (offsets % bs)[:, None] + jnp.arange(n)[None, :]  # [R, n]
        q_pos = offsets[:, None] + jnp.arange(n)[None, :]
        impl = self.paged_decode_impl()
        H, Hkv = cfg.n_heads, cfg.n_kv_heads

        def step(carry, layer_and_base, stacks):
            x, k_pool, v_pool = carry
            layer, base = layer_and_base

            def attend(q, k_new, v_new):
                with jax.named_scope("blockdiff_kv_update"):
                    k_all = k_pool.at[base + dest_block, dest_off].set(
                        k_new, mode=mode)
                    v_all = v_pool.at[base + dest_block, dest_off].set(
                        v_new, mode=mode)
                with jax.named_scope("blockdiff_attention"):
                    rows = q.reshape(R, n, Hkv, H // Hkv, -1).transpose(
                        0, 2, 1, 3, 4).reshape(R, n * H, -1)
                    o = self._attend_pages(
                        rows, k_all, v_all, layer, block_tables, lengths,
                        impl=impl, starts=None, first_block=base,
                        num_blocks=NB, run=run)
                    o = o.reshape(R, Hkv, n, H // Hkv, -1).transpose(
                        0, 2, 1, 3, 4).reshape(R, n, H, -1)
                return o, (k_all, v_all)

            x, (k_pool, v_pool), extra = self._layer(
                x, layer, q_pos, attend, live=live, stacks=stacks)
            return (x, k_pool, v_pool), extra

        main = self._whole_leaves(params["layers"])
        (x, k_out, v_out), extras = self._scan_layers(
            step,
            (self._embed(params, tokens), whole(pool["k"]),
             whole(pool["v"])),
            params, main, (jnp.arange(L, dtype=jnp.int32) * NB,))
        pool = dict(pool, k=k_out.reshape(pool["k"].shape),
                    v=v_out.reshape(pool["v"].shape))
        return (self._head(params, x if behind is None else x[R - B:]), pool,
                extras)

    def _decode_step_eva(self, params: Params, tokens: jax.Array,
                         pool: Params, block_tables: jax.Array,
                         offsets: jax.Array, live=None):
        """``decode_step_paged_counted`` of an EVA model: one softmax
        over the exact rows of the slot's window and the summaries of
        every chunk before it, each walked as its own page list
        (``paged_decode_attention(stats=True)``, kernel or reference by
        the one resolver) and joined (``merge_softmax_parts``). The
        pools live through the step whole and in place, as ever.

        A TWO-PART pool (``init_kv_pools``: ``sk``/``sv`` beside
        ``k``/``v``) comes with ``block_tables`` [2, B, MAXB]: the
        summary part's table, then the exact part's, which counts from
        the WINDOW's first block (row ``r`` of the window is row ``r %
        bs`` of entry ``r // bs``: at a window's end the engine hands
        the slot a fresh table). The step writes its row into the exact
        part, pools the chunk that row lies in (its earlier rows read
        back from the same block) and writes that as summary row
        ``offset // chunk``: EVERY slot EVERY step, no mask and no
        conditional. Only the step at ``offset % chunk == chunk - 1``
        writes the value that stays; the 15 before it write a partial
        one over the same row, which no query can see (a window's
        summaries are read from the next window on). That costs a step
        one block gathered and one row written a slot-layer beside the
        ~70 blocks its attention reads: under a tenth of the program on
        the chip (PERF.md, PR 35).

        A UNIFORM pool (``init_kv_pool``, one table, one row a position:
        the harness's logits check) keeps every row, so the step reads
        the window through ``starts`` and pools the earlier windows'
        chunks as it goes: small batches only, and the same arithmetic.
        """
        window, chunk = self.eva
        L, NB, bs = pool["k"].shape[:3]
        if bs % chunk:
            raise ValueError(
                f"an EVA layer's chunk ({chunk}) has to divide the K/V "
                f"block ({bs}): a chunk's rows are read from one block")
        two_part = "sk" in pool
        start = offsets // window * window        # the window's first
        seen = visible_summaries(offsets, window, chunk)
        impl = self.paged_decode_impl()
        from ray_tpu.ops.paged_attention import (
            paged_decode_attention, ragged_decode_attention_reference)

        def entry(table, index):
            return jnp.take_along_axis(table, index[:, None], axis=1)[:, 0]

        if two_part:
            NBs = pool["sk"].shape[1]
            table_s, table = block_tables
            row = offsets - start                 # within the window
            dest_s = entry(table_s, offsets // chunk // bs)
            off_s = offsets // chunk % bs
        else:
            table, row = block_tables, offsets
        dest, off = entry(table, row // bs), row % bs

        chunk_rows = ((off // chunk * chunk)[:, None]
                      + jnp.arange(chunk))[:, :, None, None]

        def chunk_of(rows, base):
            """Each slot's current chunk [B, chunk, Hkv, D], out of the
            block the step writes (whole blocks are gathered: B windows
            of the stack, not B * chunk)."""
            return jnp.take_along_axis(rows[base + dest], chunk_rows, axis=1)

        def step(carry, layer_and_bases):
            x, pools = carry
            layer, base, base_s = layer_and_bases
            phi, mu = layer["eva_phi"], layer["eva_mu"]

            def attend(q, k_new, v_new):
                k_all, v_all, *summaries = pools
                with jax.named_scope("kv_update"):
                    k_all = k_all.at[base + dest, off].set(k_new[:, 0])
                    v_all = v_all.at[base + dest, off].set(v_new[:, 0])
                if two_part:
                    ks, vs = chunk_summaries(
                        chunk_of(k_all, base), chunk_of(v_all, base),
                        phi, mu, chunk)                    # [B, 1, Hkv, D]
                    with jax.named_scope("kv_update"):
                        sk_all, sv_all = (
                            a.at[base_s + dest_s, off_s].set(new[:, 0])
                            for a, new in zip(summaries, (ks, vs)))
                    summaries = [sk_all, sv_all]
                with jax.named_scope("eva_attention"):
                    if two_part:
                        parts = [
                            paged_decode_attention(
                                q[:, 0], k_all, v_all, table, row + 1,
                                impl=impl, first_block=base, num_blocks=NB,
                                stats=True),
                            paged_decode_attention(
                                q[:, 0], sk_all, sv_all, table_s, seen,
                                impl=impl, first_block=base_s,
                                num_blocks=NBs, stats=True)]
                    else:
                        held = base + table                # [B, MAXB]
                        ks, vs = chunk_summaries(
                            k_all[held].reshape(len(tokens), -1,
                                                *k_all.shape[2:]),
                            v_all[held].reshape(len(tokens), -1,
                                                *v_all.shape[2:]),
                            phi, mu, chunk)
                        parts = [
                            paged_decode_attention(
                                q[:, 0], k_all, v_all, table, offsets + 1,
                                impl=impl, starts=start, first_block=base,
                                num_blocks=NB, stats=True),
                            ragged_decode_attention_reference(
                                q[:, 0], ks, vs, seen, stats=True)]
                    o = merge_softmax_parts(parts).astype(q.dtype)
                return o[:, None], (k_all, v_all, *summaries)

            x, pools, extra = self._layer(x, layer, offsets[:, None], attend,
                                          live=live)
            return (x, pools), extra

        names = ("k", "v", "sk", "sv") if two_part else ("k", "v")
        layers = jnp.arange(L, dtype=jnp.int32)
        (x, out), extras = jax.lax.scan(
            step,
            (self._embed(params, tokens[:, None]),
             tuple(pool[n].reshape((-1,) + pool[n].shape[2:])
                   for n in names)),
            (params["layers"], layers * NB,
             layers * (NBs if two_part else 0)))
        pool = dict(pool, **{n: a.reshape(pool[n].shape)
                             for n, a in zip(names, out)})
        return self._head(params, x)[:, 0], pool, extras

    def prefill_with_prefix(self, params: Params, tokens: jax.Array,
                            prefix_k: jax.Array, prefix_v: jax.Array,
                            prefix_len: jax.Array, lengths: jax.Array,
                            prefix_start: Optional[jax.Array] = None,
                            summaries: Optional[Tuple[jax.Array, jax.Array]]
                            = None) -> Tuple[jax.Array, Params]:
        """Suffix prefill attending over a cached (shared) prefix.

        tokens   [N, Tb] suffix tokens (right-padded)
        prefix_k/v [L, N, Pmax, Hkv, D] dense prefix K/V gathered from
                 the pool, right-padded past ``prefix_len``
        prefix_len [N] valid prefix tokens
        lengths  [N] valid suffix tokens
        Returns (last-token logits [N, V], suffix K/V [L, N, Tb, Hkv, D])
        — the caller scatters the suffix K/V into fresh pool blocks; the
        prefix blocks are never copied or rewritten (prefix-reuse skips
        their FLOPs entirely). A sliding layer masks the prefix rows
        behind each query's window, so its rows there may hold anything
        (the engine has freed their blocks and gathers none of them).

        An EVA model's prefix is the exact rows from ``prefix_start``
        [N] on (the first position of the window ``prefix_len`` lies
        in: the gather never grows past a window) and ``summaries``,
        (k~, v~) [L, N, Smax, Hkv, D], the slot's summary rows from
        chunk 0 (those of chunks that began before ``prefix_len`` are
        read). ``prefix_len`` is a multiple of the chunk. The suffix's
        own chunks are pooled here and handed back as ``sk``/``sv``
        [L, N, Tb/chunk, Hkv, D] beside its K/V (a suffix longer than a
        window reads them itself).
        """
        Tb = tokens.shape[1]
        Pmax = prefix_k.shape[2]
        if self.eva is not None:
            return self._prefill_with_prefix_eva(
                params, tokens, prefix_k, prefix_v, prefix_len, lengths,
                prefix_start, *summaries)
        # absolute positions: suffix token t sits at prefix_len + t;
        # padded prefix rows get a position PAST every query so the
        # causal mask drops them
        pos_q = prefix_len[:, None] + jnp.arange(Tb)[None, :]       # [N,Tb]
        far = jnp.int32(2 ** 30)
        pos_prefix = jnp.where(
            jnp.arange(Pmax)[None, :] < prefix_len[:, None],
            jnp.arange(Pmax)[None, :], far)                          # [N,Pmax]
        pos_k = jnp.concatenate([pos_prefix, pos_q], axis=1)      # [N,P+Tb]
        mask_pos = self._mask_positions(pos_q)

        def step(x, layer_and_prefix, stacks):
            layer, kp, vp, kind = layer_and_prefix  # kp/vp [N, Pmax, Hkv, D]

            def attend(q, k_new, v_new):
                with jax.named_scope("attention"):
                    o = self._attend_rows(
                        q, jnp.concatenate([kp.astype(k_new.dtype), k_new],
                                           axis=1),
                        jnp.concatenate([vp.astype(v_new.dtype), v_new],
                                        axis=1),
                        layer, mask_pos, pos_k, self._window(kind))
                return o, (k_new, v_new)

            x, kv, _ = self._layer(x, layer, pos_q, attend, kind=kind,
                                   stacks=stacks)
            return x, kv

        main = self._whole_leaves(params["layers"])
        x, (k_out, v_out) = self._scan_layers(
            step, self._embed(params, tokens), params, main,
            (prefix_k, prefix_v, self._kinds_xs()), join=True)
        return (self._head(params, x, last=lengths - 1)[:, 0],
                {"k": k_out, "v": v_out})

    def _prefill_with_prefix_eva(self, params, tokens, prefix_k, prefix_v,
                                 prefix_len, lengths, prefix_start,
                                 sum_k, sum_v):
        window, chunk = self.eva
        Tb, Pmax, Smax = tokens.shape[1], prefix_k.shape[2], sum_k.shape[2]
        far = jnp.int32(2 ** 30)
        pos_q = prefix_len[:, None] + jnp.arange(Tb)[None, :]       # [N,Tb]
        at = prefix_start[:, None] + jnp.arange(Pmax)[None, :]
        pos_k = jnp.concatenate(
            [jnp.where(at < prefix_len[:, None], at, far), pos_q], axis=1)
        # summary rows: the slot's own up to the suffix's first chunk,
        # then the suffix's
        first = prefix_len[:, None] // chunk
        held = jnp.arange(Smax)[None, :]
        index_s = jnp.concatenate(
            [jnp.where(held < first, held, far),
             first + jnp.arange(Tb // chunk)[None, :]], axis=1)

        def step(x, layer_and_prefix):
            layer, kp, vp, skp, svp = layer_and_prefix

            def attend(q, k_new, v_new):
                ks, vs = chunk_summaries(k_new, v_new, layer["eva_phi"],
                                         layer["eva_mu"], chunk)

                def join(old, new):
                    return jnp.concatenate([old.astype(new.dtype), new],
                                           axis=1)

                o = eva_attention(q, join(kp, k_new), join(vp, v_new),
                                  join(skp, ks), join(svp, vs), pos_q, pos_k,
                                  index_s, window=window, chunk=chunk)
                return o, (k_new, v_new, ks, vs)

            x, kv, _ = self._layer(x, layer, pos_q, attend)
            return x, kv

        x, kv = jax.lax.scan(
            step, self._embed(params, tokens),
            (params["layers"], prefix_k, prefix_v, sum_k, sum_v))
        return (self._head(params, x, last=lengths - 1)[:, 0],
                self._kv_dict(kv))

    def loss(self, params: Params, tokens: jax.Array,
             targets: jax.Array,
             mask: Optional[jax.Array] = None) -> jax.Array:
        """Mean next-token cross-entropy."""
        # by class: ``PipelinedLlama`` borrows this method
        logits = self.apply(params, tokens)
        if self.cfg.num_pred_heads > 1:     # head 0: the next token's
            logits = logits[..., :self.cfg.vocab_size]
        return LlamaModel._cross_entropy(logits, targets, mask)

    @staticmethod
    def _cross_entropy(logits, targets, mask):
        with jax.named_scope("loss"):
            logp = jax.nn.log_softmax(logits, axis=-1)
            nll = -jnp.take_along_axis(logp, targets[..., None],
                                       axis=-1).squeeze(-1)
            if mask is not None:
                return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1)
            return jnp.mean(nll)
