"""GPT-2 family — BASELINE.md config 2 (GPT-2 125M, 4-worker DP).

Reference capability: trained via TorchTrainer+DDP in the reference's
release tests; here a pjit data/tensor-parallel functional model (pre-LN,
learned positions, tied embeddings, GELU MLP).

``GPT2Config.remat``: False keeps every intermediate of every block for
the backward; True (the default) remakes what does not fit. The layer
scan then keeps, besides its carry, the longest prefix of ``RESIDUALS``
that ``residuals_that_fit`` the device: the attention kernels' ``o`` and
log-sum-exp (the forward kernel runs once a step, not twice), then the
qkv product, the attention block's output, and w_up's product; the layer
norms, the GELU and whatever was not kept are remade in the backward.
The choice is arithmetic on ``tokens.shape``, ``cfg`` and the device's
``memory_stats()["bytes_limit"]``, made once a trace; it is logged, and
exported as the gauge ``train.kept_residual_bytes`` (one series a
candidate, 0 where it did not fit). On a CPU, which reports no limit,
and under a mesh nothing is kept. No flag selects it.
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
import logging
from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.ops.attention import (FLASH_RESIDUALS, attention,
                                   packed_attention, use_flash_on)
from ray_tpu.ops.norms import layer_norm
from ray_tpu.util.metrics import Gauge

logger = logging.getLogger(__name__)

Params = Dict[str, Any]

# What the layer scan may keep of a block for its backward, as
# ``checkpoint_name``s by the tag ``train.kept_residual_bytes`` gives them,
# in the order they are taken: the attention kernel's outputs first (they
# save its second run, and stand alone), then the matmul products by what
# completes a chain from the block's input with nothing remade: qkv, the
# attention block's output, w_up's (what each bought on the v5e: PERF.md
# PR 49)
RESIDUALS = {
    "attention": FLASH_RESIDUALS,
    "qkv": ("gpt2.qkv",),
    "wo": ("gpt2.wo",),
    "w_up": ("gpt2.w_up",),
}


# Device bytes the layer scan's budget leaves alone: what the compiler
# reserves of a chip (0.26 GiB on a v5e) and what a training process
# holds outside its step program (0.15 GiB: PERF.md PR 49)
_RESERVE_BYTES = 420 << 20


def residuals_that_fit(candidates: Sequence[int], held_bytes: int,
                       logits_bytes: int, capacity: Optional[int]) -> int:
    """How many of ``candidates`` (the bytes of each of ``RESIDUALS``
    over the whole layer stack, in its order) the layer scan keeps: the
    longest prefix that fits ``capacity`` beside ``held_bytes`` (the
    train state, its gradients and the scan's carry, held whatever is
    kept), the loss head's float32 ``logits_bytes`` and
    ``_RESERVE_BYTES``. Arithmetic, never a trial compile, and on the
    side of keeping less: a step remade costs a few percent, a program
    the compiler refuses costs the run (the v5e compiler's frontier for
    GPT-2 medium, batch by batch: PERF.md PR 49). None where the device
    does not say what it holds (``capacity`` None: a CPU)."""
    if capacity is None:
        return 0
    room = capacity - _RESERVE_BYTES - held_bytes - logits_bytes
    return sum(total <= room for total in itertools.accumulate(candidates))


def _chip_bytes() -> Optional[int]:
    """What the first device may allocate; None where the backend does
    not say (a CPU)."""
    stats = jax.devices()[0].memory_stats()
    return stats.get("bytes_limit") if stats else None


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
            wqkv, bqkv, wo = (layer[name].astype(dt)
                              for name in ("wqkv", "bqkv", "wo"))
            if self.mesh is not None:
                # the heads are sharded: the product keeps them an axis,
                # and is cut for the reference (a Mosaic call carries no
                # partitioning rule)
                qkv = jnp.einsum("bsd,dthk->bsthk", h, wqkv) + bqkv
                qkv = checkpoint_name(qkv, "gpt2.qkv")
                o = attention(*(qkv[:, :, n] for n in range(3)), causal=True,
                              use_flash=False)
                o = jnp.einsum("bshk,hkd->bsd", o, wo)
            else:
                # off a mesh the heads stay side by side in the lanes from
                # the product to wo: what the dispatcher finds tiles, and
                # the kernels, read and write that as it lies
                qkv = jnp.einsum("bsd,df->bsf", h,
                                 wqkv.reshape(cfg.dim, -1)) + bqkv.reshape(-1)
                qkv = checkpoint_name(qkv, "gpt2.qkv")
                o = packed_attention(qkv, cfg.n_heads, causal=True,
                                     use_flash=self._use_flash)
                o = jnp.einsum("bsf,fd->bsd", o, wo.reshape(-1, cfg.dim))
        with jax.named_scope("norm_residual"):
            x = checkpoint_name(x + o + layer["bo"].astype(dt), "gpt2.wo")
            h = layer_norm(x, layer["ln2_w"], layer["ln2_b"],
                           eps=cfg.norm_eps)
        with jax.named_scope("mlp"):
            up = jnp.einsum("bsd,df->bsf", h, layer["w_up"].astype(dt))
            up = checkpoint_name(up + layer["b_up"].astype(dt), "gpt2.w_up")
            up = jax.nn.gelu(up)
            down = jnp.einsum("bsf,fd->bsd", up, layer["w_down"].astype(dt))
        with jax.named_scope("norm_residual"):
            return x + down + layer["b_down"].astype(dt)

    def _keeping(self, batch: int, seq: int):
        """The layer scan's ``jax.checkpoint`` policy at this batch: the
        prefix of ``RESIDUALS`` that ``residuals_that_fit`` the device,
        read once a trace from the shapes, ``cfg`` and the device. Under
        a mesh a chip's share of each term is the shardings' to say, not
        this model's: nothing is kept, as on a CPU. Where the dispatcher
        does not take the fused kernels (under 256 positions, a head past
        VMEM) the first candidate's names are in no program and its bytes
        are set aside for nothing."""
        cfg = self.cfg
        GiB = 2 ** 30
        # [batch, seq, 1] of activations over the stack, and the kernels'
        # row of float32 log-sum-exp a head (padded to whole lanes)
        act = batch * seq * jnp.dtype(cfg.dtype).itemsize * cfg.n_layers
        lse = batch * cfg.n_heads * (seq + -seq % 128) * 4 * cfg.n_layers
        sizes = {"attention": act * cfg.dim + lse, "qkv": 3 * act * cfg.dim,
                 "wo": act * cfg.dim, "w_up": act * cfg.ffn_dim}
        # float32 parameters, two AdamW moments and gradients, the bf16
        # copy of the weights the compiler makes outside the scan, and
        # the scan's carry
        held = 18 * cfg.num_params() + act * cfg.dim
        logits = 4 * batch * seq * cfg.vocab_size
        capacity = None if self.mesh is not None else _chip_bytes()
        kept = list(RESIDUALS)[:residuals_that_fit(
            [sizes[tag] for tag in RESIDUALS], held, logits, capacity)]
        keep = jax.checkpoint_policies.save_only_these_names(
            *(name for tag in kept for name in RESIDUALS[tag]))

        @functools.cache
        def report():
            gauge = Gauge(
                "train.kept_residual_bytes",
                "what GPT-2's layer scan keeps for its backward", ("name",))
            for tag, size in sizes.items():
                gauge.set(size if tag in kept else 0, {"name": tag})
            logger.info(
                "gpt2 layer scan at %d x %d keeps %s of %s GiB beside %.2f "
                "GiB of state and carry, %.2f of float32 logits and %.2f "
                "of reserve: capacity %s GiB", batch, seq, kept or "nothing",
                {t: round(b / GiB, 2) for t, b in sizes.items()},
                held / GiB, logits / GiB, _RESERVE_BYTES / GiB,
                capacity and round(capacity / GiB, 2))

        def policy(*args, **kwargs):
            report()        # asked only where the block is differentiated
            return keep(*args, **kwargs)

        return policy

    def apply(self, params: Params, tokens: jax.Array) -> jax.Array:
        cfg = self.cfg
        B, S = tokens.shape
        with jax.named_scope("embed"):
            x = params["wte"].astype(cfg.dtype)[tokens]
            x = x + params["wpe"].astype(cfg.dtype)[:S][None]

        block = self._block
        if cfg.remat:
            block = jax.checkpoint(block, policy=self._keeping(B, S))

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
