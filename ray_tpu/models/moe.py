"""Mixture-of-experts decoder on the Llama block (SURVEY.md §2.3: EP is
absent in the reference — vLLM handles MoE internally — so this is a
native capability).

``MoEModel`` is ``LlamaModel`` with two methods overridden: ``_ffn`` (a
router and ``num_experts`` SwiGLU experts, ``expert_top_k`` a token) and
``_qk_norm`` (OLMoE's RMSNorm over all heads' lanes of q and of k, or
Qwen3's over each head's). The
parent's one decoder layer (``LlamaModel._layer``) calls both, so every
program built on it — training ``apply``/``loss``, ``forward_step``,
``decode_step_paged``, ``prefill_with_prefix``, so Serve and the engine —
runs the expert block.

Off an ``ep`` mesh axis the FFN is DROPLESS (``ops/moe_dispatch.
dropless_expert_ffn``): every chosen expert is computed. Under ``ep`` > 1
it is the capacity-bounded GShard dispatch (tokens beyond an expert's
capacity are dropped), as one-hot einsums or as an explicit all-to-all
(``moe_dispatch``); the mesh decides, not a flag.

The three expert matmuls are ``ops.moe_dispatch.grouped_matmul``: on a
TPU backend the Pallas grouped matmul at a tiling chosen from the
call's shapes (rows, k, n), at every expert width that has one, the
three walking the groups by ONE table computed a layer;
``jax.lax.ragged_dot`` off the chip and where no tiling is legal;
``grouped_matmul_plan`` says which for an engine's ``stats``
(``moe_grouped_impl``). Training runs the same forward, with
``ragged_dot``'s backward.

The serving programs hold the expert stacks WHOLE: their layer scans
close over ``e_gate`` / ``e_up`` / ``e_down`` as ``[L*E, ...]`` and the
grouped matmuls read layer ``l``'s experts in place, as groups ``l*E``
onwards (``WHOLE_LAYER_LEAVES``, ``LlamaModel._whole_leaves``,
``dropless_expert_ffn(first_expert=)``). Handed to a scan as ``xs`` each
stack's slice was copied out a layer a step, since a grouped matmul's
operand must be a buffer of its own: more time than the matmuls took
(PERF.md, PR 37). What the caller hands in decides, not a flag, and two
callers keep the slice, each for a reason the code can see: TRAINING
(``apply``, under ``grad``: the transpose of a whole-stack operand is a
stack-sized gradient a LAYER) and a model with a MESH (the ``ep``
capacity paths' einsums slice for free, and merging ``L`` with a sharded
``experts`` dimension would re-shard the stack).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models.llama import LlamaConfig, LlamaModel, Params
from ray_tpu.ops.norms import rms_norm


@dataclasses.dataclass(frozen=True)
class MoEConfig(LlamaConfig):
    """``ffn_dim`` is ONE expert's width."""
    num_experts: int = 8
    expert_top_k: int = 2
    # the top-k router weights renormalised to sum to 1 (Mixtral, GShard)
    # or used as the softmax gave them (OLMoE: ``norm_topk_prob`` false)
    norm_topk_prob: bool = True
    # RMSNorm of q and of k over ALL heads' lanes, before the split into
    # heads and before RoPE (OLMoE); adds ``q_norm``/``k_norm`` params
    qk_norm: bool = False
    # with ``qk_norm``: one RMS over EACH head's lanes instead, one
    # weight of ``head_dim`` shared by the heads (Qwen3's)
    qk_norm_per_head: bool = False
    router_z_loss: float = 1e-3
    load_balance_loss: float = 1e-2
    # Under an ``ep`` mesh axis only (off it the FFN is dropless):
    # rows an expert takes = capacity_factor * T * K / E, and which of
    # the two capacity dispatches runs: "einsum" = dense one-hot, XLA
    # chooses collectives; "alltoall" = explicit expert all-to-all
    # inside shard_map (ops/moe_dispatch.py).
    capacity_factor: float = 1.25
    moe_dispatch: str = "einsum"
    # The router. "softmax": the top-k of a softmax over all experts.
    # "sigmoid" (``topk_method: noaux_tc``, one group): scores
    # ``sigmoid(logits)``; chosen by ``scores + router_bias`` [E], a
    # float32 leaf that is no part of the weights (its values come from
    # training; ``init`` draws them N(0, ``router_bias_init_std``) so that
    # a program that drops it differs); weights the chosen scores, under
    # ``norm_topk_prob`` over their sum, times ``routed_scaling_factor``
    router_kind: str = "softmax"
    routed_scaling_factor: float = 1.0
    router_bias_init_std: float = 0.0
    # what the sigmoid router adds to the chosen scores' sum before it
    # divides by it (``norm_topk_prob``): the family's (LFM2: 1e-6)
    router_renorm_eps: float = 1e-20
    # the sigmoid router's GROUP LIMIT (``n_group``, ``topk_group``): the
    # experts are ``router_n_group`` groups of neighbours, scored by the
    # sum of their two best; the top-k is taken in the
    # ``router_topk_group`` best groups alone (``route_topk``). 1: none
    router_n_group: int = 1
    router_topk_group: int = 1
    # ONE CHIP'S SHARE of a layer that several chips share by experts:
    # the layer HOLDS ``experts_held`` of the router's ``num_experts``,
    # from ``first_expert_held`` on (None: all of them). The router
    # keeps its width and its top-k; an assignment to an expert that is
    # not held is computed by nobody here and its part of the sum is
    # left out (``dropless_expert_ffn(held=)``); nothing stands in for
    # the other chips or their exchange
    experts_held: Optional[int] = None
    first_expert_held: int = 0
    # a dense SwiGLU this wide on EVERY token beside the routed sum (the
    # shared experts, side by side: n_shared x one's width); 0: none
    shared_ffn_dim: int = 0
    # the first ``leading_layers`` layers are DENSE (SwiGLU
    # ``leading_ffn_dim``): another parameter tree, a stack of its own
    # (``params["leading_layers"]``, ``LlamaModel._scan_layers``)
    leading_layers: int = 0
    leading_ffn_dim: Optional[int] = None

    def __post_init__(self):
        super().__post_init__()
        if self.moe_dispatch not in ("einsum", "alltoall"):
            raise ValueError(
                f"moe_dispatch must be 'einsum' or 'alltoall', "
                f"got {self.moe_dispatch!r}")
        if self.router_kind not in ("softmax", "sigmoid"):
            raise ValueError(
                f"router_kind must be 'softmax' or 'sigmoid', "
                f"got {self.router_kind!r}")
        if not 0 <= self.leading_layers < self.n_layers or (
                self.leading_layers and not self.leading_ffn_dim):
            raise ValueError(
                f"leading_layers ({self.leading_layers}) dense layers of "
                f"width leading_ffn_dim ({self.leading_ffn_dim}) come "
                f"before at least one of the {self.n_layers} layers")
        if self.leading_layers and self.layer_types is not None:
            raise ValueError("leading layers of another tree are not "
                             "combined with layer kinds")
        groups, kept = self.router_n_group, self.router_topk_group
        if groups > 1 and (self.router_kind != "sigmoid"
                           or self.num_experts % groups
                           or not 1 <= kept <= groups
                           or self.num_experts // groups < 2
                           or kept * (self.num_experts // groups)
                           < self.expert_top_k):
            raise ValueError(
                f"a group limit is the sigmoid router's: {groups} groups "
                f"that divide the {self.num_experts} experts into two or "
                f"more each, of which {kept} hold the top "
                f"{self.expert_top_k}")
        if self.held != (0, self.num_experts) and not (
                0 <= self.held[0] and self.held[1] >= 1
                and sum(self.held) <= self.num_experts):
            raise ValueError(
                f"experts {self.held[0]} to {sum(self.held)} are not among "
                f"the router's {self.num_experts}")

    @property
    def held(self) -> Tuple[int, int]:
        """(first, count) of the experts this layer holds."""
        if self.experts_held is None:
            return 0, self.num_experts
        return self.first_expert_held, self.experts_held

    def attention_params(self) -> int:
        """One layer's attention weights (and QK-norm scales)."""
        d = self.dim
        q = self.n_heads * self.head_dim
        kv = self.n_kv_heads * self.head_dim
        norms = (0 if not self.qk_norm
                 else 2 * self.head_dim if self.qk_norm_per_head else q + kv)
        return 2 * d * q + 2 * d * kv + norms

    def num_params(self) -> int:
        d, f, v, E = self.dim, self.ffn_dim, self.vocab_size, self.num_experts
        # and two norms, and the stream maps where there are streams
        attn = self.attention_params() + 2 * d + self.hc_params()
        expert_layer = (attn + d * E + 3 * self.held[1] * d * f
                        + 3 * d * self.shared_ffn_dim
                        + (E if self.router_kind == "sigmoid" else 0))
        lead = self.leading_layers
        dense_layer = attn + 3 * d * (self.leading_ffn_dim or 0)
        heads = 0 if self.tie_embeddings else v * d
        return (v * d + lead * dense_layer
                + (self.n_layers - lead) * expert_layer + d + heads)

    @staticmethod
    def debug_moe(num_experts: int = 4) -> "MoEConfig":
        return MoEConfig(vocab_size=256, dim=64, n_layers=2, n_heads=4,
                         n_kv_heads=2, ffn_dim=128, max_seq_len=128,
                         remat=False, num_experts=num_experts)

    @staticmethod
    def debug_olmoe(vocab_size: int = 512, max_seq_len: int = 128,
                    **overrides) -> "MoEConfig":
        """OLMoE's block at debug widths: 8 experts, top-2 weights as
        they are, QK-norm, as many KV heads as heads."""
        return MoEConfig(**{**dict(
            vocab_size=vocab_size, dim=64, n_layers=2, n_heads=4,
            n_kv_heads=4, ffn_dim=32, max_seq_len=max_seq_len, remat=False,
            rope_theta=10_000.0, num_experts=8, expert_top_k=2,
            norm_topk_prob=False, qk_norm=True), **overrides})

    @staticmethod
    def debug_sdar(vocab_size: int = 512, max_seq_len: int = 128,
                   **overrides) -> "MoEConfig":
        """SDAR's block at debug widths: 8 experts, top-2 renormalised,
        QK-norm a head, GQA 4/2, and generation by diffusion over blocks
        of four (four passes, the published default rule, the last id
        as the mask)."""
        return MoEConfig(**{**dict(
            vocab_size=vocab_size, dim=64, n_layers=2, n_heads=4,
            n_kv_heads=2, head_dim=16, ffn_dim=32, max_seq_len=max_seq_len,
            remat=False, rope_theta=1_000_000.0, norm_eps=1e-6,
            num_experts=8, expert_top_k=2, norm_topk_prob=True,
            qk_norm=True, qk_norm_per_head=True, block_length=4,
            denoising_steps=4, mask_token_id=vocab_size - 1), **overrides})


def moe_param_logical_axes(cfg: MoEConfig) -> Params:
    from ray_tpu.models.llama import param_logical_axes
    axes = param_logical_axes(cfg)
    layers = dict(axes["layers"])
    for key in ("w_gate", "w_up", "w_down"):
        del layers[key]
    layers["router"] = (None, "embed_in", "experts")
    layers["e_gate"] = (None, "experts", "embed_in", "mlp")
    layers["e_up"] = (None, "experts", "embed_in", "mlp")
    layers["e_down"] = (None, "experts", "mlp", "embed_in")
    if cfg.router_kind == "sigmoid":
        layers["router_bias"] = (None, "experts")
    if cfg.shared_ffn_dim:
        layers["s_gate"] = layers["s_up"] = (None, "embed_in", "mlp")
        layers["s_down"] = (None, "mlp", "embed_in")
    if cfg.leading_layers:
        axes["leading_layers"] = param_logical_axes(cfg)["layers"]
    if cfg.qk_norm and cfg.qk_norm_per_head:
        layers["q_norm"] = layers["k_norm"] = (None, None)
    elif cfg.qk_norm:
        layers["q_norm"] = (None, "heads", None)
        layers["k_norm"] = (None, "kv_heads", None)
    axes["layers"] = layers
    return axes


class MoEModel(LlamaModel):
    """Llama block with an expert FFN (and, if configured, QK-norm)."""

    # the expert stacks for the dense FFN's three; the ROUTER is not
    # among them: its logits are computed in float32 (``route_topk``) and
    # a bf16 router would choose other experts
    MATMUL_LAYER_LEAVES = ("wq", "wk", "wv", "wo",
                           "e_gate", "e_up", "e_down",
                           # the shared expert's, and a leading dense
                           # layer's FFN (the router's BIAS, like the
                           # router, stays float32)
                           "s_gate", "s_up", "s_down",
                           "w_gate", "w_up", "w_down")
    # the grouped matmuls' operands (the router is a dense matmul's)
    WHOLE_LAYER_LEAVES = ("e_gate", "e_up", "e_down")

    def __init__(self, cfg: MoEConfig, mesh=None,
                 rules: Optional[Dict] = None):
        super().__init__(cfg, mesh=mesh, rules=rules)
        self._ep = 1 if mesh is None else mesh.shape.get("ep", 1)
        if self._ep > 1 and (cfg.router_kind != "softmax"
                             or cfg.shared_ffn_dim
                             or cfg.experts_held is not None):
            raise NotImplementedError(
                "the capacity dispatches under an ep mesh axis have the "
                "softmax router, no shared expert and every expert (the "
                "mesh shares them out, not ``experts_held``)")

    @property
    def _main_layers(self) -> int:
        return self.cfg.n_layers - self.cfg.leading_layers

    def init(self, rng: jax.Array) -> Params:
        params = super().init(rng)
        cfg: MoEConfig = self.cfg
        d, f, E, L = cfg.dim, cfg.ffn_dim, cfg.num_experts, self._main_layers
        H = cfg.held[1]              # the stacks hold the layer's share
        keys = jax.random.split(jax.random.fold_in(rng, 1), 4)
        layers = params["layers"]
        for key in ("w_gate", "w_up", "w_down"):
            del layers[key]
        layers["router"] = jax.random.normal(
            keys[0], (L, d, E), jnp.float32) * 0.02
        layers["e_gate"] = jax.random.normal(
            keys[1], (L, H, d, f), jnp.float32) * d ** -0.5
        layers["e_up"] = jax.random.normal(
            keys[2], (L, H, d, f), jnp.float32) * d ** -0.5
        layers["e_down"] = jax.random.normal(
            keys[3], (L, H, f, d), jnp.float32) * f ** -0.5
        if cfg.qk_norm and cfg.qk_norm_per_head:
            layers["q_norm"] = jnp.ones((L, cfg.head_dim), jnp.float32)
            layers["k_norm"] = jnp.ones((L, cfg.head_dim), jnp.float32)
        elif cfg.qk_norm:
            layers["q_norm"] = jnp.ones(
                (L, cfg.n_heads, cfg.head_dim), jnp.float32)
            layers["k_norm"] = jnp.ones(
                (L, cfg.n_kv_heads, cfg.head_dim), jnp.float32)
        more = iter(jax.random.split(jax.random.fold_in(rng, 2), 16))
        if cfg.router_kind == "sigmoid":
            layers["router_bias"] = jax.random.normal(
                next(more), (L, E), jnp.float32) * cfg.router_bias_init_std
        if cfg.shared_ffn_dim:
            fs = cfg.shared_ffn_dim
            layers["s_gate"] = self._dense(next(more), (L, d, fs), d)
            layers["s_up"] = self._dense(next(more), (L, d, fs), d)
            layers["s_down"] = self._dense(next(more), (L, fs, d), fs)
        if cfg.leading_layers:
            params["leading_layers"] = self._init_layers(
                more, cfg.leading_layers, cfg.leading_ffn_dim)
        return params

    def param_shardings(self):
        from ray_tpu.parallel.mesh import named_sharding
        axes = moe_param_logical_axes(self.cfg)
        return jax.tree.map(
            lambda names: named_sharding(self.mesh, *names,
                                         rules=self.rules),
            axes, is_leaf=lambda x: isinstance(x, tuple))

    # -- the layer's two overrides -----------------------------------------
    def _qk_norm(self, q, k, layer: Params):
        cfg: MoEConfig = self.cfg
        if not cfg.qk_norm:
            return q, k

        def norm(x, w):
            if cfg.qk_norm_per_head:    # one RMS a head, w [hd]
                return rms_norm(x, w, eps=cfg.norm_eps)
            # one RMS over every head's lanes: [B, T, H, hd] as [B, T, H*hd]
            flat = rms_norm(x.reshape(*x.shape[:2], -1), w.reshape(-1),
                            eps=cfg.norm_eps)
            return flat.reshape(x.shape)

        with jax.named_scope("qk_norm"):
            return norm(q, layer["q_norm"]), norm(k, layer["k_norm"])

    def _ffn(self, h, layer: Params, live=None, constrain: bool = False,
             stacks: Optional[Params] = None):
        """(``constrain`` is the dense FFN's: the expert dispatches place
        their own.) h [B, T, D] -> (out, {"aux": training loss of the router,
        "load": [E] rows handed to each expert, of ``live`` slots,
        "experts": [B, T, K] each token's chosen experts}). Under an
        ``ep`` mesh axis the capacity dispatch runs and only "aux" is
        there (``ffn_load_shape`` says so). With ``stacks`` (a serving
        program off a mesh) the expert weights are every layer's,
        ``[L*E, ...]``, and this layer's begin at ``layer["index"] * E``.

        The layer's own tree says what it is: one with no router is a
        LEADING DENSE layer (``cfg.leading_layers``), the parent's SwiGLU
        and no extra. A ``shared_ffn_dim`` adds the shared expert's dense
        SwiGLU of every token to the routed sum."""
        cfg: MoEConfig = self.cfg
        if "router" not in layer:
            with jax.named_scope("dense_ffn_leading"):
                return super()._ffn(h, layer, live, constrain)
        shared = dict(top_k=cfg.expert_top_k, dtype=cfg.dtype,
                      norm_topk_prob=cfg.norm_topk_prob,
                      z_coef=cfg.router_z_loss,
                      lb_coef=cfg.load_balance_loss)
        held = layer if stacks is None else stacks
        weights = (layer["router"], held["e_gate"], held["e_up"],
                   held["e_down"])
        if self._ep > 1:
            from ray_tpu.ops.moe_dispatch import (capacity_einsum_ffn,
                                                  expert_alltoall_ffn)
            shared.update(num_experts=cfg.num_experts,
                          capacity_factor=cfg.capacity_factor)
            if cfg.moe_dispatch == "alltoall":
                out, aux = expert_alltoall_ffn(h, *weights, self.mesh,
                                               **shared)
                aux = jnp.mean(aux)
            else:
                out, aux = capacity_einsum_ffn(h, *weights, **shared)
            return out, {"aux": aux}
        from ray_tpu.ops.moe_dispatch import dropless_expert_ffn
        B, T, D = h.shape
        rows_live = None if live is None else jnp.repeat(live, T)
        if cfg.router_kind == "sigmoid":
            shared.update(sigmoid_bias=layer["router_bias"],
                          weight_scale=cfg.routed_scaling_factor,
                          renorm_eps=cfg.router_renorm_eps)
        if cfg.router_n_group > 1:
            shared.update(groups=(cfg.router_n_group, cfg.router_topk_group))
        if cfg.experts_held is not None:
            shared.update(held=cfg.held)
        out, load, experts, aux = dropless_expert_ffn(
            h.reshape(B * T, D), *weights, live=rows_live,
            first_expert=(None if stacks is None
                          else layer["index"] * cfg.held[1]), **shared)
        out = out.reshape(B, T, D)
        if cfg.shared_ffn_dim:
            with jax.named_scope("moe_shared_expert"):
                dt = cfg.dtype
                act = (jax.nn.silu(jnp.einsum(
                    "bsd,df->bsf", h, layer["s_gate"].astype(dt)))
                    * jnp.einsum("bsd,df->bsf", h, layer["s_up"].astype(dt)))
                out = out + jnp.einsum("bsf,fd->bsd", act,
                                       layer["s_down"].astype(dt))
        return out, {
            "aux": aux, "load": load,
            "experts": experts.reshape(B, T, cfg.expert_top_k)}

    def ffn_load_shape(self) -> Optional[Tuple[int, int]]:
        """[layers, experts]; nothing under an ``ep`` mesh axis."""
        if self._ep > 1:
            return None
        return self._main_layers, self.cfg.num_experts

    def grouped_matmul_plan(self, tokens: int) -> Dict[str, str]:
        """Which implementation the three grouped matmuls of a program of
        ``tokens`` tokens take and at what (rows, k, n) tiling, as
        ``ops.moe_dispatch.grouped_matmul_impl`` resolves them from the
        platform and the shapes (``tokens * expert_top_k`` rows; gate and
        up ``[D, F]``, down ``[F, D]``). A tiling is "" where the call is
        ``ragged_dot``; all are "" under an ``ep`` mesh axis, where the
        capacity dispatch runs and no grouped matmul does."""
        plan = super().grouped_matmul_plan(tokens)
        if self._ep > 1:
            return plan
        from ray_tpu.ops.moe_dispatch import grouped_matmul_impl
        cfg: MoEConfig = self.cfg
        m, d, f = tokens * cfg.expert_top_k, cfg.dim, cfg.ffn_dim
        itemsize = jnp.dtype(cfg.dtype).itemsize
        calls = {"gate": (d, f), "up": (d, f), "down": (f, d)}
        chosen = {name: grouped_matmul_impl(m, k, n, itemsize)
                  for name, (k, n) in calls.items()}
        plan["moe_grouped_impl"] = "+".join(sorted(
            {impl for impl, _ in chosen.values()}))
        for name, (_, tiling) in chosen.items():
            if tiling is not None:
                plan[f"moe_gmm_tiling_{name}"] = "x".join(map(str, tiling))
        return plan

    def apply_with_aux(self, params: Params, tokens: jax.Array,
                       positions=None):
        logits, extras = self._apply_with_extras(params, tokens, positions)
        return logits, jnp.sum(extras["aux"])

    def loss(self, params: Params, tokens: jax.Array, targets: jax.Array,
             mask=None) -> jax.Array:
        logits, aux = self.apply_with_aux(params, tokens)
        return self._cross_entropy(logits, targets, mask) + aux
