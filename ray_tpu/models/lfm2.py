"""A hybrid gated-short-convolution / attention decoder with experts
(published ``lfm2_moe``): every layer is TWO sublayers,

    x <- x + operator_i(RMSNorm(x));   x <- x + ffn_i(RMSNorm(x))

``operator_i`` is GQA attention (q and k RMS-normed a head before RoPE)
where ``mixer_types[i]`` says ``"full_attention"``, else the gated short
convolution (``ops/shortconv.py``):

    [B | C | x] = h W_in;   g = B * x;   c_t = sum_j w[:, j] g_{t-(K-1)+j}
    out = (C * c) W_out

``ffn_i`` is a dense SwiGLU in the first ``num_dense_layers`` layers and
the routed experts after them (``MoEModel._ffn``, which this model
INHERITS: a sigmoid router with a selection bias, the chosen scores over
their sum + ``router_renorm_eps``, the dropless grouped matmuls and their
counters). The head is the embedding, tied.

A KIND of layer is (mixer, ffn): ``conv_dense``, ``conv_moe``,
``attn_moe`` (and ``attn_dense`` where a pattern has one). Each kind is a
parameter TREE, a stack of its layers (``params["conv_moe"]`` [L, ...]),
and every program walks the RUNS of like layers as ``JambaModel`` does
(for 10 published layers: 2 conv_dense | attn_moe | 3 conv_moe | attn_moe
| 3 conv_moe): a run of several layers is ONE ``lax.scan`` over its
indices into the kind's stack. What a program keeps a layer is carried
through the scans WHOLE and written at the layer's index in place: the
attention layers' K/V rows, the conv layers' state, the expert layers'
counters. The expert stacks are read whole too (``[L*E, ...]``, the
layer's experts from ``j*E`` on: ``moe.py``'s docstring has the reason).

THE RECURRENT STATE is the third form behind the engine's contract
(``llm/engine.py``; docs/serving.md, "Three forms of state, one
contract"): ``"conv"`` [Lc, rows, K-1, dim], the last ``K - 1`` rows of
``g`` a conv layer, in the compute dtype, a row a cache row (bucket
prefill) or a row a SLOT (``init_kv_pool(.., slots)``). Nothing decays and
nothing is scanned: a prefill's state is a slice of its own ``g``.

K/V HEADS OF 64 LANES lie two to a pool row (``LlamaModel.kv_lane_pack``,
``ops/paged_attention.py``): the pool is ``[La, NB, bs, Hkv/2, 128]`` and
the paged kernel reads its pages where they lie.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models.layer_runs import LayerRuns
from ray_tpu.models.llama import Params
from ray_tpu.models.moe import MoEConfig, MoEModel
from ray_tpu.ops import shortconv

CONV, ATTENTION = "conv", "full_attention"


@dataclasses.dataclass(frozen=True)
class Lfm2Config(MoEConfig):
    """``MoEConfig``'s widths (``ffn_dim`` ONE expert's) and router; layer
    ``i``'s mixer is ``mixer_types[i]``, its FFN the dense SwiGLU
    ``dense_ffn_dim`` iff ``i < num_dense_layers``."""
    mixer_types: Tuple[str, ...] = ()
    num_dense_layers: int = 2
    dense_ffn_dim: int = 0
    conv_kernel: int = 3             # taps a channel (``conv_L_cache``)
    # the family's: a biased sigmoid router, q and k normed a head, the
    # head tied to the embedding
    router_kind: str = "sigmoid"
    router_renorm_eps: float = 1e-6
    qk_norm: bool = True
    qk_norm_per_head: bool = True
    tie_embeddings: bool = True
    remat: bool = False

    def __post_init__(self):
        super().__post_init__()
        object.__setattr__(self, "mixer_types", tuple(self.mixer_types))
        if (len(self.mixer_types) != self.n_layers
                or set(self.mixer_types) - {CONV, ATTENTION}
                or self.conv_kernel < 2
                or not 0 <= self.num_dense_layers <= self.n_layers
                or (self.num_dense_layers and not self.dense_ffn_dim)
                or self.router_kind != "sigmoid" or self.leading_layers
                or self.layer_types is not None or self.shared_ffn_dim
                or self.hc_mult != 1 or self.block_length != 1):
            raise ValueError(
                f"a mixer ({CONV!r} or {ATTENTION!r}) for each of the "
                f"{self.n_layers} layers, got {self.mixer_types}; "
                f"{self.num_dense_layers} dense layers of width "
                f"{self.dense_ffn_dim} first; a filter of "
                f"{self.conv_kernel} taps; the sigmoid router, no shared "
                "expert, one residual stream, one token a step")

    def kind(self, i: int) -> str:
        mixer = "conv" if self.mixer_types[i] == CONV else "attn"
        return f"{mixer}_{'dense' if i < self.num_dense_layers else 'moe'}"

    @property
    def conv_layers(self) -> int:
        return self.mixer_types.count(CONV)

    @property
    def attn_layers(self) -> int:
        return self.n_layers - self.conv_layers

    @property
    def expert_layers(self) -> int:
        return self.n_layers - self.num_dense_layers

    def num_params(self) -> int:
        d, E = self.dim, self.num_experts
        conv = d * 3 * d + d * d + d * self.conv_kernel
        dense = 3 * d * self.dense_ffn_dim
        experts = 3 * E * d * self.ffn_dim + d * E + E
        head = 0 if self.tie_embeddings else self.vocab_size * d
        return (self.conv_layers * conv
                + self.attn_layers * self.attention_params()
                + self.num_dense_layers * dense
                + self.expert_layers * experts + self.n_layers * 2 * d
                + self.vocab_size * d + d + head)

    @staticmethod
    def debug(pattern: str = "ccacca", vocab_size: int = 256,
              max_seq_len: int = 128, **kw) -> "Lfm2Config":
        """Debug widths; ``pattern`` a letter a layer, ``c`` a conv layer
        and ``a`` an attention layer; two leading dense layers, 8 experts
        of which 2 are taken, GQA 4 / 2."""
        base = dict(
            vocab_size=vocab_size, dim=64, n_layers=len(pattern), n_heads=4,
            n_kv_heads=2, head_dim=16, ffn_dim=32, dense_ffn_dim=96,
            max_seq_len=max_seq_len, norm_eps=1e-5, rope_theta=1_000_000.0,
            num_experts=8, expert_top_k=2, router_bias_init_std=0.1,
            mixer_types=tuple(CONV if c == "c" else ATTENTION
                              for c in pattern), dtype=jnp.float32)
        return Lfm2Config(**{**base, **kw})


class Lfm2Model(LayerRuns, MoEModel):
    """``MoEModel``'s embedding, norms, QK-norm, RoPE, dense and expert
    FFN, head and sampler around a walk of the runs of like layers over a
    stack a kind (module docstring; ``LayerRuns`` has the runs' loop and
    the attention layers' side of the serving programs, ``JambaModel``'s
    too). No mesh: the kinds' stacks and the state have no partitioning
    rule yet."""

    # the kernel's call and everything beside it: q's placing in its
    # head's lanes, the lanes taken back
    PAGED_ATTENTION_SCOPE = "gqa64_attention"

    # what ``serving_params`` casts to the compute dtype, any stack; the
    # norms, the router and its bias are used in float32 and stay float32
    MATMUL_LEAVES = ("w_in", "conv_w", "w_out", "wq", "wk", "wv", "wo",
                     "w_gate", "w_up", "w_down", "e_gate", "e_up", "e_down")

    def __init__(self, cfg: Lfm2Config, mesh=None,
                 rules: Optional[Dict] = None):
        if mesh is not None:
            raise NotImplementedError(
                "a hybrid short-convolution model runs on one chip: its "
                "stacks a kind and its recurrent state carry no "
                "partitioning rule")
        super().__init__(cfg)
        # (kind, first index in the kind's stack, layers) a run
        self.runs: List[Tuple[str, int, int]] = []
        # of a kind's layers, in order: each one's index among the
        # layers of its mixer (where its rows or state lie) and among
        # the expert layers (where its counters lie)
        self._mixer_at: Dict[str, List[int]] = {}
        self._expert_at: Dict[str, List[int]] = {}
        seen = {"conv": 0, "attn": 0, "moe": 0}
        for i in range(cfg.n_layers):
            kind = cfg.kind(i)
            mixer, ffn = kind.split("_")
            at = self._mixer_at.setdefault(kind, [])
            if self.runs and self.runs[-1][0] == kind:
                self.runs[-1] = (kind, self.runs[-1][1], self.runs[-1][2] + 1)
            else:
                self.runs.append((kind, len(at), 1))
            at.append(seen[mixer])
            seen[mixer] += 1
            if ffn == "moe":
                self._expert_at.setdefault(kind, []).append(seen["moe"])
                seen["moe"] += 1

    @property
    def _main_layers(self) -> int:
        """The EXPERT layers: what ``ffn_load_shape`` counts."""
        return self.cfg.expert_layers

    # -- what the engine asks ------------------------------------------------
    @property
    def recurrent(self) -> bool:
        """The cache holds a fixed-size state a row beside the K/V rows."""
        return self.cfg.conv_layers > 0

    def state_row_shapes(self) -> Dict[str, Tuple[Tuple[int, ...], Any]]:
        """One row's state a conv layer: the last ``K - 1`` rows of ``g``
        in the compute dtype (they are products the next positions
        multiply again: nothing accumulates in them)."""
        cfg = self.cfg
        return {"conv": ((cfg.conv_kernel - 1, cfg.dim), cfg.dtype)}

    def init_state(self, rows: int) -> Params:
        return {name: jnp.zeros((self.cfg.conv_layers, rows) + shape, dtype)
                for name, (shape, dtype) in self.state_row_shapes().items()}

    def state_update_impl(self) -> str:
        """What advances the state in a decode step, for an engine's
        ``decode_attention_impl``: plain XLA, whatever the attention is."""
        return "shortconv_xla"

    # -- init -------------------------------------------------------------------
    def init(self, rng: jax.Array) -> Params:
        cfg, dense = self.cfg, self._dense
        d, f, E, K = cfg.dim, cfg.ffn_dim, cfg.num_experts, cfg.conv_kernel
        k = iter(jax.random.split(rng, 64))
        params: Params = {"embed": dense(next(k), (cfg.vocab_size, d), d),
                          "norm_f": jnp.ones((d,), jnp.float32)}
        if not cfg.tie_embeddings:
            params["lm_head"] = dense(next(k), (d, cfg.vocab_size), d)

        def drawn(shape, std):   # not 1 / 0: a program that drops it differs
            return std * jax.random.normal(next(k), shape, jnp.float32)

        for kind, at in self._mixer_at.items():
            L = len(at)
            mixer, ffn = kind.split("_")
            stack = {"norm": jnp.ones((L, d), jnp.float32),
                     "ffn_norm": jnp.ones((L, d), jnp.float32)}
            if mixer == "conv":
                stack.update(w_in=dense(next(k), (L, d, 3 * d), d),
                             conv_w=dense(next(k), (L, d, K), K),
                             w_out=dense(next(k), (L, d, d), d))
            else:
                stack.update(self._init_attention(k, L))
                stack.update(
                    q_norm=1.0 + drawn((L, cfg.head_dim), 0.25),
                    k_norm=1.0 + drawn((L, cfg.head_dim), 0.25))
            if ffn == "dense":
                fd = cfg.dense_ffn_dim
                stack.update(w_gate=dense(next(k), (L, d, fd), d),
                             w_up=dense(next(k), (L, d, fd), d),
                             w_down=dense(next(k), (L, fd, d), fd))
            else:
                stack.update(
                    router=drawn((L, d, E), 0.02),
                    router_bias=drawn((L, E), cfg.router_bias_init_std),
                    e_gate=dense(next(k), (L, E, d, f), d),
                    e_up=dense(next(k), (L, E, d, f), d),
                    e_down=dense(next(k), (L, E, f, d), f))
            params[kind] = stack
        return params

    def serving_params(self, params: Params) -> Params:
        dt = self.cfg.dtype

        def cast(a):
            return a if a.dtype == dt else a.astype(dt)

        out = dict(params)
        for name in ("embed", "lm_head"):
            if name in params:
                out[name] = cast(params[name])
        for kind in self._mixer_at:
            out[kind] = {k: cast(a) if k in self.MATMUL_LEAVES else a
                         for k, a in params[kind].items()}
        return out

    def param_shardings(self):
        raise NotImplementedError("no mesh (see the class docstring)")

    # -- the two mixers --------------------------------------------------------
    def _conv_mixer(self, h, layer: Params, state, lengths=None):
        """h [B, T, D] (normed) -> (out [B, T, D], the state after the
        call). ``state`` [B, K-1, D]: ``g`` just before this call."""
        dt, d = self.cfg.dtype, self.cfg.dim
        with jax.named_scope("shortconv_in_proj"):
            bcx = jnp.einsum("btd,de->bte", h, layer["w_in"].astype(dt))
        with jax.named_scope("shortconv_gate_conv"):
            g = bcx[..., :d] * bcx[..., 2 * d:]
            c, state = shortconv.gated_conv(g, state, layer["conv_w"],
                                            lengths)
            y = bcx[..., d:2 * d] * c
        with jax.named_scope("shortconv_out_proj"):
            return jnp.einsum("bte,ed->btd", y,
                              layer["w_out"].astype(dt)), state

    # -- the walk ----------------------------------------------------------------
    def _walk(self, params: Params, x, store, conv_mixer, attn_mixer,
              live=None):
        """The runs of like layers over the kinds' stacks (module
        docstring). ``store``: what the program keeps a layer, a WHOLE
        stack a name, carried through every run; ``conv_mixer(h, layer, j,
        store) -> (out, store)`` and ``attn_mixer`` likewise run layer ``j``
        OF THEIR MIXER (traced inside a run's scan) on its normed input and
        write what they keep at ``j``. An expert layer's ``_ffn`` extras
        (``live``'s rows counted) land in ``store["ffn"]`` at the layer's
        index among the expert layers. -> (x, store)."""
        whole = self.WHOLE_LAYER_LEAVES

        def body_of(kind):
            mixer, ffn = kind.split("_")
            run_mixer = conv_mixer if mixer == "conv" else attn_mixer
            sliced = {k: v for k, v in params[kind].items()
                      if k not in whole}
            # the expert stacks as [L*E, ...], read in place (``moe.py``)
            stacks = None if ffn == "dense" else {
                k: params[kind][k].reshape(
                    (-1,) + params[kind][k].shape[2:]) for k in whole}
            mixer_at = jnp.asarray(self._mixer_at[kind], jnp.int32)
            expert_at = jnp.asarray(self._expert_at.get(kind, [0]),
                                    jnp.int32)

            def body(carry, j):
                x, store = carry
                layer = jax.tree.map(lambda a: self._at(a, j), sliced)
                with jax.named_scope("norm_residual"):
                    h = self._norm(x, layer["norm"])
                out, store = run_mixer(h, layer, mixer_at[j], store)
                with jax.named_scope("norm_residual"):
                    x = x + out
                    h = self._norm(x, layer["ffn_norm"])
                down, extra = self._ffn(h, dict(layer, index=j), live,
                                        stacks=stacks)
                if extra is not None:
                    store = dict(store, ffn={
                        name: self._put(kept, expert_at[j], extra[name])
                        for name, kept in store["ffn"].items()})
                with jax.named_scope("norm_residual"):
                    return (x + down, store), None
            return body

        return self._over_runs(body_of, (x, store))

    def _ffn_zeros(self, B: int, T: int) -> Params:
        """The expert layers' extras before the walk: ``MoEModel._ffn``'s
        names, a row an expert layer."""
        cfg = self.cfg
        Le = cfg.expert_layers
        return {"aux": jnp.zeros((Le,), jnp.float32),
                "load": jnp.zeros((Le, cfg.num_experts), jnp.int32),
                "experts": jnp.zeros((Le, B, T, cfg.expert_top_k),
                                     jnp.int32)}

    def _stored_conv(self, lengths):
        """``_walk``'s conv mixer: from the state ``store`` holds for the
        layer, the state after ``lengths`` (None: all T positions, one in
        a decode step) written back."""
        def mixer(h, layer, j, store):
            out, state = self._conv_mixer(h, layer,
                                          self._at(store["conv"], j), lengths)
            return out, dict(store, conv=self._put(store["conv"], j, state))
        return mixer

    # -- training-style forward ----------------------------------------------------
    def _apply_with_extras(self, params: Params, tokens: jax.Array,
                           positions: Optional[jax.Array] = None):
        B, T = tokens.shape
        logits, _, extras = self.forward_step_counted(
            params, tokens, self.init_kv_cache(B, T),
            jnp.zeros((B,), jnp.int32))
        return logits, extras

    # -- the serving programs ----------------------------------------------------
    def forward_step(self, params: Params, tokens: jax.Array, cache: Params,
                     offsets: jax.Array,
                     lengths: Optional[jax.Array] = None
                     ) -> Tuple[jax.Array, Params]:
        """``LlamaModel.forward_step`` with the state in the cache: each
        row continues from ITS state and stops after ITS ``lengths`` [B]
        tokens of this call (None: all T), so padding behind a row's
        length does not shift the filter's rows. -> (logits [B, T, V], the
        cache after the call)."""
        return self.forward_step_counted(params, tokens, cache, offsets,
                                         lengths)[:2]

    def forward_step_counted(self, params, tokens, cache, offsets,
                             lengths=None):
        """``forward_step`` and, third, the expert layers' extras, as
        ``decode_step_paged_counted`` hands them back: ``experts`` [Le, B,
        T, K] is what each position's router chose."""
        B, T = tokens.shape
        x, store = self._walk(
            params, self._embed(params, tokens),
            dict(cache, ffn=self._ffn_zeros(B, T)),
            self._stored_conv(lengths),
            self._slot_attention(cache, offsets, T))
        extras = store.pop("ffn")
        return self._head(params, x), store, extras

    def prefill_with_prefix(self, params: Params, tokens: jax.Array,
                            prefix_k: jax.Array, prefix_v: jax.Array,
                            prefix_len: jax.Array, lengths: jax.Array,
                            state: Optional[Params] = None
                            ) -> Tuple[jax.Array, Params]:
        """``LlamaModel.prefill_with_prefix`` for a chunk of a prompt: the
        attention layers read the gathered prefix [La, N, Pmax, rows]; the
        conv layers continue from ``state`` (``"conv"`` [Lc, N, K-1, D]:
        what the chunk before handed back; None: the prompt's first
        chunk, zeros). -> (each row's last-token logits [N, V], the
        chunk's K/V rows and the state after its ``lengths`` tokens)."""
        N_, Tb = tokens.shape
        if state is None:
            state = self.init_state(N_)
        store = {**self._kv_zeros(N_, Tb), "conv": state["conv"],
                 "ffn": self._ffn_zeros(N_, Tb)}
        x, small = self._walk(
            params, self._embed(params, tokens), store,
            self._stored_conv(lengths),
            self._prefix_attention(prefix_k, prefix_v, prefix_len, Tb))
        del small["ffn"]
        return self._head(params, x, last=lengths - 1)[:, 0], small

    def decode_step_paged_counted(self, params: Params, tokens: jax.Array,
                                  pool: Params, block_tables: jax.Array,
                                  offsets: jax.Array,
                                  live: Optional[jax.Array] = None,
                                  run: int = 1):
        """One decode step for every slot: the attention layers against
        the block pool (``LlamaModel``'s: the pool as ONE stack ``[La*NB,
        ...]``, layer ``j``'s pages from ``j*NB`` on), the conv layers
        against the slots' state rows, row ``b`` of ``"conv"`` [Lc, B, K-1,
        D] being slot ``b``'s: shifted by one position and written back
        where they lie. A slot that is idle computes on whatever its row
        holds; its next tenant's activation overwrites the row.
        -> (logits [B, V], the pool, the expert layers' extras: ``load``
        [Le, E] of the ``live`` slots, ``experts`` [Le, B, 1, K], ``aux``).
        ``run``: ``LlamaModel.decode_step_paged``'s."""
        B = tokens.shape[0]
        store, attn_mixer = self._paged_attention(pool, block_tables,
                                                  offsets, run)
        store["ffn"] = self._ffn_zeros(B, 1)
        if "conv" in pool:
            if pool["conv"].shape[1] != B:
                raise ValueError(
                    f"the decode batch is one row a slot: {B} tokens for a "
                    f"state of {pool['conv'].shape[1]} rows")
            store["conv"] = pool["conv"]

        x, store = self._walk(params, self._embed(params, tokens[:, None]),
                              store, self._stored_conv(None), attn_mixer,
                              live)
        pool = self._pages_back(pool, store)
        if "conv" in store:
            pool["conv"] = store["conv"]
        return self._head(params, x)[:, 0], pool, store["ffn"]
