"""A hybrid Mamba-1 / attention decoder (published ``jamba``): every layer
is TWO sublayers,

    x <- x + mixer_i(RMSNorm(x));   x <- x + SwiGLU(RMSNorm(x))

and the mixer is attention where ``i % attn_period == attn_offset``, else
Mamba-1 (``ops/ssm1.py``):

    [u | z] = h W_in;  u = silu(conv1d_causal(u));  [r | B | C] = u W_x
    r, B, C = RMSNorm(r), RMSNorm(B), RMSNorm(C)      (three weights)
    dt = softplus(r W_dt + b_dt);  A = -exp(A_log)    [N, inner]
    S_t[n, d] = exp(dt_t[d] A[n, d]) S_{t-1}[n, d] + dt_t[d] B_t[n] u_t[d]
    y_t = sum_n S_t C_t[n] + D u_t;  out = (y silu(z)) W_out

The decay is a number a (state index, channel) pair: no heads, no groups,
no matmul form (``ops/ssm.py`` is Mamba-2's, one decay a head). The
attention layers are MQA/GQA with NO positional encoding (the Mamba
layers carry order); every feed-forward is the dense SwiGLU; the head is
the embedding, tied.

The two kinds are two parameter TREES, a stack a kind (``params["mamba"]``
[Lm, ...] and ``["attn"]`` [La, ...], each with its layers' SwiGLU), and
every program walks the RUNS of like layers (``runs``: for 28 layers with
attention at 7 and 21, 7 Mamba | attention | 13 Mamba | attention | 6
Mamba): a run of several layers is ONE ``lax.scan`` over its indices into
the kind's stack, whose body reads its layer's leaves where they lie, so a
program holds a layer body a run (five here), not one a layer, and
compiles in the time of a handful of layers whatever the depth
(``NemotronHModel._walk`` unrolls its pattern, as its cells' depths
allow). What a program keeps a layer (the K/V rows, the recurrent state)
is carried through the scans WHOLE, a stack a name, and written at the
layer's index in place.

The programs are ``LlamaModel``'s by name and by what the serving engine
hands them (``llm/engine.py``); the cache holds, beside the attention
layers' K/V rows, the RECURRENT STATE: ``"conv"`` [Lm, rows, K-1, inner]
(the convolution runs over ``u`` alone) and ``"ssm"`` [Lm, rows, 1, N,
inner] float32 (``ops.ssm1``'s layout, the state index in the sublanes),
a row a cache row (bucket prefill) or a row a SLOT (``init_kv_pool(..,
slots)``). ``recurrent`` says so to the engine.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models.layer_runs import LayerRuns
from ray_tpu.models.llama import LlamaConfig, LlamaModel, Params
from ray_tpu.ops import ssm, ssm1
from ray_tpu.ops.norms import rms_norm


@dataclasses.dataclass(frozen=True)
class JambaConfig(LlamaConfig):
    """``LlamaConfig``'s widths (``ffn_dim`` every layer's SwiGLU) and the
    Mamba-1 mixer's; layer ``i`` is attention iff ``i % attn_period ==
    attn_offset``."""
    attn_period: int = 14
    attn_offset: int = 7
    mamba_expand: int = 2            # inner = expand x dim
    ssm_state: int = 16              # N
    conv_kernel: int = 4
    dt_rank: int = 160
    # dt at ``init``: log-uniform in ``dt_init`` through ``b_dt``
    dt_init: Tuple[float, float] = (0.001, 0.1)
    tie_embeddings: bool = True
    remat: bool = False

    def __post_init__(self):
        super().__post_init__()
        if (self.conv_kernel < 2 or self.layer_types is not None
                or self.hc_mult != 1 or self.block_length != 1
                or not 0 <= self.attn_offset < self.attn_period):
            raise ValueError(
                f"a convolution over {self.conv_kernel} positions, attention "
                f"at i % {self.attn_period} == {self.attn_offset}, no layer "
                "types, one residual stream, one token a step")

    @property
    def mamba_inner(self) -> int:
        return self.mamba_expand * self.dim

    def is_attention(self, i: int) -> bool:
        return i % self.attn_period == self.attn_offset

    @property
    def attn_layers(self) -> int:
        return sum(self.is_attention(i) for i in range(self.n_layers))

    @property
    def mamba_layers(self) -> int:
        return self.n_layers - self.attn_layers

    def num_params(self) -> int:
        d, f, inner = self.dim, self.ffn_dim, self.mamba_inner
        N, R, K = self.ssm_state, self.dt_rank, self.conv_kernel
        q, kv = self.n_heads * self.head_dim, self.n_kv_heads * self.head_dim
        mamba = (d * 2 * inner + inner * K + inner + inner * (R + 2 * N)
                 + R * inner + inner + inner * N + inner + inner * d
                 + R + 2 * N)
        attn = 2 * d * q + 2 * d * kv
        sublayers = 3 * d * f + 2 * d            # the SwiGLU and two norms
        head = 0 if self.tie_embeddings else self.vocab_size * d
        return (self.mamba_layers * (mamba + sublayers)
                + self.attn_layers * (attn + sublayers)
                + self.vocab_size * d + head + d)

    @staticmethod
    def debug(n_layers: int = 5, vocab_size: int = 256,
              max_seq_len: int = 128, **kw) -> "JambaConfig":
        """Debug widths: attention at layer 1 of every 3; a state of 6 and
        a ``dt`` bottleneck of 5, which divide nothing conveniently."""
        base = dict(
            vocab_size=vocab_size, dim=32, n_layers=n_layers, n_heads=4,
            n_kv_heads=1, head_dim=8, ffn_dim=48, max_seq_len=max_seq_len,
            norm_eps=1e-6, attn_period=3, attn_offset=1, ssm_state=6,
            dt_rank=5, dtype=jnp.float32)
        return JambaConfig(**{**base, **kw})


class JambaModel(LayerRuns, LlamaModel):
    """``LlamaModel``'s embedding, norms, SwiGLU, head and sampler around a
    walk of the runs of like layers over two stacks (module docstring;
    ``LayerRuns`` has the runs' loop and the attention layers' side of
    the serving programs). No mesh: the kinds' stacks and the state have
    no partitioning rule yet."""

    # what ``serving_params`` casts to the compute dtype, either stack;
    # ``A_log``, ``D``, ``b_dt`` and every norm are used in float32 and
    # stay float32
    MATMUL_LEAVES = ("w_in", "conv_w", "conv_b", "w_x", "w_dt", "w_out",
                     "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")

    def __init__(self, cfg: JambaConfig, mesh=None,
                 rules: Optional[Dict] = None):
        if mesh is not None:
            raise NotImplementedError(
                "a hybrid state-space model runs on one chip: its stacks a "
                "kind and its recurrent state carry no partitioning rule")
        self.cfg = cfg
        self.mesh = self.rules = None
        self._sp = self._ep = 1
        self.eva = self.layer_kinds = None
        # (kind, first index in the kind's stack, layers) a run
        self.runs: List[Tuple[str, int, int]] = []
        seen = {"mamba": 0, "attn": 0}
        for i in range(cfg.n_layers):
            kind = "attn" if cfg.is_attention(i) else "mamba"
            if self.runs and self.runs[-1][0] == kind:
                self.runs[-1] = (kind, self.runs[-1][1], self.runs[-1][2] + 1)
            else:
                self.runs.append((kind, seen[kind], 1))
            seen[kind] += 1

    # -- what the engine asks ------------------------------------------------
    @property
    def recurrent(self) -> bool:
        """The cache holds a fixed-size state a row beside the K/V rows."""
        return self.cfg.mamba_layers > 0

    def state_row_shapes(self) -> Dict[str, Tuple[Tuple[int, ...], Any]]:
        """One row's recurrent state a Mamba layer: name -> (shape,
        dtype); what follows ``[Lm, rows]`` in the cache. ``S`` is held
        in float32 (``NemotronHModel``'s precedent and its reason: a
        rounded state that decays by less than half an ulp a step stands
        still)."""
        cfg = self.cfg
        return {
            "conv": ((cfg.conv_kernel - 1, cfg.mamba_inner), cfg.dtype),
            "ssm": ((1, cfg.ssm_state, cfg.mamba_inner), jnp.float32)}

    def init_state(self, rows: int) -> Params:
        return {name: jnp.zeros((self.cfg.mamba_layers, rows) + shape, dtype)
                for name, (shape, dtype) in self.state_row_shapes().items()}

    def state_update_impl(self) -> str:
        """What advances the state in a decode step, for an engine's
        ``decode_attention_impl``: the kernel or its twin, as the
        attention's."""
        return f"ssm_{self.paged_decode_impl()}"

    def state_heads(self, state: jax.Array) -> jax.Array:
        """``"ssm"`` rows ``[..., 1, N, inner]`` as ``[..., N, inner, 1]``:
        Mamba-1 has no heads; a comparison a "head" is one a STATE INDEX
        ``n``, a row of ``A``, which keeps the entries that decay slowest
        (``A[0]``, the smallest rate at ``init``) apart from the loud
        fast ones."""
        return jnp.squeeze(state, axis=-3)[..., None]

    # -- init -------------------------------------------------------------------
    def init(self, rng: jax.Array) -> Params:
        cfg = self.cfg
        d, f, dense = cfg.dim, cfg.ffn_dim, self._dense
        inner, N, R = cfg.mamba_inner, cfg.ssm_state, cfg.dt_rank
        k = iter(jax.random.split(rng, 40))
        params: Params = {"embed": dense(next(k), (cfg.vocab_size, d), d),
                          "norm_f": jnp.ones((d,), jnp.float32)}
        if not cfg.tie_embeddings:
            params["lm_head"] = dense(next(k), (d, cfg.vocab_size), d)

        def sublayers(L):       # a layer's second sublayer and both norms
            return {"norm": jnp.ones((L, d), jnp.float32),
                    "ffn_norm": jnp.ones((L, d), jnp.float32),
                    "w_gate": dense(next(k), (L, d, f), d),
                    "w_up": dense(next(k), (L, d, f), d),
                    "w_down": dense(next(k), (L, f, d), f)}

        def drawn_norm(L, n):   # not ones: a program that drops it differs
            return 1.0 + 0.25 * jax.random.normal(next(k), (L, n),
                                                  jnp.float32)

        Lm, La = cfg.mamba_layers, cfg.attn_layers
        if Lm:
            lo, hi = cfg.dt_init
            dt = jnp.exp(jax.random.uniform(
                next(k), (Lm, inner), jnp.float32, jnp.log(lo), jnp.log(hi)))
            params["mamba"] = {
                **sublayers(Lm),
                "w_in": dense(next(k), (Lm, d, 2 * inner), d),
                "conv_w": dense(next(k), (Lm, inner, cfg.conv_kernel),
                                cfg.conv_kernel),
                # drawn, not zero: a program that drops it must differ
                "conv_b": 0.1 * jax.random.normal(next(k), (Lm, inner),
                                                  jnp.float32),
                "w_x": dense(next(k), (Lm, inner, R + 2 * N), inner),
                "dt_norm": drawn_norm(Lm, R),
                "b_norm": drawn_norm(Lm, N),
                "c_norm": drawn_norm(Lm, N),
                "w_dt": dense(next(k), (Lm, R, inner), R),
                # softplus(b_dt) = dt
                "b_dt": dt + jnp.log(-jnp.expm1(-dt)),
                # A[n, d] = -(n + 1), a channel: the family's init
                "A_log": jnp.broadcast_to(
                    jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32))[
                        None, :, None], (Lm, N, inner)),
                "D": 1.0 + 0.1 * jax.random.normal(next(k), (Lm, inner),
                                                   jnp.float32),
                "w_out": dense(next(k), (Lm, inner, d), inner)}
        if La:
            params["attn"] = {**sublayers(La), **self._init_attention(k, La)}
        return params

    def serving_params(self, params: Params) -> Params:
        dt = self.cfg.dtype

        def cast(a):
            return a if a.dtype == dt else a.astype(dt)

        out = {k: cast(v) if k in ("embed", "lm_head") else v
               for k, v in params.items()}
        for stack in ("mamba", "attn"):
            if stack in params:
                out[stack] = {k: cast(v) if k in self.MATMUL_LEAVES else v
                              for k, v in params[stack].items()}
        return out

    def param_shardings(self):
        raise NotImplementedError("no mesh (see the class docstring)")

    # -- the two mixers --------------------------------------------------------
    def _rope(self, x, positions, kind):
        return x            # the family's attention turns nothing

    def _mamba(self, h, layer: Params, window, scan, lengths=None):
        """h [B, T, D] (normed) -> (out [B, T, D], the convolution's window
        after the call, ``scan``'s second result). ``window`` [B, K-1,
        inner]: the convolution's inputs just before this call; ``scan(u
        [B, T, inner], dt [B, T, inner] float32, a [N, inner], Bm, Cm [B,
        T, N] float32) -> (y [B, T, inner] float32 without D u,
        anything)``: the calling program's recurrence, which knows where
        ``S`` is kept."""
        cfg = self.cfg
        dt_, f32 = cfg.dtype, jnp.float32
        inner, N, R = cfg.mamba_inner, cfg.ssm_state, cfg.dt_rank
        with jax.named_scope("ssm1_in_proj"):
            uz = jnp.einsum("btd,de->bte", h, layer["w_in"].astype(dt_))
            u, z = uz[..., :inner], uz[..., inner:]
        with jax.named_scope("ssm1_conv"):
            u, window = ssm.causal_conv(u, window, layer["conv_w"],
                                        layer["conv_b"], lengths)
        with jax.named_scope("ssm1_x_proj"):
            rbc = jnp.einsum("bte,er->btr", u, layer["w_x"].astype(dt_),
                             preferred_element_type=f32)
            r = rms_norm(rbc[..., :R], layer["dt_norm"], eps=cfg.norm_eps)
            Bm = rms_norm(rbc[..., R:R + N], layer["b_norm"],
                          eps=cfg.norm_eps)
            Cm = rms_norm(rbc[..., R + N:], layer["c_norm"],
                          eps=cfg.norm_eps)
            dt = jax.nn.softplus(jnp.einsum(
                "btr,re->bte", r.astype(dt_), layer["w_dt"].astype(dt_),
                preferred_element_type=f32) + layer["b_dt"])
            a = -jnp.exp(layer["A_log"].astype(f32))
        y, carried = scan(u, dt, a, Bm, Cm)
        with jax.named_scope("ssm1_gate_out_proj"):
            y = ((y + layer["D"] * u.astype(f32))
                 * jax.nn.silu(z.astype(f32))).astype(dt_)
            out = jnp.einsum("bte,ed->btd", y, layer["w_out"].astype(dt_))
        return out, window, carried

    # -- the walk ----------------------------------------------------------------
    def _walk(self, params: Params, x, store, mamba_mixer, attn_mixer):
        """The runs of like layers over the two stacks (module docstring).
        ``store``: what the program keeps a layer, a WHOLE stack a name,
        carried through every run; ``mamba_mixer(h, layer, j, store) ->
        (out, store)`` and ``attn_mixer`` likewise run layer ``j`` OF
        THEIR KIND (``j`` traced inside a run's scan) on its normed input
        and write what they keep at ``j``. -> (x, store)."""
        mixers = {"mamba": mamba_mixer, "attn": attn_mixer}

        def body_of(kind):
            def body(carry, j):
                x, store = carry
                layer = jax.tree.map(lambda a: self._at(a, j), params[kind])
                with jax.named_scope("norm_residual"):
                    h = self._norm(x, layer["norm"])
                out, store = mixers[kind](h, layer, j, store)
                with jax.named_scope("norm_residual"):
                    x = x + out
                    h = self._norm(x, layer["ffn_norm"])
                down, _ = self._ffn(h, layer)
                with jax.named_scope("norm_residual"):
                    return (x + down, store), None
            return body

        return self._over_runs(body_of, (x, store))

    def _prefill_mamba(self, lengths):
        """``_walk``'s Mamba mixer for a prefill: the scan from the state
        ``store`` holds for the layer, the state after it written back."""
        impl = self.paged_decode_impl()

        def mixer(h, layer, j, store):
            def scan(u, dt, a, Bm, Cm):
                with jax.named_scope("ssm1_scan"):
                    return ssm1.selective_scan(
                        u, dt, a, Bm, Cm, self._at(store["ssm"], j)[:, 0],
                        lengths, impl=impl)

            out, window, S = self._mamba(h, layer,
                                         self._at(store["conv"], j), scan,
                                         lengths)
            return out, dict(store,
                             conv=self._put(store["conv"], j, window),
                             ssm=self._put(store["ssm"], j, S[:, None]))
        return mixer

    # -- training-style forward ----------------------------------------------------
    def _apply_with_extras(self, params: Params, tokens: jax.Array,
                           positions: Optional[jax.Array] = None):
        B, T = tokens.shape
        logits, _ = self.forward_step(params, tokens,
                                      self.init_kv_cache(B, T),
                                      jnp.zeros((B,), jnp.int32))
        return logits, None

    # -- the serving programs ----------------------------------------------------
    def forward_step(self, params: Params, tokens: jax.Array, cache: Params,
                     offsets: jax.Array,
                     lengths: Optional[jax.Array] = None
                     ) -> Tuple[jax.Array, Params]:
        """``LlamaModel.forward_step`` with the state in the cache: each
        row continues from ITS state and stops after ITS ``lengths`` [B]
        tokens of this call (None: all T), so padding behind a row's
        length neither advances ``S`` nor shifts the convolution's
        window. -> (logits [B, T, V], the cache after the call)."""
        x, cache = self._walk(
            params, self._embed(params, tokens), dict(cache),
            self._prefill_mamba(lengths),
            self._slot_attention(cache, offsets, tokens.shape[1]))
        return self._head(params, x), cache

    def prefill_with_prefix(self, params: Params, tokens: jax.Array,
                            prefix_k: jax.Array, prefix_v: jax.Array,
                            prefix_len: jax.Array, lengths: jax.Array,
                            state: Optional[Params] = None
                            ) -> Tuple[jax.Array, Params]:
        """``LlamaModel.prefill_with_prefix`` for a chunk of a prompt:
        the attention layers read the gathered prefix [La, N, Pmax, Hkv,
        D]; the Mamba layers continue from ``state`` (``"conv"`` /
        ``"ssm"`` [Lm, N, ...]: what the chunk before handed back; None:
        the prompt's first chunk, zeros). -> (each row's last-token
        logits [N, V], the chunk's K/V rows and the state after its
        ``lengths`` tokens)."""
        N_, Tb = tokens.shape
        if state is None:
            state = self.init_state(N_)
        store = {**self._kv_zeros(N_, Tb),
                 **{name: state[name] for name in ("conv", "ssm")}}
        x, small = self._walk(
            params, self._embed(params, tokens), store,
            self._prefill_mamba(lengths),
            self._prefix_attention(prefix_k, prefix_v, prefix_len, Tb))
        return self._head(params, x, last=lengths - 1)[:, 0], small

    def decode_step_paged_counted(self, params: Params, tokens: jax.Array,
                                  pool: Params, block_tables: jax.Array,
                                  offsets: jax.Array,
                                  live: Optional[jax.Array] = None,
                                  run: int = 1):
        """One decode step for every slot: the attention layers against
        the block pool (``LlamaModel``'s: the pool as ONE stack ``[La*NB,
        ...]``, layer ``j``'s pages from ``j*NB`` on), the Mamba layers
        against the slots' state rows, row ``b`` of ``"conv"`` / ``"ssm"``
        [Lm, B, ...] being slot ``b``'s: read, advanced by one position
        and written back where they lie (``ops.ssm1.state_update`` takes
        the stack of ``S`` whole and the layer's index). A slot that is
        idle computes on whatever its row holds; its next tenant's
        activation overwrites the row.
        -> (logits [B, V], the pool, None: the dense SwiGLU counts
        nothing).
        ``run``: ``LlamaModel.decode_step_paged``'s."""
        B = tokens.shape[0]
        impl = self.paged_decode_impl()
        store, attn_mixer = self._paged_attention(pool, block_tables,
                                                  offsets, run)
        if "ssm" in pool:
            if pool["ssm"].shape[1] != B:
                raise ValueError(
                    f"the decode batch is one row a slot: {B} tokens for a "
                    f"state of {pool['ssm'].shape[1]} rows")
            store["ssm"] = jnp.squeeze(pool["ssm"], axis=2)  # [Lm, B, N, W]
            store["conv"] = pool["conv"]

        def mamba_mixer(h, layer, j, store):
            def scan(u, dt, a, Bm, Cm):
                with jax.named_scope("ssm1_state_update"):
                    dt1 = dt[:, 0]
                    stack, y = ssm1.state_update(
                        store["ssm"], j, a, dt1,
                        dt1 * u[:, 0].astype(jnp.float32), Bm[:, 0],
                        Cm[:, 0], impl=impl)
                return y[:, None], stack

            out, window, stack = self._mamba(
                h, layer, self._at(store["conv"], j), scan)
            return out, dict(store, ssm=stack,
                             conv=self._put(store["conv"], j, window))

        x, store = self._walk(params, self._embed(params, tokens[:, None]),
                              store, mamba_mixer, attn_mixer)
        pool = self._pages_back(pool, store)
        if "ssm" in store:
            pool["ssm"] = store["ssm"][:, :, None]
            pool["conv"] = store["conv"]
        return self._head(params, x)[:, 0], pool, None
