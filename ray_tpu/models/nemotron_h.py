"""A hybrid state-space decoder (published ``nemotron_h``): every layer
is ONE sublayer, ``x <- x + mixer(RMSNorm(x))``, and a PATTERN string
says which, a letter a layer:

- ``M``, Mamba-2 (``ops/ssm.py``): an input projection to ``[z | xBC |
  dt]``, a depthwise causal convolution over ``xBC``'s last
  ``conv_kernel`` positions, the recurrence ``S_t = exp(dt_t a) S_{t-1}
  + dt_t x_t (x) B_t``, ``y_t = S_t C_t + D x_t``, the gate ``y
  silu(z)``, an RMS norm over each group's lanes apart, an output
  projection. What the layer carries from one call to the next is of
  FIXED size: the convolution's last ``conv_kernel - 1`` inputs and
  ``S``, which is held in float32.
- ``*``, attention: GQA, causal, NO positional encoding (the family's
  attention layers turn nothing; the Mamba layers carry order).
- ``E``, experts in a LATENT: the router (sigmoid, a selection bias,
  top-k renormalised and scaled: ``ops.moe_dispatch.route_topk``) and a
  shared expert read the hidden state; the routed experts read ``h
  W_down`` (``latent_dim`` wide), are NOT gated (``relu(u W1)^2 W2``)
  and their weighted sum goes back through ``W_up``.

The three kinds are three parameter TREES: a stack a kind
(``params["mamba"]``, ``["attn"]``, ``["moe"]``), and every program walks
the pattern over them, layer ``i`` taking the next slice of its kind's
stack (``_walk``: a Python loop, so the pattern may be ANY string over
the three letters; the programs' size grows with depth, as the cells'
depths allow). The programs are ``LlamaModel``'s by name and by what the
serving engine hands them (``llm/engine.py``), with one more thing in
the cache beside the attention layers' K/V rows: the RECURRENT STATE,
``"conv"`` [Lm, rows, K-1, C] and ``"ssm"`` [Lm, rows, G, N, hg*P]
(``ops.ssm``'s layout), a row a cache row (bucket prefill, ``forward_step``)
or a row a SLOT (``init_kv_pool(.., slots)``: the decode step rewrites
every slot's row in place). ``recurrent`` says so to the engine.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models.llama import LlamaModel, Params
from ray_tpu.models.moe import MoEConfig
from ray_tpu.ops import ssm
from ray_tpu.ops.norms import rms_norm

KINDS = {"M": "mamba", "*": "attn", "E": "moe"}


@dataclasses.dataclass(frozen=True)
class NemotronHConfig(MoEConfig):
    """``MoEConfig``'s router, share (``experts_held``) and shared expert
    as they are; ``ffn_dim`` is one routed expert's width, ``n_layers``
    follows from ``pattern``."""
    pattern: str = "ME*E"
    mamba_heads: int = 8
    mamba_head_dim: int = 16
    ssm_groups: int = 2              # heads ``j`` use group ``j // (H / G)``
    ssm_state: int = 16              # N
    conv_kernel: int = 4
    scan_chunk: int = 128
    latent_dim: int = 32             # the routed experts' input and output
    # A and dt at ``init``, as the family's initialisation draws them:
    # A uniform in ``a_init``, dt log-uniform in ``dt_init``
    a_init: Tuple[float, float] = (1.0, 16.0)
    dt_init: Tuple[float, float] = (0.001, 0.1)

    def __post_init__(self):
        object.__setattr__(self, "n_layers", len(self.pattern))
        object.__setattr__(self, "router_kind", "sigmoid")
        super().__post_init__()
        if not self.pattern or set(self.pattern) - set(KINDS):
            raise ValueError(
                f"pattern is a string over {sorted(KINDS)}, one letter a "
                f"layer, got {self.pattern!r}")
        if (self.mamba_heads % self.ssm_groups or self.conv_kernel < 2
                or self.layer_types is not None or self.leading_layers
                or self.hc_mult != 1):
            raise ValueError(
                f"{self.mamba_heads} heads in {self.ssm_groups} groups, a "
                f"convolution over {self.conv_kernel} positions, no layer "
                "types, no leading layers and one residual stream (the "
                "walk adds a block's output to x itself)")

    @property
    def mamba_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_channels(self) -> int:
        """x, B and C side by side: what the convolution runs over."""
        return self.mamba_inner + 2 * self.ssm_groups * self.ssm_state

    def count(self, letter: str) -> int:
        return self.pattern.count(letter)

    def num_params(self) -> int:
        d, f, l = self.dim, self.ffn_dim, self.latent_dim
        inner, C, H = self.mamba_inner, self.conv_channels, self.mamba_heads
        q, kv = self.n_heads * self.head_dim, self.n_kv_heads * self.head_dim
        mamba = (d * (inner + C + H) + C * self.conv_kernel + C + 3 * H
                 + inner + inner * d + d)
        attn = 2 * d * q + 2 * d * kv + d
        moe = (d * self.num_experts + self.num_experts + 2 * d * l
               + self.held[1] * 2 * l * f + 2 * d * self.shared_ffn_dim + d)
        return (self.count("M") * mamba + self.count("*") * attn
                + self.count("E") * moe + 2 * self.vocab_size * d + d)

    @staticmethod
    def debug_hybrid(pattern: str = "ME*EM", vocab_size: int = 256,
                     max_seq_len: int = 128, **kw) -> "NemotronHConfig":
        base = dict(
            vocab_size=vocab_size, dim=32, n_heads=4, n_kv_heads=2,
            head_dim=8, ffn_dim=24, max_seq_len=max_seq_len, remat=False,
            pattern=pattern, mamba_heads=8, mamba_head_dim=8, ssm_groups=2,
            ssm_state=16, scan_chunk=8, latent_dim=16, num_experts=8,
            expert_top_k=3, shared_ffn_dim=40, routed_scaling_factor=2.5,
            router_bias_init_std=0.1, dtype=jnp.float32)
        return NemotronHConfig(**{**base, **kw})


class NemotronHModel(LlamaModel):
    """``LlamaModel``'s embedding, norms and head around a walk of the
    pattern over three stacks (module docstring). No mesh: the kinds'
    stacks and the state have no partitioning rule yet."""

    # what ``serving_params`` casts to the compute dtype, a stack each;
    # ``A_log``, ``D``, ``dt_bias``, the norms (``gnorm`` too), the router
    # and its bias are used in float32 and stay float32
    MATMUL_LEAVES = {
        "mamba": ("w_in", "conv_w", "conv_b", "w_out"),
        "attn": ("wq", "wk", "wv", "wo"),
        "moe": ("w_lat_down", "w_lat_up", "e_up", "e_down", "s_up",
                "s_down")}

    def __init__(self, cfg: NemotronHConfig, mesh=None,
                 rules: Optional[Dict] = None):
        if mesh is not None:
            raise NotImplementedError(
                "a hybrid state-space model runs on one chip: its stacks a "
                "kind and its recurrent state carry no partitioning rule")
        self.cfg = cfg
        self.mesh = self.rules = None
        self._sp = self._ep = 1
        self.eva = self.layer_kinds = None
        # (letter, index in its kind's stack) a layer
        seen = {k: 0 for k in KINDS}
        self.layers = []
        for letter in cfg.pattern:
            self.layers.append((letter, seen[letter]))
            seen[letter] += 1

    # -- what the engine asks ------------------------------------------------
    @property
    def recurrent(self) -> bool:
        """The cache holds a fixed-size state a row beside the K/V rows."""
        return self.cfg.count("M") > 0

    def state_row_shapes(self) -> Dict[str, Tuple[Tuple[int, ...], Any]]:
        """One row's recurrent state a Mamba layer: name -> (shape,
        dtype); what follows ``[Lm, rows]`` in the cache. ``S`` is held
        in float32, as the published serving command holds it
        (``--mamba_ssm_cache_dtype float32``)."""
        cfg = self.cfg
        return {
            "conv": ((cfg.conv_kernel - 1, cfg.conv_channels), cfg.dtype),
            "ssm": ((cfg.ssm_groups, cfg.ssm_state,
                     cfg.mamba_inner // cfg.ssm_groups), jnp.float32)}

    def init_state(self, rows: int) -> Params:
        return {name: jnp.zeros((self.cfg.count("M"), rows) + shape, dtype)
                for name, (shape, dtype) in self.state_row_shapes().items()}

    def state_update_impl(self) -> str:
        """What advances the state in a decode step, for an engine's
        ``decode_attention_impl``: the kernel or its twin, as the
        attention's."""
        return f"ssm_{self.paged_decode_impl()}"

    def state_heads(self, state: jax.Array) -> jax.Array:
        """``"ssm"`` rows as ``[..., H, P, N]``, a head's ``S`` as the
        equations write it."""
        return ssm.state_to_heads(state, self.cfg.mamba_head_dim)

    def ffn_load_shape(self) -> Optional[Tuple[int, int]]:
        n = self.cfg.count("E")
        return (n, self.cfg.num_experts) if n else None

    def grouped_matmul_plan(self, tokens: int) -> Dict[str, str]:
        plan = LlamaModel.grouped_matmul_plan(self, tokens)
        if not self.cfg.count("E"):
            return plan
        from ray_tpu.ops.moe_dispatch import grouped_matmul_impl
        cfg = self.cfg
        m, l, f = tokens * cfg.expert_top_k, cfg.latent_dim, cfg.ffn_dim
        itemsize = jnp.dtype(cfg.dtype).itemsize
        chosen = {"up": grouped_matmul_impl(m, l, f, itemsize),
                  "down": grouped_matmul_impl(m, f, l, itemsize)}
        plan["moe_grouped_impl"] = "+".join(sorted(
            {impl for impl, _ in chosen.values()}))
        for name, (_, tiling) in chosen.items():
            if tiling is not None:
                plan[f"moe_gmm_tiling_{name}"] = "x".join(map(str, tiling))
        return plan

    # -- init -------------------------------------------------------------------
    def init(self, rng: jax.Array) -> Params:
        cfg = self.cfg
        d, dense = cfg.dim, self._dense
        k = iter(jax.random.split(rng, 32))
        Lm, La, Le = (cfg.count(c) for c in "M*E")
        inner, C, H = cfg.mamba_inner, cfg.conv_channels, cfg.mamba_heads
        params: Params = {
            "embed": dense(next(k), (cfg.vocab_size, d), d),
            "norm_f": jnp.ones((d,), jnp.float32),
            "lm_head": dense(next(k), (d, cfg.vocab_size), d)}
        if Lm:
            lo, hi = cfg.dt_init
            dt = jnp.exp(jax.random.uniform(
                next(k), (Lm, H), jnp.float32, jnp.log(lo), jnp.log(hi)))
            params["mamba"] = {
                "norm": jnp.ones((Lm, d), jnp.float32),
                "w_in": dense(next(k), (Lm, d, inner + C + H), d),
                "conv_w": dense(next(k), (Lm, C, cfg.conv_kernel),
                                cfg.conv_kernel),
                # drawn, not zero: a program that drops it must differ
                "conv_b": 0.1 * jax.random.normal(next(k), (Lm, C),
                                                  jnp.float32),
                # softplus(dt_bias) = dt
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "A_log": jnp.log(jax.random.uniform(
                    next(k), (Lm, H), jnp.float32, *cfg.a_init)),
                "D": 1.0 + 0.1 * jax.random.normal(next(k), (Lm, H),
                                                   jnp.float32),
                "gnorm": jnp.ones((Lm, inner), jnp.float32),
                "w_out": dense(next(k), (Lm, inner, d), inner)}
        if La:
            params["attn"] = {"norm": jnp.ones((La, d), jnp.float32),
                              **self._init_attention(k, La)}
        if Le:
            E, held = cfg.num_experts, cfg.held[1]
            l, f, fs = cfg.latent_dim, cfg.ffn_dim, cfg.shared_ffn_dim
            params["moe"] = {
                "norm": jnp.ones((Le, d), jnp.float32),
                "router": 0.02 * jax.random.normal(next(k), (Le, d, E),
                                                   jnp.float32),
                "router_bias": cfg.router_bias_init_std * jax.random.normal(
                    next(k), (Le, E), jnp.float32),
                "w_lat_down": dense(next(k), (Le, d, l), d),
                "w_lat_up": dense(next(k), (Le, l, d), l),
                "e_up": dense(next(k), (Le, held, l, f), l),
                "e_down": dense(next(k), (Le, held, f, l), f),
                "s_up": dense(next(k), (Le, d, fs), d),
                "s_down": dense(next(k), (Le, fs, d), fs)}
        return params

    def serving_params(self, params: Params) -> Params:
        dt = self.cfg.dtype

        def cast(a):
            return a if a.dtype == dt else a.astype(dt)

        out = {k: cast(v) if k in ("embed", "lm_head") else v
               for k, v in params.items()}
        for stack, names in self.MATMUL_LEAVES.items():
            if stack in params:
                out[stack] = {k: cast(v) if k in names else v
                              for k, v in params[stack].items()}
        return out

    def param_shardings(self):
        raise NotImplementedError("no mesh (see the class docstring)")

    # -- the three mixers ------------------------------------------------------
    def _rope(self, x, positions, kind):
        return x            # the family's attention turns nothing

    def _mamba(self, h, layer: Params, window, scan, lengths=None):
        """h [B, T, D] (normed) -> (out [B, T, D], the convolution's
        window after the call, ``scan``'s second result). ``window`` [B,
        K-1, C]: the convolution's inputs just before this call;
        ``scan(x [B,T,H,P], dt [B,T,H], a [H], Bm, Cm [B,T,G,N]) -> (y
        [B,T,H,P] float32 without D x, anything)``: the calling
        program's recurrence, which knows where ``S`` is kept."""
        cfg = self.cfg
        dt_, f32 = cfg.dtype, jnp.float32
        B, T, _ = h.shape
        inner, H, P = cfg.mamba_inner, cfg.mamba_heads, cfg.mamba_head_dim
        G, N = cfg.ssm_groups, cfg.ssm_state
        with jax.named_scope("ssm_in_proj"):
            zxd = jnp.einsum("btd,de->bte", h, layer["w_in"].astype(dt_))
            z = zxd[..., :inner]
            xbc = zxd[..., inner:inner + cfg.conv_channels]
            dt = jax.nn.softplus(zxd[..., inner + cfg.conv_channels:].astype(
                f32) + layer["dt_bias"])
        with jax.named_scope("ssm_conv"):
            xbc, window = ssm.causal_conv(xbc, window, layer["conv_w"],
                                          layer["conv_b"], lengths)
        x = xbc[..., :inner].reshape(B, T, H, P)
        Bm = xbc[..., inner:inner + G * N].reshape(B, T, G, N)
        Cm = xbc[..., inner + G * N:].reshape(B, T, G, N)
        y, carried = scan(x, dt, -jnp.exp(layer["A_log"]), Bm, Cm)
        with jax.named_scope("ssm_gated_norm"):
            y = y + layer["D"][:, None] * x.astype(f32)
            y = y.reshape(B, T, inner) * jax.nn.silu(z.astype(f32))
            # an RMS norm over each group's lanes apart
            y = rms_norm(y.reshape(B, T, G, inner // G),
                         jnp.ones((inner // G,), f32), eps=cfg.norm_eps)
            y = (y.reshape(B, T, inner) * layer["gnorm"]).astype(dt_)
        with jax.named_scope("ssm_out_proj"):
            out = jnp.einsum("bte,ed->btd", y, layer["w_out"].astype(dt_))
        return out, window, carried

    def _attention_mixer(self, h, layer: Params, positions, attend):
        dt = self.cfg.dtype
        with jax.named_scope("attention"):
            q, k, v = self._qkv(h, layer, positions, None,
                                lambda a, *names: a)
        o, kv = attend(q, k, v)
        with jax.named_scope("attention"):
            return jnp.einsum("bshk,hkd->bsd", o, layer["wo"].astype(dt)), kv

    def _experts(self, h, layer: Params, stacks: Params, index: int,
                 live=None):
        """h [B, T, D] (normed) -> (out, {"load": [E], "experts": [B, T,
        K], "aux"}). ``stacks``: every expert layer's ``e_up`` /
        ``e_down`` as ``[Le*held, ...]``, read in place from group
        ``index * held`` on (``dropless_expert_ffn(first_expert=)``)."""
        from ray_tpu.ops.moe_dispatch import dropless_expert_ffn
        cfg = self.cfg
        dt = cfg.dtype
        B, T, D = h.shape
        with jax.named_scope("moe_latent_down"):
            u = jnp.einsum("btd,dl->btl", h, layer["w_lat_down"].astype(dt))
        out, load, experts, aux = dropless_expert_ffn(
            h.reshape(B * T, D), layer["router"], None, stacks["e_up"],
            stacks["e_down"], expert_input=u.reshape(B * T, -1),
            top_k=cfg.expert_top_k, norm_topk_prob=cfg.norm_topk_prob,
            dtype=dt, live=None if live is None else jnp.repeat(live, T),
            first_expert=index * cfg.held[1],
            sigmoid_bias=layer["router_bias"],
            weight_scale=cfg.routed_scaling_factor,
            held=None if cfg.experts_held is None else cfg.held)
        with jax.named_scope("moe_latent_up"):
            out = jnp.einsum("btl,ld->btd", out.reshape(B, T, -1),
                             layer["w_lat_up"].astype(dt))
        with jax.named_scope("moe_shared_expert"):
            act = jnp.square(jax.nn.relu(jnp.einsum(
                "btd,df->btf", h, layer["s_up"].astype(dt))))
            out = out + jnp.einsum("btf,fd->btd", act,
                                   layer["s_down"].astype(dt))
        return out, {"load": load, "aux": aux,
                     "experts": experts.reshape(B, T, cfg.expert_top_k)}

    # -- the walk ----------------------------------------------------------------
    def _walk(self, params: Params, x, positions, attend_of, scan_of,
              live=None, lengths=None):
        """The pattern over the three stacks. ``attend_of(j)`` is
        ``LlamaModel._layer``'s ``attend`` for attention layer ``j``;
        ``scan_of(j) -> (window, scan)`` the convolution's window before
        the call and ``_mamba``'s ``scan`` for Mamba layer ``j``.
        -> (x, {"attn": [what each attend handed back], "mamba":
        [(window, scan's second result)], "moe": [extras]})."""
        stacks = None
        if "moe" in params:
            stacks = {name: params["moe"][name].reshape(
                (-1,) + params["moe"][name].shape[2:])
                for name in ("e_up", "e_down")}
        outs = {"attn": [], "mamba": [], "moe": []}
        for letter, j in self.layers:
            kind = KINDS[letter]
            layer = {name: a[j] for name, a in params[kind].items()
                     if name not in ("e_up", "e_down")}
            with jax.named_scope("norm_residual"):
                h = self._norm(x, layer["norm"])
            if letter == "M":
                window, scan = scan_of(j)
                out, window, carried = self._mamba(h, layer, window, scan,
                                                   lengths)
                outs[kind].append((window, carried))
            elif letter == "*":
                out, kv = self._attention_mixer(h, layer, positions,
                                                attend_of(j))
                outs[kind].append(kv)
            else:
                out, extra = self._experts(h, layer, stacks, j, live)
                outs[kind].append(extra)
            with jax.named_scope("norm_residual"):
                x = x + out
        return x, outs

    def _chunked(self, state, lengths):
        """``_mamba``'s ``scan`` for a prefill: the chunked form from
        ``state`` [B, G, N, W], handing the state after the call on."""
        cfg = self.cfg

        def scan(x, dt, a, Bm, Cm):
            with jax.named_scope("ssm_scan"):
                return ssm.chunked_scan(
                    x, dt, a, Bm, Cm, state, chunk=cfg.scan_chunk,
                    lengths=lengths, dtype=cfg.dtype)

        return scan

    @staticmethod
    def _stack_state(mamba_outs, like: Params) -> Params:
        if not mamba_outs:
            return {}
        return {"conv": jnp.stack([w for w, _ in mamba_outs]),
                "ssm": jnp.stack([s for _, s in mamba_outs]).astype(
                    like["ssm"].dtype)}

    @staticmethod
    def _stack_extras(moe_outs):
        if not moe_outs:
            return None
        return {k: jnp.stack([e[k] for e in moe_outs]) for k in moe_outs[0]}

    # -- training-style forward ----------------------------------------------------
    def _apply_with_extras(self, params: Params, tokens: jax.Array,
                           positions: Optional[jax.Array] = None):
        B, T = tokens.shape
        zero = self.init_state(B)
        pos = jnp.broadcast_to(jnp.arange(T)[None], (B, T))

        def attend_of(j):
            def attend(q, k, v):
                with jax.named_scope("attention"):
                    return self._attend_rows(q, k, v, None, pos,
                                             jnp.arange(T)), None
            return attend

        def scan_of(j):
            return zero["conv"][j], self._chunked(zero["ssm"][j], None)

        x, outs = self._walk(params, self._embed(params, tokens), pos,
                             attend_of, scan_of)
        return self._head(params, x), self._stack_extras(outs["moe"])

    # -- the serving programs ----------------------------------------------------
    def _kv_zeros(self, *leading: int) -> Params:
        return {name: jnp.zeros((self.cfg.count("*"),) + leading + row,
                                self.kv_dtype)
                for name, row in zip(("k", "v"), self.kv_row_shapes())}

    def init_kv_cache(self, batch: int, max_seq: int) -> Params:
        """Slot-major cache: k/v [La, B, S, Hkv, D] of the attention
        layers and the recurrent state a row."""
        return {**self._kv_zeros(batch, max_seq), **self.init_state(batch)}

    def init_kv_pool(self, num_blocks: int, block_size: int,
                     slots: int = 0) -> Params:
        """The attention layers' block pool, k/v [La, num_blocks, bs,
        Hkv, D], and with ``slots`` the recurrent state a SLOT beside it:
        ONE tree, which the decode step takes and hands back whole."""
        pool = self._kv_zeros(num_blocks, block_size)
        return {**pool, **self.init_state(slots)} if slots else pool

    def forward_step(self, params: Params, tokens: jax.Array, cache: Params,
                     offsets: jax.Array,
                     lengths: Optional[jax.Array] = None
                     ) -> Tuple[jax.Array, Params]:
        """``LlamaModel.forward_step`` with the state in the cache: each
        row continues from ITS state and stops after ITS ``lengths`` [B]
        tokens of this call (None: all T), so padding behind a row's
        length neither advances ``S`` nor shifts the convolution's
        window. -> (logits [B, T, V], the cache after the call)."""
        B, T = tokens.shape
        S = cache["k"].shape[2] if "k" in cache else 0
        q_pos = offsets[:, None] + jnp.arange(T)[None, :]
        batch_idx = jnp.arange(B)[:, None]

        def attend_of(j):
            def attend(q, k_new, v_new):
                with jax.named_scope("kv_update"):
                    k_all = cache["k"][j].at[batch_idx, q_pos].set(k_new)
                    v_all = cache["v"][j].at[batch_idx, q_pos].set(v_new)
                with jax.named_scope("attention"):
                    o = self._attend_rows(q, k_all, v_all, None, q_pos,
                                          jnp.arange(S))
                return o, (k_all, v_all)
            return attend

        def scan_of(j):
            return cache["conv"][j], self._chunked(cache["ssm"][j], lengths)

        x, outs = self._walk(params, self._embed(params, tokens), q_pos,
                             attend_of, scan_of, lengths=lengths)
        new = dict(cache, **self._stack_state(outs["mamba"], cache))
        if outs["attn"]:
            new["k"] = jnp.stack([k for k, _ in outs["attn"]])
            new["v"] = jnp.stack([v for _, v in outs["attn"]])
        return self._head(params, x), new

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
        Pmax = prefix_k.shape[2]
        if state is None:
            state = self.init_state(N_)
        pos_q = prefix_len[:, None] + jnp.arange(Tb)[None, :]
        far = jnp.int32(2 ** 30)
        pos_prefix = jnp.where(
            jnp.arange(Pmax)[None, :] < prefix_len[:, None],
            jnp.arange(Pmax)[None, :], far)
        pos_k = jnp.concatenate([pos_prefix, pos_q], axis=1)

        def attend_of(j):
            def attend(q, k_new, v_new):
                with jax.named_scope("attention"):
                    o = self._attend_rows(
                        q, jnp.concatenate([prefix_k[j].astype(k_new.dtype),
                                            k_new], axis=1),
                        jnp.concatenate([prefix_v[j].astype(v_new.dtype),
                                         v_new], axis=1),
                        None, pos_q, pos_k)
                return o, (k_new, v_new)
            return attend

        def scan_of(j):
            return state["conv"][j], self._chunked(state["ssm"][j], lengths)

        x, outs = self._walk(params, self._embed(params, tokens), pos_q,
                             attend_of, scan_of, lengths=lengths)
        small = self._stack_state(outs["mamba"], state)
        if outs["attn"]:
            small["k"] = jnp.stack([k for k, _ in outs["attn"]])
            small["v"] = jnp.stack([v for _, v in outs["attn"]])
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
        and written back where they lie (the stack of ``S`` is handed to
        ``ops.ssm.state_step`` whole, layer ``j``'s rows from ``j*rows``
        on, ``rows`` the state's rows a layer, which is the batch). A slot that is idle computes on whatever its row holds; its
        next tenant's activation overwrites the row.
        -> (logits [B, V], the pool, {"load": [Le, E], ...} or None).
        ``run``: ``LlamaModel.decode_step_paged``'s."""
        cfg = self.cfg
        B = tokens.shape[0]
        impl = self.paged_decode_impl()
        q_pos = offsets[:, None]
        lengths = offsets + 1
        attn = "k" in pool
        if attn:
            La, NB, bs = pool["k"].shape[:3]
            k_pool = pool["k"].reshape((La * NB,) + pool["k"].shape[2:])
            v_pool = pool["v"].reshape((La * NB,) + pool["v"].shape[2:])
            dest_block = jnp.take_along_axis(
                block_tables, (offsets // bs)[:, None], axis=-1)[:, 0]
            dest_off = offsets % bs
        rec = "ssm" in pool
        if rec:
            rows = pool["ssm"].shape[1]
            if rows != B:
                raise ValueError(
                    f"the decode batch is one row a slot: {B} tokens for a "
                    f"state of {rows} rows")
            stack = pool["ssm"].reshape((-1,) + pool["ssm"].shape[2:])
            conv = pool["conv"]
        G = cfg.ssm_groups
        W = cfg.mamba_inner // G
        pools = {}

        def attend_of(j):
            def attend(q, k_new, v_new):
                k_all, v_all = pools.get("kv", (k_pool, v_pool))
                with jax.named_scope("kv_update"):
                    k_all = k_all.at[j * NB + dest_block, dest_off].set(
                        k_new[:, 0])
                    v_all = v_all.at[j * NB + dest_block, dest_off].set(
                        v_new[:, 0])
                with jax.named_scope("attention"):
                    o = self._attend_pages(
                        q[:, 0], k_all, v_all, None, block_tables, lengths,
                        impl=impl, starts=None, first_block=j * NB,
                        num_blocks=NB, run=run)
                pools["kv"] = (k_all, v_all)
                return o[:, None], None
            return attend

        def scan_of(j):
            def scan(x, dt, a, Bm, Cm):
                with jax.named_scope("ssm_state_update"):
                    dt1 = dt[:, 0]                            # [B, H]
                    decay = jnp.repeat(jnp.exp(dt1 * a), cfg.mamba_head_dim,
                                       axis=-1).reshape(B, G, W)
                    dtx = (dt1[..., None] * x[:, 0].astype(jnp.float32)
                           ).reshape(B, G, W)
                    new, y = ssm.state_step(
                        pools.get("ssm", stack), j * rows, decay, dtx,
                        Bm[:, 0], Cm[:, 0], impl=impl)
                    pools["ssm"] = new
                return y.reshape(B, 1, cfg.mamba_heads,
                                 cfg.mamba_head_dim), None
            return conv[j], scan

        x, outs = self._walk(params, self._embed(params, tokens[:, None]),
                             q_pos, attend_of, scan_of, live=live)
        pool = dict(pool)
        if "kv" in pools:
            pool["k"] = pools["kv"][0].reshape(pool["k"].shape)
            pool["v"] = pools["kv"][1].reshape(pool["v"].shape)
        if "ssm" in pools:
            pool["ssm"] = pools["ssm"].reshape(pool["ssm"].shape)
            pool["conv"] = jnp.stack([w for w, _ in outs["mamba"]])
        return (self._head(params, x)[:, 0], pool,
                self._stack_extras(outs["moe"]))
