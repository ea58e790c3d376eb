"""The plain reference for the hybrid Mamba-1 configuration (published
``jamba``): float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``, independent of
``ray_tpu/models`` and ``ray_tpu/ops``.

Layer ``i`` of ``x``, the residual stream (``eps`` in every norm):

    x <- x + mixer_i(RMSNorm(x; input_layernorm))
    x <- x + (silu(h gate_proj) * (h up_proj)) down_proj,
                                         h = RMSNorm(x; pre_ff_layernorm)

``mixer_i`` is attention where ``i % attn_layer_period ==
attn_layer_offset`` (MQA/GQA, causal, scale ``head_dim^-0.5``, NO
positional encoding, a block of queries at a time), else Mamba-1:

    [u | z] = h in_proj                       u first, the gate z second
    u = silu(conv1d(u))     the depthwise causal convolution written as
                            ``d_conv`` SHIFTED PRODUCTS over a zero-padded
                            sequence, plus its bias
    [r | B | C] = u x_proj;  r, B, C <- RMSNorm(r; dt_layernorm),
                            RMSNorm(B; b_layernorm), RMSNorm(C; c_layernorm)
    dt = softplus(r dt_proj_weight + dt_proj_bias)
    A = -exp(A_log)                                          [inner, N]
    S_t[d, n] = exp(dt_t[d] A[d, n]) S_{t-1}[d, n] + dt_t[d] B_t[n] u_t[d]
    y_t[d] = sum_n S_t[d, n] C_t[n] + D[d] u_t[d]
    out = (y silu(z)) out_proj

the recurrence a plain ``lax.scan`` OVER POSITIONS with ``S`` as the
equations write it, ``[inner, N]`` (the program holds ``[N, inner]`` and
runs a kernel: another layout and another formulation). The logits are
``RMSNorm(x; final_layernorm) E^T``, ``E`` the embedding (tied).

DEPARTURES from the published description, each the configuration's
(``benchmark/configs/jamba2-3b.json``, ``assumed``): none in the
arithmetic. HOW IT IS RUN: consecutive layers of one kind are a
``lax.scan`` over their indices into that kind's stack, each layer's
leaves upcast to float32 INSIDE the body (so at most one layer's float32
copy is alive: the model at full depth is 12.1 GB in float32 and stands
beside a live engine on the chip); ``forward_rows`` hands the head back
UNAPPLIED (``RowsOfLogits``) and takes attention a block of queries at a
time. ``FAULTS`` are deliberate departures for the controls.
"""

from __future__ import annotations

import itertools
from typing import Optional

import jax
import jax.numpy as jnp

FAULTS = ("bf16_state", "no_inner_norms", "no_dt_bias", "no_conv_bias",
          "scalar_A", "int8_weights")
QUERY_BLOCK = 512


def _f32(a):
    return a.astype(jnp.float32)


def _w(a, fault=None, axis=-2):
    """A matmul weight in float32; under ``int8_weights`` through int8
    first, one scale per output channel (the largest magnitude over the
    input ``axis``): the nearest precision below the stated bf16."""
    f = _f32(a)
    if fault != "int8_weights":
        return f
    scale = jnp.max(jnp.abs(f), axis=axis, keepdims=True) / 127.0
    return jnp.round(f / jnp.maximum(scale, 1e-30)) * scale


def _rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def recurrence(u, dt, A, Bm, Cm, state=None, round_state: bool = False):
    """The recurrence a position at a time. u, dt [B, S, inner]; A
    [inner, N]; Bm, Cm [B, S, N]; ``state`` [B, inner, N] before position
    0 (None: zeros). -> (y [B, S, inner] without ``D u``, the final
    state)."""
    B, _, inner = u.shape
    if state is None:
        state = jnp.zeros((B, inner, A.shape[1]), jnp.float32)

    def step(s, inp):
        ut, dtt, bt, ct = inp
        s = (jnp.exp(dtt[:, :, None] * A[None]) * s
             + (dtt * ut)[:, :, None] * bt[:, None, :])
        if round_state:
            # (not a pair of casts: the TPU compiler may keep the excess
            # precision of one)
            s = jax.lax.reduce_precision(s, exponent_bits=8,
                                         mantissa_bits=7)
        return s, jnp.sum(s * ct[:, None, :], axis=-1)

    state, ys = jax.lax.scan(
        step, state, tuple(jnp.moveaxis(v, 1, 0) for v in (u, dt, Bm, Cm)))
    return jnp.moveaxis(ys, 0, 1), state


def causal_conv(x, weight, bias):
    """x [B, S, C]; weight [C, K]; bias [C]: K shifted products over the
    sequence padded with K - 1 zeros in front."""
    K = weight.shape[1]
    S = x.shape[1]
    padded = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return bias + sum(padded[:, j:j + S] * weight[:, j] for j in range(K))


def mamba(h, lp, *, d_state: int, dt_rank: int, eps: float, fault=None,
          keep=None):
    """The Mamba-1 mixer of h [B, S, D] (normed) by one layer's leaves
    ``lp``. ``keep`` (a list): appended ``{"state": [B, inner, N] after
    the last position, "conv_in": [B, S, inner] the convolution's
    inputs}``."""
    inner = lp["out_proj"].shape[0]
    uz = h @ _w(lp["in_proj"], fault)
    u, z = uz[..., :inner], uz[..., inner:]
    bias = _f32(lp["conv1d_bias"])
    if fault == "no_conv_bias":
        bias = jnp.zeros_like(bias)
    conv_in = u
    u = jax.nn.silu(causal_conv(u, _f32(lp["conv1d_weight"]), bias))
    rbc = u @ _w(lp["x_proj"], fault)
    r, Bm, Cm = (rbc[..., :dt_rank], rbc[..., dt_rank:dt_rank + d_state],
                 rbc[..., dt_rank + d_state:])
    if fault != "no_inner_norms":
        r = _rms_norm(r, _f32(lp["dt_layernorm"]), eps)
        Bm = _rms_norm(Bm, _f32(lp["b_layernorm"]), eps)
        Cm = _rms_norm(Cm, _f32(lp["c_layernorm"]), eps)
    dt = r @ _w(lp["dt_proj_weight"], fault)
    if fault != "no_dt_bias":
        dt = dt + _f32(lp["dt_proj_bias"])
    dt = jax.nn.softplus(dt)
    A = -jnp.exp(_f32(lp["A_log"]))                          # [inner, N]
    if fault == "scalar_A":       # Mamba-2's form: one number a channel
        A = jnp.broadcast_to(jnp.mean(A, axis=1, keepdims=True), A.shape)
    y, final = recurrence(u, dt, A, Bm, Cm,
                          round_state=fault == "bf16_state")
    y = (y + _f32(lp["D"]) * u) * jax.nn.silu(z)
    if keep is not None:
        keep.append({"state": final, "conv_in": conv_in})
    return y @ _w(lp["out_proj"], fault)


def attention(h, lp, *, n_heads: int, n_kv_heads: int, head_dim: int,
              fault=None):
    B, S, _ = h.shape
    q = (h @ _w(lp["q_proj"], fault)).reshape(B, S, n_heads, head_dim)
    k = (h @ _w(lp["k_proj"], fault)).reshape(B, S, n_kv_heads, head_dim)
    v = (h @ _w(lp["v_proj"], fault)).reshape(B, S, n_kv_heads, head_dim)
    rep = n_heads // n_kv_heads
    q = q.reshape(B, S, n_kv_heads, rep, head_dim) * head_dim ** -0.5
    outs = []
    for lo in range(0, S, QUERY_BLOCK):
        qb = q[:, lo:lo + QUERY_BLOCK]
        s = jnp.einsum("bqgrd,bkgd->bgrqk", qb, k)
        seen = (jnp.arange(S)[None, :]
                <= (lo + jnp.arange(qb.shape[1]))[:, None])
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("bgrqk,bkgd->bqgrd", p, v))
    o = jnp.concatenate(outs, axis=1).reshape(B, S, n_heads * head_dim)
    return o @ _w(lp["o_proj"], fault)


def swiglu(h, lp, fault=None):
    return ((jax.nn.silu(h @ _w(lp["gate_proj"], fault))
             * (h @ _w(lp["up_proj"], fault))) @ _w(lp["down_proj"], fault))


def layer_kinds(n_layers: int, period: int, offset: int):
    """[(kind, index in the kind's stack)] a layer, in layer order."""
    seen = {"mamba": 0, "attn": 0}
    out = []
    for i in range(n_layers):
        kind = "attn" if i % period == offset else "mamba"
        out.append((kind, seen[kind]))
        seen[kind] += 1
    return out


@jax.tree_util.register_pytree_node_class
class RowsOfLogits:
    """The logits of ``forward_rows``, head unapplied: ``self[index]``
    (an index into ``[B, S]``) is the final norm's rows at ``index`` times
    the embedding transposed, float32 at "highest"."""

    def __init__(self, x, embed, fault=None):
        self.x, self.embed, self.fault = x, embed, fault

    def __getitem__(self, index):
        with jax.default_matmul_precision("highest"):
            return self.x[index] @ _w(self.embed, self.fault, axis=-1).T

    def tree_flatten(self):
        return (self.x, self.embed), self.fault

    @classmethod
    def tree_unflatten(cls, fault, leaves):
        return cls(*leaves, fault)


def forward_rows(params, tokens, *, n_layers: int, attn_layer_period: int,
                 attn_layer_offset: int, num_heads: int, num_kv_heads: int,
                 head_dim: int, d_state: int, dt_rank: int, eps: float,
                 fault: Optional[str] = None):
    """tokens [B, S] int32 -> ``RowsOfLogits`` over [B, S]. ``params``:
    ``embed`` [V, D], ``final_layernorm`` [D], and a stack a kind,
    ``mamba`` / ``attn``, of the leaves the module docstring names, each
    ``[layers of the kind, ...]``, in any float dtype."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"no fault {fault!r}; known: {FAULTS}")

    def one_layer(x, kind, lp):
        h = _rms_norm(x, _f32(lp["input_layernorm"]), eps)
        if kind == "mamba":
            x = x + mamba(h, lp, d_state=d_state, dt_rank=dt_rank, eps=eps,
                          fault=fault)
        else:
            x = x + attention(h, lp, n_heads=num_heads,
                              n_kv_heads=num_kv_heads, head_dim=head_dim,
                              fault=fault)
        h = _rms_norm(x, _f32(lp["pre_ff_layernorm"]), eps)
        return x + swiglu(h, lp, fault)

    with jax.default_matmul_precision("highest"):
        x = _w(params["embed"][tokens], fault, axis=-1)
        kinds = layer_kinds(n_layers, attn_layer_period, attn_layer_offset)
        for kind, run in itertools.groupby(kinds, key=lambda ki: ki[0]):
            indices = jnp.asarray([i for _, i in run], jnp.int32)
            stack = params[kind]

            def body(x, i, kind=kind, stack=stack):
                lp = {name: a[i] for name, a in stack.items()}
                return one_layer(x, kind, lp), None

            x, _ = jax.lax.scan(body, x, indices)
        x = _rms_norm(x, _f32(params["final_layernorm"]), eps)
    return RowsOfLogits(x, params["embed"], fault)


def forward(params, tokens, **kw):
    """tokens [B, S] int32 -> float32 logits [B, S, V]."""
    return forward_rows(params, tokens, **kw)[:]


def first_state(params, tokens, *, d_state: int, dt_rank: int, eps: float):
    """What layer 0, which must be a Mamba layer, holds after tokens [B,
    S]: (``S`` [B, inner, N], the convolution's inputs [B, S, inner]),
    float32, by the embedding and that one mixer alone."""
    kept = []
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"][tokens])
        lp = {name: a[0] for name, a in params["mamba"].items()}
        mamba(_rms_norm(x, _f32(lp["input_layernorm"]), eps), lp,
              d_state=d_state, dt_rank=dt_rank, eps=eps, keep=kept)
    return kept[0]["state"], kept[0]["conv_in"]
