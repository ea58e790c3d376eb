"""The plain reference for the hybrid gated-short-convolution / attention
expert configuration (published ``lfm2_moe``): float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``, independent of
``ray_tpu/models`` and ``ray_tpu/ops``. POSITIONAL: one forward over the
whole sequence, no state, no cache, no batching, no kernels.

Layer ``i`` of ``x``, the residual stream (``eps`` in every norm, no bias
anywhere):

    x <- x + operator_i(RMSNorm(x; operator_norm))
    x <- x + ffn_i(RMSNorm(x; ffn_norm))

``operator_i`` is attention where ``layer_types[i] == "full_attention"``
(GQA; q and k RMS-normed A HEAD by ``q_layernorm`` / ``k_layernorm``
BEFORE RoPE; RoPE ``default``, half-split rotation; causal softmax at
scale ``head_dim^-0.5``, a block of queries at a time), else the gated
short convolution:

    [B | C | x] = h in_proj                        three chunks, that order
    g = B * x
    c_t = sum_{j=0..K-1} conv_weight[:, j] * g_{t-(K-1)+j}
                        the depthwise causal filter written as K SHIFTED
                        PRODUCTS over a zero-padded sequence; tap K-1 on
                        the current position
    out = (C * c) out_proj

``ffn_i`` is the SwiGLU ``w2(silu(w1 h) * (w3 h))`` in the first
``num_dense_layers`` layers and the experts after them:

    s = sigmoid(h gate)                                  float32, [E]
    chosen = top_k(s + expert_bias)     the bias selects and weighs nothing
    w = s[chosen] / (sum(s[chosen]) + 1e-6) * routed_scaling_factor
    out = sum_e w_e * w2_e(silu(w1_e h) * (w3_e h))      no shared expert

computed as a DENSE sum over every expert, an expert at a time (a token's
weight for an expert it did not choose is 0): no sorting, no grouped
matmul. The logits are ``RMSNorm(x; embedding_norm) E^T``, ``E`` the
embedding (tied).

HOW IT IS RUN: consecutive layers of one kind (mixer x ffn) are a
``lax.scan`` over their indices into that kind's stack, each layer's
leaves upcast to float32 INSIDE the body and an expert layer's experts
one at a time inside the scan over experts, so that at most one layer's
float32 mixer and one expert's float32 weights are alive (a layer's 64
experts are 2.4 GB in float32 and stand beside a live engine on the
chip); ``forward_rows`` hands the head back UNAPPLIED (``RowsOfLogits``).
``FAULTS`` are deliberate departures for the controls.
"""

from __future__ import annotations

import itertools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

FAULTS = ("no_B_gate", "no_C_gate", "last_tap_only", "taps_reversed",
          "no_expert_bias", "softmax_router", "no_renorm", "no_qk_norm",
          "dense_layers_as_experts", "int8_weights")
QUERY_BLOCK = 512
RENORM_EPS = 1e-6
ATTENTION = "full_attention"


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


def causal_filter(g, weight):
    """g [B, S, C]; weight [C, K]: K shifted products over the sequence
    padded with K - 1 zeros in front."""
    K = weight.shape[1]
    S = g.shape[1]
    padded = jnp.pad(g, ((0, 0), (K - 1, 0), (0, 0)))
    return sum(padded[:, j:j + S] * weight[:, j] for j in range(K))


def short_conv(h, lp, fault=None, keep=None):
    """The gated short convolution of h [B, S, D] (normed) by one layer's
    leaves ``lp``. ``keep`` (a list): appended ``g`` [B, S, D], what the
    filter runs over (a serving program's state is its last K-1 rows)."""
    d = h.shape[-1]
    bcx = h @ _w(lp["in_proj"], fault)
    b, c, x = bcx[..., :d], bcx[..., d:2 * d], bcx[..., 2 * d:]
    g = x if fault == "no_B_gate" else b * x
    if keep is not None:
        keep.append(g)
    weight = _f32(lp["conv_weight"])
    if fault == "taps_reversed":
        weight = weight[:, ::-1]
    if fault == "last_tap_only":
        y = g * weight[:, -1]
    else:
        y = causal_filter(g, weight)
    if fault != "no_C_gate":
        y = c * y
    return y @ _w(lp["out_proj"], fault)


def rope(x, positions, theta):
    """x [B, S, H, hd] turned by the default table, halves split."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * inv_freq   # [S, half]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(h, lp, *, n_heads: int, n_kv_heads: int, head_dim: int,
              theta: float, eps: float, fault=None):
    B, S, _ = h.shape
    q = (h @ _w(lp["q_proj"], fault)).reshape(B, S, n_heads, head_dim)
    k = (h @ _w(lp["k_proj"], fault)).reshape(B, S, n_kv_heads, head_dim)
    v = (h @ _w(lp["v_proj"], fault)).reshape(B, S, n_kv_heads, head_dim)
    if fault != "no_qk_norm":
        q = _rms_norm(q, _f32(lp["q_layernorm"]), eps)
        k = _rms_norm(k, _f32(lp["k_layernorm"]), eps)
    q, k = rope(q, jnp.arange(S), theta), rope(k, jnp.arange(S), theta)
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
    return o @ _w(lp["out_proj"], fault)


def swiglu(h, w1, w3, w2, fault=None):
    return ((jax.nn.silu(h @ _w(w1, fault)) * (h @ _w(w3, fault)))
            @ _w(w2, fault))


def route(h, lp, *, top_k: int, norm_topk_prob: bool, scale: float,
          fault=None, forced=None):
    """-> (weights [B, S, K], experts [B, S, K], own [B, S, K]).
    ``forced`` [B, S, K]: the experts are THESE (a system's own choice
    under bf16 compute, whose near-ties may fall the other way); the
    weights are still the reference's scores of them. ``own`` is what the
    reference chooses from the same ``h`` either way."""
    logits = h @ _f32(lp["gate"])
    if fault == "softmax_router":
        scores = biased = jax.nn.softmax(logits, axis=-1)
    else:
        scores = jax.nn.sigmoid(logits)
        biased = scores if fault == "no_expert_bias" else (
            scores + _f32(lp["expert_bias"]))
    own = jax.lax.top_k(biased, top_k)[1]
    experts = own if forced is None else forced
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    if norm_topk_prob and fault != "no_renorm":
        weights = weights / (jnp.sum(weights, -1, keepdims=True)
                             + RENORM_EPS)
    return weights * scale, experts, own


def experts_ffn(h, lp, *, fault=None, **router):
    """The routed sum, dense: every expert over every token, an expert at
    a time, weighed by the token's weight for it (0 where not chosen).
    -> (the sum [B, S, D], ``route``'s ``own``)."""
    weights, experts, own = route(h, lp, fault=fault, **router)
    E = lp["gate"].shape[-1]
    # [B, S, E]: a token's weight for each expert
    per_expert = jnp.sum(jax.nn.one_hot(experts, E, dtype=jnp.float32)
                         * weights[..., None], axis=-2)

    def one(acc, e):
        out = swiglu(h, lp["experts_w1"][e], lp["experts_w3"][e],
                     lp["experts_w2"][e], fault)
        return acc + per_expert[..., e][..., None] * out, None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(h), jnp.arange(E))
    return acc, own


def layer_kinds(layer_types: Sequence[str], num_dense_layers: int):
    """[(kind, index in the kind's stack)] a layer, in layer order; a kind
    is ``conv_dense``, ``conv_moe``, ``attn_dense`` or ``attn_moe``."""
    seen, out = {}, []
    for i, mixer in enumerate(layer_types):
        kind = (("attn" if mixer == ATTENTION else "conv") + "_"
                + ("dense" if i < num_dense_layers else "moe"))
        out.append((kind, seen.get(kind, 0)))
        seen[kind] = seen.get(kind, 0) + 1
    return out


@jax.tree_util.register_pytree_node_class
class RowsOfLogits:
    """The logits of ``forward_rows``, head unapplied: ``self[index]``
    (an index into ``[B, S]``) is the final norm's rows at ``index`` times
    the embedding transposed, float32 at "highest". ``experts`` [expert
    layers, B, S, K]: what the reference's OWN router chose a layer, from
    the hidden state it had there (under ``forced_experts`` the one that
    followed the forced choices so far)."""

    def __init__(self, x, embed, experts, fault=None):
        self.x, self.embed, self.experts = x, embed, experts
        self.fault = fault

    def __getitem__(self, index):
        with jax.default_matmul_precision("highest"):
            return self.x[index] @ _w(self.embed, self.fault, axis=-1).T

    def tree_flatten(self):
        return (self.x, self.embed, self.experts), self.fault

    @classmethod
    def tree_unflatten(cls, fault, leaves):
        return cls(*leaves, fault)


def forward_rows(params, tokens, *, layer_types: Sequence[str],
                 num_dense_layers: int, num_heads: int, num_kv_heads: int,
                 head_dim: int, top_k: int, norm_topk_prob: bool,
                 routed_scaling_factor: float, rope_theta: float, eps: float,
                 fault: Optional[str] = None, forced_experts=None):
    """tokens [B, S] int32 -> ``RowsOfLogits`` over [B, S]. ``params``:
    ``embed`` [V, D], ``embedding_norm`` [D], and a stack a kind of the
    leaves the module docstring names, each ``[layers of the kind, ...]``,
    in any float dtype. ``forced_experts`` [expert layers, B, S, K]:
    ``route``'s ``forced``, an expert layer a row."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"no fault {fault!r}; known: {FAULTS}")
    router = dict(top_k=top_k, norm_topk_prob=norm_topk_prob,
                  scale=routed_scaling_factor)
    kinds = layer_kinds(layer_types, num_dense_layers)
    # the first expert layer's leaves, for ``dense_layers_as_experts``
    first_moe = next(((k, i) for k, i in kinds if k.endswith("moe")), None)

    def one_layer(x, kind, lp, forced):
        """-> (x after the layer, an expert layer's own choice or None)."""
        h = _rms_norm(x, _f32(lp["operator_norm"]), eps)
        if kind.startswith("attn"):
            x = x + attention(h, lp, n_heads=num_heads,
                              n_kv_heads=num_kv_heads, head_dim=head_dim,
                              theta=rope_theta, eps=eps, fault=fault)
        else:
            x = x + short_conv(h, lp, fault)
        h = _rms_norm(x, _f32(lp["ffn_norm"]), eps)
        if kind.endswith("moe"):
            out, own = experts_ffn(h, lp, fault=fault, forced=forced,
                                   **router)
            return x + out, own
        if fault == "dense_layers_as_experts":
            stack, i = params[first_moe[0]], first_moe[1]
            return x + experts_ffn(
                h, {name: a[i] for name, a in stack.items()}, fault=fault,
                **router)[0], None
        return x + swiglu(h, lp["w1"], lp["w3"], lp["w2"], fault), None

    with jax.default_matmul_precision("highest"):
        x = _w(params["embed"][tokens], fault, axis=-1)
        expert_layer, chosen = 0, []
        for kind, run in itertools.groupby(kinds, key=lambda ki: ki[0]):
            indices = jnp.asarray([i for _, i in run], jnp.int32)
            stack = params[kind]
            forced = None
            if kind.endswith("moe"):
                if forced_experts is not None:
                    forced = forced_experts[
                        expert_layer:expert_layer + len(indices)]
                expert_layer += len(indices)

            def body(x, i_forced, kind=kind, stack=stack):
                i, forced = i_forced
                lp = {name: a[i] for name, a in stack.items()}
                return one_layer(x, kind, lp, forced)

            x, own = jax.lax.scan(body, x, (indices, forced))
            if own is not None:
                chosen.append(own)
        x = _rms_norm(x, _f32(params["embedding_norm"]), eps)
    experts = jnp.concatenate(chosen) if chosen else None
    return RowsOfLogits(x, params["embed"], experts, fault)


def forward(params, tokens, **kw):
    """tokens [B, S] int32 -> float32 logits [B, S, V]."""
    return forward_rows(params, tokens, **kw)[:]


def first_state(params, tokens, *, eps: float):
    """What layer 0, which must be a conv layer, runs its filter over for
    tokens [B, S]: ``g`` [B, S, D], float32, by the embedding and that
    one mixer alone (a serving program's state rows are its last K - 1
    positions)."""
    kept = []
    kind = "conv_dense" if "conv_dense" in params else "conv_moe"
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"][tokens])
        lp = {name: a[0] for name, a in params[kind].items()}
        short_conv(_rms_norm(x, _f32(lp["operator_norm"]), eps), lp,
                   keep=kept)
    return kept[0]
