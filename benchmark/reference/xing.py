"""Plain float32 reference of the Xing4.0-29B-A4B block (``model_type:
xing4_0``; huggingface.co/XingChen-AGI/Xing4.0-29B-A4B), as ISSUE 50
writes the layer down from the published keys, *mHC: Manifold-Constrained
Hyper-Connections* (arXiv:2512.24880, section 4) and *Hyper-Connections*
(arXiv:2409.19606).

Independent of ``ray_tpu/models`` and ``ray_tpu/ops``: straightforward
``jax.numpy``, the latent attention in the EXPANDED form only, the
streams a plain ``[B, S, n, C]`` array and ``einsum``s, the Sinkhorn a
Python loop. A token's residual is ``X`` [n, C] (n = ``hc_mult``):

- entry: ``X_0[i] = Emb(t)`` for every stream i. ASSUMED (Hyper-
  Connections, Alg. 2: n copies); exit: ``x = sum_i X[i]``, then the
  final RMSNorm and the untied head. ASSUMED; the alternative NOT taken
  is the open DeepSeek-V4 inference code's (whose ``hc_*`` keys this
  configuration shares): a learned, sigmoid-weighted sum of the streams.
- ONE SUBLAYER (each layer has two, attention then FFN, each with its
  own ``Phi``, ``alpha``, ``b``): ``r = rsqrt(mean(vec(X)^2) + rms_norm_eps)``
  over all n C lanes (ASSUMED: the statistic has no weight of its own,
  it folds into ``Phi``, and uses ``rms_norm_eps``); ``m = alpha * (r
  vec(X) Phi) + b``, ``Phi`` [n C, 2n + n^2], ``alpha`` three scalars, one
  for each part of ``m = [m_pre (n) | m_post (n) | m_res (n^2)]``;
  ``H_pre = sigmoid(m_pre)``; ``H_post = 2 sigmoid(m_post)``; ``M_0 =
  exp(clamp(mat(m_res), mhc_h_res_clamp_min, mhc_h_res_clamp_max))``
  (ASSUMED: the clamp is on ``m_res``, before ``exp``), then
  ``hc_sinkhorn_iters`` times ``M <- M / (rowsum(M) + hc_eps)``, ``M <- M
  / (colsum(M) + hc_eps)`` (ASSUMED: ``hc_eps`` sits in the denominators;
  rows before columns, where the paper writes ``T_r(T_c(.))``: at 20
  rounds the two orders differ by what either is short of doubly
  stochastic, ~1e-6 for ``b_res`` 0 and up to ~1e-2 for a near-identity
  ``H_res``, whose Sinkhorn converges slowly); ``H_res = M``. ``h = sum_i
  H_pre[i] X[i]``; ``y = F(RMSNorm(h; w))``, F the attention or the FFN
  and ``w`` the sublayer's own norm weight; ``X'[i] = sum_j H_res[i, j]
  X[j] + H_post[i] y``.
- attention (``deepseek_v3``'s latent attention with a compressed query):
  ``qr = RMSNorm(h Wq_a)``, ``q = qr Wq_b`` -> heads x (nope + rope);
  ``[c~ | k~_pe] = h Wkv_a``, ``c = RMSNorm(c~)``; ``[k_nope | v] = c
  Wkv_b``; RoPE on the rotary lanes in ADJACENT pairs (ASSUMED:
  ``rope_interleave`` true, as the ``deepseek_v3`` family), YaRN's
  frequencies, cos and sin unscaled (``mscale = mscale_all_dim``), the
  softmax scale ``(nope + rope)^-0.5 m^2``, ``m = 0.1 mscale_all_dim
  ln(factor) + 1``; causal; ``o Wo``.
- FFN: the first ``first_k_dense_replace`` layers SwiGLU of
  ``intermediate_size``; every later one ``s = sigmoid(h W_r)``, the
  ``top_k`` largest of ``s + b_e`` (``noaux_tc``; one group), weights ``s``
  of the chosen over their sum + 1e-20 (``norm_topk_prob``) times
  ``routed_scaling_factor``, SwiGLU experts, plus one shared expert.

Departures from the published description, none of which changes the
function: matrices are input-first as the system stores them;
``kv_b_proj`` arrives as [R, H, nope + v]; the multi-token-prediction
module (a 41st layer) is not run: it lies beyond the cut in depth, and
the configuration does not say how it takes n streams in.

No kernels, no cache, no batching, no absorbed product; float32 under
``jax.default_matmul_precision("highest")``. Sized so that 8,200 tokens
at the published widths fit beside a live engine (the streams are [8,200,
4, 3,584] float32 = 470 MB a copy, so few are held): attention goes by
groups of ``HEAD_GROUP`` heads and blocks of ``QUERY_BLOCK`` queries, the
experts one at a time over all tokens, a wide FFN ``FFN_CHUNK`` columns at
a time, the layers' parameters arrive STACKED and a layer cuts its slice
behind an ``optimization_barrier``, and ``forward_rows`` hands the head
back UNAPPLIED (``RowsOfLogits``).

Takes the SYSTEM'S OWN parameter arrays under the names below
(``benchmark/builders/xing.py`` maps them). Each layer: ``attn_norm [d]``,
``q_a_proj [d, r]``, ``q_a_layernorm [r]``, ``q_b_proj [r, H, nope +
rope]``, ``kv_a_proj [d, R + rope]``, ``kv_a_layernorm [R]``, ``kv_b_proj
[R, H, nope + v]``, ``o_proj [H, v, d]``, ``mlp_norm [d]``, and for each
of ``attn`` / ``mlp``: ``<s>_hc_phi [n d, 2n + n^2]``, ``<s>_hc_alpha
[3]``, ``<s>_hc_bias [2n + n^2]``. A dense layer: ``gate/up [d, f]``,
``down [f, d]``. An expert layer: ``router [d, E]``, ``router_bias [E]``,
``e_gate/e_up [E, d, f]``, ``e_down [E, f, d]``, ``s_gate/s_up [d, fs]``,
``s_down [fs, d]``. Model: ``embed``, ``dense_layers`` / ``moe_layers``
(dicts of those, stacked), ``norm_f``, ``lm_head [d, V]``.

``forced_experts`` [L_moe, B, S, K] makes the expert layers use the
SYSTEM's chosen experts (bf16 swaps near-ties at the 4th expert);
``fault`` names ONE deliberate departure, for the controls that the
comparison has to refuse (``FAULTS``).
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

QUERY_BLOCK = 256
HEAD_GROUP = 8           # heads whose keys and values are expanded at once
FFN_CHUNK = 1024         # columns of a wide feed-forward at once

FAULTS = ("h_res_identity", "one_sinkhorn_round", "maps_in_bf16",
          "h_post_without_2", "no_mscale", "no_q_norm", "no_clamp",
          "columns_before_rows", "weights_with_bias", "no_routed_scale",
          "skip_last_layer", "int8_weights")


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _w(a, fault=None, axis=-2):
    """A matmul weight in float32; under ``int8_weights`` through int8
    first, one scale per output channel (the largest magnitude over the
    input ``axis``): the nearest precision below the stated bf16. (The
    routers and the stream maps' ``Phi``, which the configuration states
    in float32, are not matmul weights in this sense.)"""
    f = _f32(a)
    if fault != "int8_weights":
        return f
    scale = jnp.max(jnp.abs(f), axis=axis, keepdims=True) / 127.0
    return jnp.round(f / jnp.maximum(scale, 1e-30)) * scale


def _rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def yarn_inv_freq(rope: int, theta: float, yarn):
    """[rope / 2] inverse frequencies (YaRN, arXiv:2309.00071, as HF's
    ``_compute_yarn_parameters``): lane ``i`` turns ``theta^(-2i/rope)``
    a position where it makes more than ``beta_fast`` turns over the
    original context, that over ``factor`` where fewer than
    ``beta_slow``, a linear ramp between. ``yarn`` None: the plain ones."""
    base = theta ** (-jnp.arange(0, rope, 2, dtype=jnp.float32) / rope)
    if yarn is None:
        return base
    factor, original, beta_fast, beta_slow = yarn

    def lane(turns):
        return (rope * math.log(original / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(lane(beta_fast)), 0)
    high = min(math.ceil(lane(beta_slow)), rope - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(rope // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return base * (1 - ramp) + base / factor * ramp


def _rope(x, inv_freq):
    """x [B, S, H, r] at positions 0..S-1: lanes (2i, 2i + 1) turn by
    frequency i."""
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq[None]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


# -- the residual streams ----------------------------------------------------
def stream_maps(X, phi, alpha, bias, *, iters: int, eps: float,
                norm_eps: float, clamp, fault=None):
    """X [B, S, n, C] -> (H_pre [B, S, n], H_post [B, S, n], H_res [B, S,
    n, n]) of ONE sublayer (module docstring)."""
    B, S, n, C = X.shape
    v = X.reshape(B, S, n * C)
    r = jax.lax.rsqrt(jnp.mean(v * v, axis=-1, keepdims=True) + norm_eps)
    phi, bias = _f32(phi), _f32(bias)
    scale = jnp.concatenate([jnp.full((n,), alpha[0]), jnp.full((n,), alpha[1]),
                             jnp.full((n * n,), alpha[2])])
    dt = jnp.float32
    if fault == "maps_in_bf16":            # the statistic, the product and
        dt = jnp.bfloat16                  # everything after it in bf16
        v, r, phi, scale, bias = (a.astype(dt)
                                  for a in (v, r, phi, scale, bias))
    m = scale * (r * (v @ phi)) + bias
    h_pre = jax.nn.sigmoid(m[..., :n])
    h_post = jax.nn.sigmoid(m[..., n:2 * n])
    if fault != "h_post_without_2":
        h_post = 2.0 * h_post
    m_res = m[..., 2 * n:].reshape(B, S, n, n)
    if fault != "no_clamp":
        m_res = jnp.clip(m_res, *clamp)
    M = jnp.exp(m_res)
    eps = jnp.asarray(eps, dt)
    for _ in range(1 if fault == "one_sinkhorn_round" else iters):
        if fault == "columns_before_rows":
            M = M / (jnp.sum(M, axis=-2, keepdims=True) + eps)
            M = M / (jnp.sum(M, axis=-1, keepdims=True) + eps)
        else:
            M = M / (jnp.sum(M, axis=-1, keepdims=True) + eps)
            M = M / (jnp.sum(M, axis=-2, keepdims=True) + eps)
    if fault == "h_res_identity":
        M = jnp.broadcast_to(jnp.eye(n, dtype=dt), M.shape)
    return _f32(h_pre), _f32(h_post), _f32(M)


def _sublayer(X, lp, which: str, fn, hc, eps, fault):
    """``X' = H_res X + H_post F(RMSNorm(H_pre X))`` for the sublayer
    ``which`` ("attn" / "mlp") of the layer ``lp``; ``fn(h) -> (y,
    extra)``."""
    h_pre, h_post, h_res = stream_maps(
        X, lp[which + "_hc_phi"], _f32(lp[which + "_hc_alpha"]),
        lp[which + "_hc_bias"], fault=fault, **hc)
    h = jnp.einsum("bsn,bsnc->bsc", h_pre, X)
    y, extra = fn(_rms_norm(h, _f32(lp[which + "_norm"]), eps))
    X = (jnp.einsum("bsij,bsjc->bsic", h_res, X)
         + h_post[..., None] * y[:, :, None, :])
    return X, extra


# -- attention -----------------------------------------------------------------
def _by_blocks(fn, S: int, *rows_of):
    """``fn(rows, *blocks)`` over blocks of ``QUERY_BLOCK`` positions (the
    last padded; its padding cut off again)."""
    blocks = -(-S // QUERY_BLOCK)
    pad = blocks * QUERY_BLOCK - S

    def split(a):
        a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        return jnp.moveaxis(a.reshape(a.shape[0], blocks, QUERY_BLOCK,
                                      *a.shape[2:]), 1, 0)

    out = jax.lax.map(lambda args: fn(*args), (
        jnp.arange(blocks * QUERY_BLOCK).reshape(blocks, QUERY_BLOCK),
        *map(split, rows_of)))
    return jnp.moveaxis(out, 0, 1).reshape(
        out.shape[1], -1, *out.shape[3:])[:, :S]


def _latent_attention(h, lp, *, nope: int, rope: int, rank: int,
                      theta: float, yarn, mscale_all_dim: float, eps: float,
                      fault):
    B, S, _ = h.shape
    inv = yarn_inv_freq(rope, theta, yarn)
    qr = h @ _w(lp["q_a_proj"], fault)
    if fault != "no_q_norm":
        qr = _rms_norm(qr, _f32(lp["q_a_layernorm"]), eps)
    down = h @ _w(lp["kv_a_proj"], fault)
    c = _rms_norm(down[..., :rank], _f32(lp["kv_a_layernorm"]), eps)
    k_pe = _rope(down[..., None, rank:], inv)                 # [B, S, 1, r]
    m = 1.0
    if yarn is not None and mscale_all_dim and fault != "no_mscale":
        m = 0.1 * mscale_all_dim * math.log(yarn[0]) + 1.0
    scale = m * m / math.sqrt(nope + rope)
    cols = jnp.arange(S)
    H = lp["q_b_proj"].shape[1]
    group = math.gcd(H, HEAD_GROUP)

    def heads(total, weights):
        """``group`` heads at a time: their q, expanded keys and values,
        the causal softmax by blocks of queries, their part of ``o Wo``."""
        q_b, kv_b = (_w(a, fault, axis=0) for a in weights[:2])
        o_w = _w(weights[2], fault, axis=(0, 1))
        q = jnp.einsum("bsr,rhk->bshk", qr, q_b)
        q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], inv)], -1)
        kv = jnp.einsum("bsr,rhk->bshk", c, kv_b)
        k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
            k_pe, kv.shape[:3] + (rope,))], -1)
        v = kv[..., nope:]

        def block(rows, qb):
            s = jnp.einsum("bqhk,bthk->bhqt", qb, k) * scale
            s = jnp.where((rows[:, None] >= cols[None, :])[None, None], s,
                          -jnp.inf)
            return jnp.einsum("bhqt,bthk->bqhk", jax.nn.softmax(s, -1), v)

        o = _by_blocks(block, S, q)
        return total + jnp.einsum("bqhk,hkd->bqd", o, o_w), None

    def grouped(a, axis):
        a = jnp.moveaxis(a, axis, 0)
        return a.reshape(H // group, group, *a.shape[1:])

    out, _ = jax.lax.scan(heads, jnp.zeros_like(h), (
        jnp.moveaxis(grouped(lp["q_b_proj"], 1), 1, 2),
        jnp.moveaxis(grouped(lp["kv_b_proj"], 1), 1, 2),
        grouped(lp["o_proj"], 0)))
    return out


# -- the feed-forward ------------------------------------------------------------
def _swiglu(h, gate, up, down, fault=None):
    return (jax.nn.silu(h @ _w(gate, fault)) * (h @ _w(up, fault))
            ) @ _w(down, fault)


def _swiglu_wide(h, gate, up, down, fault=None):
    """``_swiglu`` of a WIDE feed-forward, ``FFN_CHUNK`` of its columns
    at a time."""
    f = gate.shape[-1]
    chunk = math.gcd(f, FFN_CHUNK)

    def add(total, w):
        return total + _swiglu(h, *w, fault), None

    out, _ = jax.lax.scan(add, jnp.zeros_like(h), (
        jnp.moveaxis(gate.reshape(-1, f // chunk, chunk), 1, 0),
        jnp.moveaxis(up.reshape(-1, f // chunk, chunk), 1, 0),
        down.reshape(f // chunk, chunk, -1)))
    return out


def _expert_block(h, lp, *, top_k: int, norm_topk_prob: bool,
                  routed_scale: float, forced, fault):
    """h [B, S, d] -> (out, chosen experts [B, S, K])."""
    scores = jax.nn.sigmoid(h @ _f32(lp["router"]))              # [B, S, E]
    biased = scores + _f32(lp["router_bias"])
    experts = jax.lax.top_k(biased, top_k)[1] if forced is None else forced
    weights = jnp.take_along_axis(
        biased if fault == "weights_with_bias" else scores, experts, axis=-1)
    if norm_topk_prob:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    if fault != "no_routed_scale":
        weights = weights * routed_scale
    dense_w = jnp.sum(jax.nn.one_hot(experts, scores.shape[-1])
                      * weights[..., None], axis=-2)             # [B, S, E]

    def add_expert(total, e):
        gate, up, down, w = e
        return total + _swiglu(h, gate, up, down, fault) * w[..., None], None

    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(h), (
        lp["e_gate"], lp["e_up"], lp["e_down"], jnp.moveaxis(dense_w, -1, 0)))
    return out + _swiglu(h, lp["s_gate"], lp["s_up"], lp["s_down"],
                         fault), experts


@jax.tree_util.register_pytree_node_class
class RowsOfLogits:
    """The logits of ``forward_rows``, head unapplied: ``self[index]``
    (an index into ``[B, S]``) is the final norm's rows at ``index``
    times the head, float32 at "highest"."""

    def __init__(self, x, head, fault=None):
        self.x, self.head, self.fault = x, head, fault

    def __getitem__(self, index):
        with jax.default_matmul_precision("highest"):
            return self.x[index] @ _w(self.head, self.fault)

    def tree_flatten(self):
        return (self.x, self.head), self.fault

    @classmethod
    def tree_unflatten(cls, fault, leaves):
        return cls(*leaves, fault)


def forward_rows(params, tokens, *, hc_mult: int, hc_sinkhorn_iters: int,
                 hc_eps: float, hc_res_clamp, qk_nope_head_dim: int,
                 qk_rope_head_dim: int, kv_lora_rank: int, rope_theta: float,
                 yarn, mscale_all_dim: float, rms_norm_eps: float, top_k: int,
                 routed_scaling_factor: float, norm_topk_prob: bool = True,
                 forced_experts=None, with_routing: bool = False,
                 fault: Optional[str] = None):
    """tokens [B, S] int32 -> ``RowsOfLogits`` over [B, S]; with
    ``with_routing`` also ``{"experts": [L_moe, B, S, K]}``."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"no fault {fault!r}; known: {FAULTS}")
    attn = dict(nope=qk_nope_head_dim, rope=qk_rope_head_dim,
                rank=kv_lora_rank, theta=rope_theta, yarn=yarn,
                mscale_all_dim=mscale_all_dim, eps=rms_norm_eps, fault=fault)
    hc = dict(iters=hc_sinkhorn_iters, eps=hc_eps, norm_eps=rms_norm_eps,
              clamp=hc_res_clamp)
    with jax.default_matmul_precision("highest"):
        x = _w(params["embed"][tokens], fault, axis=-1)
        X = jnp.broadcast_to(x[:, :, None, :],
                             (*x.shape[:2], hc_mult, x.shape[-1]))
        stacks = {"dense": params["dense_layers"] or {},
                  "moe": params["moe_layers"]}
        plan = [(kind, i) for kind in ("dense", "moe")
                for i in range(len(stacks[kind].get("attn_norm", ())))]
        if fault == "skip_last_layer":
            plan = plan[:-1]
        chosen = []
        for kind, i in plan:
            X, stacks = jax.lax.optimization_barrier((X, stacks))
            lp = {name: a[i] for name, a in stacks[kind].items()}
            X, _ = _sublayer(
                X, lp, "attn",
                lambda h: (_latent_attention(h, lp, **attn), None),
                hc, rms_norm_eps, fault)
            if kind == "dense":
                X, _ = _sublayer(
                    X, lp, "mlp",
                    lambda h: (_swiglu_wide(h, lp["gate"], lp["up"],
                                            lp["down"], fault), None),
                    hc, rms_norm_eps, fault)
                continue
            X, experts = _sublayer(
                X, lp, "mlp", lambda h: _expert_block(
                    h, lp, top_k=top_k, norm_topk_prob=norm_topk_prob,
                    routed_scale=routed_scaling_factor, fault=fault,
                    forced=(None if forced_experts is None
                            else forced_experts[i])),
                hc, rms_norm_eps, fault)
            chosen.append(experts)
        rows = RowsOfLogits(_rms_norm(jnp.sum(X, axis=2),
                                      _f32(params["norm_f"]), rms_norm_eps),
                            params["lm_head"], fault)
    if with_routing:
        return rows, {"experts": jnp.stack(chosen)}
    return rows


def forward(params, tokens, **kw):
    """tokens [B, S] int32 -> logits [B, S, V] float32 (``forward_rows``
    with the head applied to every row)."""
    out = forward_rows(params, tokens, **kw)
    if kw.get("with_routing"):
        return out[0][:, :], out[1]
    return out[:, :]
