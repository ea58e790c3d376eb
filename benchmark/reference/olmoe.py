"""Plain float32 reference of the OLMoE-1B-7B block.

Independent of ``ray_tpu/models``: straightforward ``jax.numpy`` following
the published description (OLMoE, arXiv:2409.02060; HF
``modeling_olmoe.py``): pre-RMSNorm; q, k, v projections without bias;
RMSNorm of q and of k over ALL heads' lanes together (``q_norm`` /
``k_norm`` of width heads x head_dim) before the split into heads and
before the rotary embedding (rotate-half, theta from the config);
causal softmax attention with as many KV heads as heads; a router that is
one matrix, softmax in float32 over all experts, top-k, the chosen
probabilities used AS THEY ARE (``norm_topk_prob`` false: they sum to
less than 1); every chosen expert a SwiGLU MLP, none dropped, no shared
expert; final RMSNorm, untied output head.

No kernels, no cache, no sorting, no scan: a Python loop over layers and,
inside it, EVERY expert computed for every token in one dense einsum and
masked by the top-k weights. float32 throughout under
``jax.default_matmul_precision("highest")`` (on a TPU an f32 matmul
otherwise runs in bf16 passes).

Departures from the published model, none of which changes the function:
``clip_qkv`` is null in the published config and not implemented; the
projection matrices are laid out input-first ([d, H, hd], experts
[E, d, f]) as the system stores them, where HF stores [out, in]; the
norm weights of q and k arrive as [H, hd] and are read flat; ties in the
top-k go to the lower expert index (``jax.lax.top_k``); the auxiliary
router losses of training are left out (inference only).

Takes the SYSTEM'S OWN parameter arrays (mapped to the names below by
``benchmark/builders/olmoe.py``), so nothing is held twice.

Layer params: ``attn_norm [d]``, ``wq/wk/wv [d, H, hd]``, ``q_norm/k_norm
[H, hd]``, ``wo [H, hd, d]``, ``mlp_norm [d]``, ``router [d, E]``,
``e_gate/e_up [E, d, f]``, ``e_down [E, f, d]``; model: ``embed [V, d]``,
``layers`` (list), ``norm_f [d]``, ``lm_head [d, V]``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _rope(x, theta):
    """x [B, S, H, hd]; rotate-half convention, positions 0..S-1."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _expert_mlp(h, lp, top_k, norm_topk_prob, forced):
    """h [B, S, d] -> (out, chosen experts [B, S, K], gap [B, S] between
    the K-th and (K+1)-th router probability). ``forced`` [B, S, K]
    replaces the router's own choice (its probabilities still weigh)."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    probs = jax.nn.softmax(h @ f32(lp["router"]), axis=-1)       # [B, S, E]
    ranked, experts = jax.lax.top_k(probs, top_k + 1)
    gap = ranked[..., top_k - 1] - ranked[..., top_k]
    experts = experts[..., :top_k] if forced is None else forced
    weights = jnp.take_along_axis(probs, experts, axis=-1)
    if norm_topk_prob:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    dense_w = jnp.sum(jax.nn.one_hot(experts, probs.shape[-1])
                      * weights[..., None], axis=-2)             # [B, S, E]
    gate = jnp.einsum("bsd,edf->bsef", h, f32(lp["e_gate"]))
    up = jnp.einsum("bsd,edf->bsef", h, f32(lp["e_up"]))
    every = jnp.einsum("bsef,efd->bsed", jax.nn.silu(gate) * up,
                       f32(lp["e_down"]))
    return jnp.einsum("bsed,bse->bsd", every, dense_w), experts, gap


def forward(params, tokens, *, rope_theta: float, rms_norm_eps: float,
            top_k: int, norm_topk_prob: bool = False, forced_experts=None,
            with_routing: bool = False):
    """tokens [B, S] int32 -> logits [B, S, V] float32; with
    ``with_routing`` also ``{"experts": [L, B, S, K], "gap": [L, B, S]}``.
    ``forced_experts`` [L, B, S, K] makes every layer use those experts
    instead of its own top-k (for comparing a lower-precision system
    whose near-tied choices differ)."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    with jax.default_matmul_precision("highest"):
        x = f32(params["embed"])[tokens]
        B, S = tokens.shape
        causal = jnp.tril(jnp.ones((S, S), bool))
        chosen, gaps = [], []
        for i, lp in enumerate(params["layers"]):
            h = _rms_norm(x, f32(lp["attn_norm"]), rms_norm_eps)
            q = jnp.einsum("bsd,dhk->bshk", h, f32(lp["wq"]))
            k = jnp.einsum("bsd,dhk->bshk", h, f32(lp["wk"]))
            v = jnp.einsum("bsd,dhk->bshk", h, f32(lp["wv"]))
            # one norm over all heads' lanes, then back into heads
            q = _rms_norm(q.reshape(B, S, -1), f32(lp["q_norm"]).reshape(-1),
                          rms_norm_eps).reshape(q.shape)
            k = _rms_norm(k.reshape(B, S, -1), f32(lp["k_norm"]).reshape(-1),
                          rms_norm_eps).reshape(k.shape)
            q, k = _rope(q, rope_theta), _rope(k, rope_theta)
            s = jnp.einsum("bqhk,bthk->bhqt", q, k) / (q.shape[-1] ** 0.5)
            s = jnp.where(causal[None, None], s, -jnp.inf)
            o = jnp.einsum("bhqt,bthk->bqhk", jax.nn.softmax(s, -1), v)
            x = x + jnp.einsum("bqhk,hkd->bqd", o, f32(lp["wo"]))
            h = _rms_norm(x, f32(lp["mlp_norm"]), rms_norm_eps)
            out, experts, gap = _expert_mlp(
                h, lp, top_k, norm_topk_prob,
                None if forced_experts is None else forced_experts[i])
            x = x + out
            chosen.append(experts)
            gaps.append(gap)
        x = _rms_norm(x, f32(params["norm_f"]), rms_norm_eps)
        logits = x @ f32(params["lm_head"])
    if with_routing:
        return logits, {"experts": jnp.stack(chosen), "gap": jnp.stack(gaps)}
    return logits
