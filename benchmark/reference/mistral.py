"""Plain float32 reference of the Mistral-7B block (Llama-shaped).

Independent of ``ray_tpu/models``: straightforward ``jax.numpy`` following
the published description (HF ``modeling_mistral.py``): pre-RMSNorm,
grouped-query attention with rotary embeddings in the rotate-half
convention (theta from the config), SwiGLU MLP, final RMSNorm, untied
output head. No kernels, no cache, no batching tricks, no scan; float32
throughout under ``jax.default_matmul_precision("highest")`` (on a TPU an
f32 matmul otherwise runs in bf16 passes).

Takes the SYSTEM'S OWN parameter arrays (mapped to the names below by
``benchmark/builders``), so nothing is held twice.

Layer params: ``attn_norm [d]``, ``wq [d, H, hd]``, ``wk/wv [d, Hkv, hd]``,
``wo [H, hd, d]``, ``mlp_norm [d]``, ``w_gate/w_up [d, f]``,
``w_down [f, d]``; model: ``embed [V, d]``, ``layers`` (list),
``norm_f [d]``, ``lm_head [d, V]``.
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


def forward(params, tokens, *, rope_theta: float, rms_norm_eps: float):
    """tokens [B, S] int32 -> logits [B, S, V] float32."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    with jax.default_matmul_precision("highest"):
        x = f32(params["embed"])[tokens]
        S = tokens.shape[1]
        causal = jnp.tril(jnp.ones((S, S), bool))
        for lp in params["layers"]:
            h = _rms_norm(x, f32(lp["attn_norm"]), rms_norm_eps)
            q = jnp.einsum("bsd,dhk->bshk", h, f32(lp["wq"]))
            k = jnp.einsum("bsd,dhk->bshk", h, f32(lp["wk"]))
            v = jnp.einsum("bsd,dhk->bshk", h, f32(lp["wv"]))
            q, k = _rope(q, rope_theta), _rope(k, rope_theta)
            groups = q.shape[2] // k.shape[2]
            k = jnp.repeat(k, groups, axis=2)
            v = jnp.repeat(v, groups, axis=2)
            s = jnp.einsum("bqhk,bthk->bhqt", q, k) / (q.shape[-1] ** 0.5)
            s = jnp.where(causal[None, None], s, -jnp.inf)
            o = jnp.einsum("bhqt,bthk->bqhk", jax.nn.softmax(s, -1), v)
            x = x + jnp.einsum("bqhk,hkd->bqd", o, f32(lp["wo"]))
            h = _rms_norm(x, f32(lp["mlp_norm"]), rms_norm_eps)
            gate = h @ f32(lp["w_gate"])
            up = h @ f32(lp["w_up"])
            x = x + (jax.nn.silu(gate) * up) @ f32(lp["w_down"])
        x = _rms_norm(x, f32(params["norm_f"]), rms_norm_eps)
        return x @ f32(params["lm_head"])
