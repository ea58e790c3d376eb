"""Plain float32 reference of the GPT-2 block.

Independent of ``ray_tpu/models``: ``jax.numpy`` following the published
model (HF ``modeling_gpt2.py``): learned positions, pre-LayerNorm with
bias, multi-head attention with biases, GELU MLP (tanh approximation,
``gelu_new``), final LayerNorm, output head tied to the token
embedding. Float32 under ``jax.default_matmul_precision("highest")``.

Takes the system's own parameter arrays. Layer params: ``ln1_w/ln1_b``,
``wqkv [d, 3, H, hd]``, ``bqkv [3, H, hd]``, ``wo [H, hd, d]``, ``bo``,
``ln2_w/ln2_b``, ``w_up [d, f]``, ``b_up``, ``w_down [f, d]``,
``b_down``; model: ``wte [V, d]``, ``wpe [P, d]``, ``layers`` (list),
``lnf_w/lnf_b``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _layer_norm(x, w, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w + b


def forward(params, tokens, *, layer_norm_epsilon: float):
    """tokens [B, S] int32 -> logits [B, S, V] float32."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    with jax.default_matmul_precision("highest"):
        S = tokens.shape[1]
        x = f32(params["wte"])[tokens] + f32(params["wpe"])[:S][None]
        causal = jnp.tril(jnp.ones((S, S), bool))
        eps = layer_norm_epsilon
        for lp in params["layers"]:
            h = _layer_norm(x, f32(lp["ln1_w"]), f32(lp["ln1_b"]), eps)
            qkv = jnp.einsum("bsd,dthk->bsthk", h, f32(lp["wqkv"])) \
                + f32(lp["bqkv"])
            q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
            s = jnp.einsum("bqhk,bthk->bhqt", q, k) / (q.shape[-1] ** 0.5)
            s = jnp.where(causal[None, None], s, -jnp.inf)
            o = jnp.einsum("bhqt,bthk->bqhk", jax.nn.softmax(s, -1), v)
            x = x + jnp.einsum("bqhk,hkd->bqd", o, f32(lp["wo"])) \
                + f32(lp["bo"])
            h = _layer_norm(x, f32(lp["ln2_w"]), f32(lp["ln2_b"]), eps)
            up = jax.nn.gelu(h @ f32(lp["w_up"]) + f32(lp["b_up"]),
                             approximate=True)
            x = x + up @ f32(lp["w_down"]) + f32(lp["b_down"])
        x = _layer_norm(x, f32(params["lnf_w"]), f32(params["lnf_b"]), eps)
        return x @ f32(params["wte"]).T
