"""Plain float32 reference of the EvaByte block: a Llama-shaped decoder
whose attention is EVA (Zheng et al., "Efficient Attention via Control
Variates", ICLR 2023, as EvaByte ships it).

Independent of ``ray_tpu/``: straightforward ``jax.numpy`` over the WHOLE
sequence, no cache, no kernel; float32 throughout under
``jax.default_matmul_precision("highest")``. Per layer:

- pre-norm RMSNorm with scale ``1 + g`` (``norm_add_unit_offset``), the
  residual stream float32 (``fp32_skip_add``), rotary embeddings in the
  rotate-half convention, SwiGLU, final RMSNorm, untied head of
  ``num_pred_heads x vocab`` columns (head ``p`` predicts byte ``t + 1 +
  p``);
- EVA attention, per head ``h`` with ``d`` the head's width, ``s =
  d^-1/2``, window ``W``, chunk ``c`` and q, k already turned:
  chunk ``j`` holds positions ``[c j, c j + c)``; its summary is
  ``a = softmax_m(s phi_h . k_m)``, ``k~_j = sum_m a_m k_m + mu_h``,
  ``v~_j = sum_m a_m v_m``. A query at ``i`` lies in window ``w = i //
  W`` and sees exactly the keys ``m`` with ``w W <= m <= i`` (the window
  does not slide, it starts anew at every multiple of ``W``) and the
  summaries of every chunk of every EARLIER window, ``j < w W / c``;
  ONE softmax over both.

The queries are taken a window at a time (``lax.map``), so that 16k
tokens fit: the scores of one window are ``[H, W, W + T/c]``. The FFN is
taken in blocks of rows for the same reason, and the sequence is padded
to whole windows first (the padding lies past every real position, which
sees nothing after itself). None of this changes a number.

Takes the SYSTEM'S OWN parameter arrays (``benchmark/builders/evabyte``),
the layers stacked on a leading axis and sliced here one at a time, so
nothing is held twice. Layer params: ``attn_norm [d]``, ``wq [d, H,
hd]``, ``wk/wv [d, Hkv, hd]``, ``wo [H, hd, d]``, ``eva_phi/eva_mu [Hkv,
hd]``, ``mlp_norm [d]``, ``w_gate/w_up [d, f]``, ``w_down [f, d]``;
model: ``embed [V, d]``, ``norm_f [d]``, ``lm_head [d, heads * V]``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _rms_norm(x, g, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + g)


def _rope(x, theta):
    """x [B, S, H, hd]; rotate-half convention, positions 0..S-1."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def chunk_summaries(k, v, phi, mu, chunk):
    """k, v [B, S, H, hd] -> the summaries of the S // chunk whole
    chunks, (k~, v~) [B, S // chunk, H, hd]."""
    B, S, H, hd = k.shape
    n = S // chunk
    kc = k[:, :n * chunk].reshape(B, n, chunk, H, hd)
    vc = v[:, :n * chunk].reshape(B, n, chunk, H, hd)
    a = jax.nn.softmax(
        jnp.einsum("bnchd,hd->bnch", kc, phi) * hd ** -0.5, axis=2)
    return (jnp.einsum("bnch,bnchd->bnhd", a, kc) + mu,
            jnp.einsum("bnch,bnchd->bnhd", a, vc))


def eva_attention(q, k, v, phi, mu, window, chunk):
    """q [B, S, H, hd], k/v [B, S, Hkv, hd] -> [B, S, H, hd]; ``S`` a
    whole number of windows."""
    B, S, H, hd = q.shape
    ks, vs = chunk_summaries(k, v, phi, mu, chunk)
    groups = H // k.shape[2]
    k, v, ks, vs = (jnp.repeat(a, groups, axis=2) for a in (k, v, ks, vs))
    causal = jnp.tril(jnp.ones((window, window), bool))
    chunk_of = jnp.arange(ks.shape[1])

    def one_window(w):
        cut = lambda a: jax.lax.dynamic_slice_in_dim(a, w * window, window, 1)
        s = jnp.einsum("bqhk,bthk->bhqt", cut(q), cut(k)) * hd ** -0.5
        s = jnp.where(causal[None, None], s, -jnp.inf)
        # the summaries of every chunk of every earlier window
        s_sum = jnp.einsum("bqhk,bjhk->bhqj", cut(q), ks) * hd ** -0.5
        s_sum = jnp.where((chunk_of < w * (window // chunk))[None, None, None],
                          s_sum, -jnp.inf)
        p = jax.nn.softmax(jnp.concatenate([s, s_sum], axis=-1), axis=-1)
        return (jnp.einsum("bhqt,bthk->bqhk", p[..., :window], cut(v))
                + jnp.einsum("bhqj,bjhk->bqhk", p[..., window:], vs))

    o = jax.lax.map(one_window, jnp.arange(S // window))  # [n, B, W, H, hd]
    return jnp.moveaxis(o, 0, 1).reshape(B, S, H, hd)


def _rows(fn, x, block):
    """``fn`` over x [B, S, d] in blocks of rows (it acts on each row
    alone); ``S`` a whole number of blocks."""
    B, S, d = x.shape
    out = jax.lax.map(
        fn, jnp.moveaxis(x.reshape(B, S // block, block, d), 1, 0))
    return jnp.moveaxis(out, 0, 1).reshape(B, S, -1)


def forward(params, tokens, *, rope_theta: float, rms_norm_eps: float,
            window: int, chunk: int):
    """tokens [B, S] int32 -> logits [B, S, columns of ``lm_head``]
    float32 (all ``num_pred_heads x vocab`` of the model's head; a
    caller that wants the next byte's alone hands head 0's columns)."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    layers = params["layers"]
    depth = layers["wq"].shape[0]
    S = tokens.shape[1]
    tokens = jnp.pad(tokens, ((0, 0), (0, -S % window)))
    with jax.default_matmul_precision("highest"):
        x = f32(params["embed"])[tokens]
        for i in range(depth):
            # one layer's weights in float32 at a time: without the
            # barrier the compiler converts every layer's up front
            x, layers = jax.lax.optimization_barrier((x, layers))
            lp = {name: f32(a[i]) for name, a in layers.items()}
            h = _rms_norm(x, lp["attn_norm"], rms_norm_eps)
            q = jnp.einsum("bsd,dhk->bshk", h, lp["wq"])
            k = jnp.einsum("bsd,dhk->bshk", h, lp["wk"])
            v = jnp.einsum("bsd,dhk->bshk", h, lp["wv"])
            q, k = _rope(q, rope_theta), _rope(k, rope_theta)
            o = eva_attention(q, k, v, lp["eva_phi"], lp["eva_mu"],
                              window, chunk)
            x = x + jnp.einsum("bqhk,hkd->bqd", o, lp["wo"])

            def ffn(rows, lp=lp):
                h = _rms_norm(rows, lp["mlp_norm"], rms_norm_eps)
                return (jax.nn.silu(h @ lp["w_gate"])
                        * (h @ lp["w_up"])) @ lp["w_down"]

            x = x + _rows(ffn, x, window)
        x = _rms_norm(x[:, :S], f32(params["norm_f"]), rms_norm_eps)
        return x @ f32(params["lm_head"])
