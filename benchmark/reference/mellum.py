"""Plain float32 reference of the Mellum2-12B-A2.5B-Instruct block.

Independent of ``ray_tpu/models``: straightforward ``jax.numpy`` following
the published ``config.json`` (``model_type`` ``mellum``; JetBrains,
huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct) as ISSUE 32 writes
the layer down. With h the hidden state and t a position:

``a = RMSNorm(h)``; ``q = a Wq`` [heads x head_dim], ``k = a Wk``,
``v = a Wv`` [kv heads x head_dim], no bias, ``head_dim`` a key of its own
(128, not hidden / heads); q and k rotated (rotate-half) by the table of
THIS layer's kind: a ``sliding_attention`` layer by the default one,
``inv_freq_i = theta**(-2i/head_dim)``; a ``full_attention`` layer by
YaRN's: with ``d(n) = head_dim ln(original / (2 pi n)) / (2 ln theta)``,
``low = floor(d(beta_fast))`` and ``high = ceil(d(beta_slow))`` clipped
to [0, head_dim - 1], ``ramp_i = clip((i - low) / (high - low), 0, 1)``,
``inv_freq_i = base_i (1 - ramp_i) + base_i / factor ramp_i``, and cos
and sin both times ``attention_factor``. Scores ``q.k / sqrt(head_dim)``,
causal, and in a sliding layer key j is visible to query i only if
``i - j < sliding_window``; softmax in float32; grouped-query attention
(heads / kv heads query heads a KV head); ``h += o Wo``. Then
``b = RMSNorm(h)``; ``p = softmax(b Wr)`` over all experts; the ``top_k``
largest, divided by their sum (``norm_topk_prob``);
``h += sum_e p_e (silu(b Wg_e) * (b Wu_e)) Wd_e``; no shared expert.
Final RMSNorm, untied output head.

No kernels, no cache, no sorting: a Python loop over layers. float32
throughout under ``jax.default_matmul_precision("highest")`` (on a TPU an
f32 matmul otherwise runs in bf16 passes).

Sized so that 8,200 tokens at the published widths fit beside a live
engine, which takes three things that change no arithmetic. Attention
goes by blocks of ``QUERY_BLOCK`` queries, one after another
(``jax.lax.map``: scores [heads, 512, S] float32 a block, 0.54 GB at S =
8,200; written as a Python loop, the compiler kept all 17 blocks' scores
alive at once). The experts go one at a time over all tokens
(``jax.lax.scan`` over the expert axis, each expert's three matrices
upcast as it is used and its part added to the sum), every expert
computed for every token and weighed by the token's top-k weight for it,
0 for the experts it did not choose. And the layers' parameters arrive
STACKED over layers, as the system holds them, and each layer cuts its
own slice behind a ``jax.lax.optimization_barrier`` on its input and the
stack, so that the slices (0.8 GB of expert weights a layer) exist one
layer at a time and not all ahead.

Departures from the published model, none of which changes the function:
the projection matrices are laid out input-first ([d, H, hd], experts
[E, d, f]) as the system stores them, where HF stores [out, in]; ties in
the top-k go to the lower expert index (``jax.lax.top_k``); no QK-norm
(the config has no key for one); the "MTP head" the model card mentions
is no part of the forward pass and is left out; ``intermediate_size``
(7168) is unused, every ``mlp_layer_types`` entry being ``sparse``; the
auxiliary router losses of training are left out.

Takes the SYSTEM'S OWN parameter arrays (mapped to the names below by
``benchmark/builders/mellum.py``), so nothing is held twice.

Layer params, each with a leading axis over layers: ``attn_norm [d]``,
``wq [d, H, hd]``, ``wk/wv [d, Hkv, hd]``, ``wo [H, hd, d]``, ``mlp_norm
[d]``, ``router [d, E]``, ``e_gate/e_up [E, d, f]``, ``e_down [E, f,
d]``; model: ``embed [V, d]``, ``layers`` (a dict of those stacks),
``norm_f [d]``, ``lm_head [d, V]``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512


def _rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def inv_freq(head_dim: int, rope: dict):
    """[head_dim / 2] inverse frequencies and the factor on cos and sin
    of one ``rope_parameters`` entry (``rope_type`` default or yarn)."""
    theta = float(rope["rope_theta"])
    base = theta ** (-jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                     / head_dim)
    if rope["rope_type"] == "default":
        return base, 1.0
    if rope["rope_type"] != "yarn":
        raise ValueError(f"no rope_type {rope['rope_type']!r} here")
    original = rope["original_max_position_embeddings"]

    def lane(turns):
        return (head_dim * math.log(original / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(lane(rope["beta_fast"])), 0)
    high = min(math.ceil(lane(rope["beta_slow"])), head_dim - 1)
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    factor = rope.get("attention_factor")
    if factor is None:
        factor = 0.1 * math.log(rope["factor"]) + 1.0
    return base * (1 - ramp) + base / rope["factor"] * ramp, float(factor)


def _rope(x, rope: dict):
    """x [B, S, H, hd]; rotate-half convention, positions 0..S-1."""
    hd = x.shape[-1]
    inv, factor = inv_freq(hd, rope)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None]
    cos = (jnp.cos(ang) * factor)[None, :, None, :]
    sin = (jnp.sin(ang) * factor)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, window):
    """q [B, S, H, hd], k/v [B, S, Hkv, hd] -> [B, S, H, hd]; causal,
    and with ``window`` key j visible to query i only if i - j < window.
    By blocks of queries; each block sees every key."""
    B, S, H, hd = q.shape
    group = H // k.shape[2]
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    cols = jnp.arange(S)
    blocks = -(-S // QUERY_BLOCK)
    # queries padded to whole blocks; a padding row sees every key and
    # is cut off again below
    q = jnp.pad(q, ((0, 0), (0, blocks * QUERY_BLOCK - S), (0, 0), (0, 0)))

    def one_block(block):
        rows, qb = block                       # [512], [B, 512, H, hd]
        seen = rows[:, None] >= cols[None, :]
        if window is not None:
            seen &= rows[:, None] - cols[None, :] < window
        s = jnp.einsum("bqhk,bthk->bhqt", qb, k) / math.sqrt(hd)
        s = jnp.where(seen[None, None], s, -jnp.inf)
        return jnp.einsum("bhqt,bthk->bqhk", jax.nn.softmax(s, -1), v)

    out = jax.lax.map(one_block, (
        jnp.arange(blocks * QUERY_BLOCK).reshape(blocks, QUERY_BLOCK),
        jnp.moveaxis(q.reshape(B, blocks, QUERY_BLOCK, H, hd), 1, 0)))
    return jnp.moveaxis(out, 0, 1).reshape(B, -1, H, hd)[:, :S]


def _expert_mlp(h, lp, top_k, norm_topk_prob, forced):
    """h [B, S, d] -> (out, chosen experts [B, S, K])."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    probs = jax.nn.softmax(h @ f32(lp["router"]), axis=-1)       # [B, S, E]
    experts = jax.lax.top_k(probs, top_k)[1] if forced is None else forced
    weights = jnp.take_along_axis(probs, experts, axis=-1)
    if norm_topk_prob:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    dense_w = jnp.sum(jax.nn.one_hot(experts, probs.shape[-1])
                      * weights[..., None], axis=-2)             # [B, S, E]

    def add_expert(total, e):
        gate, up, down, w = e
        act = jax.nn.silu(h @ f32(gate)) * (h @ f32(up))
        return total + (act @ f32(down)) * w[..., None], None    # [B, S, d]

    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(h), (
        lp["e_gate"], lp["e_up"], lp["e_down"], jnp.moveaxis(dense_w, -1, 0)))
    return out, experts


def forward(params, tokens, *, layer_types, sliding_window: int,
            rope_parameters: dict, rms_norm_eps: float, top_k: int,
            norm_topk_prob: bool = True, forced_experts=None,
            with_routing: bool = False):
    """tokens [B, S] int32 -> logits [B, S, V] float32; with
    ``with_routing`` also ``{"experts": [L, B, S, K]}``. ``layer_types``
    names each layer's kind (as many as the stacks hold layers) and
    ``rope_parameters`` the rotary table of each kind, both as
    published. ``forced_experts`` [L, B, S, K] makes every layer use
    those experts instead of its own top-k (for comparing a
    lower-precision system whose near-tied choices differ)."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    with jax.default_matmul_precision("highest"):
        x = f32(params["embed"])[tokens]
        chosen = []
        stacks = params["layers"]
        for i, kind in enumerate(layer_types):
            x, stacks = jax.lax.optimization_barrier((x, stacks))
            lp = {name: stack[i] for name, stack in stacks.items()}
            h = _rms_norm(x, f32(lp["attn_norm"]), rms_norm_eps)
            q = jnp.einsum("bsd,dhk->bshk", h, f32(lp["wq"]))
            k = jnp.einsum("bsd,dhk->bshk", h, f32(lp["wk"]))
            v = jnp.einsum("bsd,dhk->bshk", h, f32(lp["wv"]))
            q, k = (_rope(a, rope_parameters[kind]) for a in (q, k))
            o = _attention(q, k, v, sliding_window
                           if kind == "sliding_attention" else None)
            x = x + jnp.einsum("bqhk,hkd->bqd", o, f32(lp["wo"]))
            h = _rms_norm(x, f32(lp["mlp_norm"]), rms_norm_eps)
            out, experts = _expert_mlp(
                h, lp, top_k, norm_topk_prob,
                None if forced_experts is None else forced_experts[i])
            x = x + out
            chosen.append(experts)
        x = _rms_norm(x, f32(params["norm_f"]), rms_norm_eps)
        logits = x @ f32(params["lm_head"])
    if with_routing:
        return logits, {"experts": jnp.stack(chosen)}
    return logits
