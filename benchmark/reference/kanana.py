"""Plain float32 reference of the kanana-2-30b-a3b-instruct-2601 block
(``model_type: deepseek_v3``; kakaocorp,
huggingface.co/kakaocorp/kanana-2-30b-a3b-instruct-2601).

Independent of ``ray_tpu/models`` and ``ray_tpu/ops``: straightforward
``jax.numpy`` following HF ``modeling_deepseek_v3`` with this config's
keys, as ISSUE 41 writes the layer down, in the EXPANDED form only. With x
the residual stream and ``h = RMSNorm(x)``:

``q = h Wq`` -> heads x (nope + rope) lanes (``q_lora_rank`` null: no
query compression), split ``q_nope | q_pe``; ``[c~ | k~_pe] = h Wkv_a``
-> ``kv_lora_rank`` + rope lanes; ``c = RMSNorm(c~; kv_a_layernorm)``;
``k_pe = RoPE(k~_pe)``, ONE key part for all heads; ``q_pe = RoPE(q_pe)``;
``[k_nope | v] = c Wkv_b`` -> heads x (nope + v); scores ``(q_nope . k_nope
+ q_pe . k_pe) / sqrt(nope + rope)``, causal, softmax in float32;
``x += (P v) Wo``. The first ``first_k_dense_replace`` layers then add a
SwiGLU of ``intermediate_size``; every later layer, with ``g =
RMSNorm(x)``: ``s = sigmoid(g Wr)``; the ``top_k`` largest of ``s + b``
(``e_score_correction_bias``: it chooses and weighs nothing); weights
``s`` of the chosen, divided by their sum + 1e-20 (``norm_topk_prob``),
times ``routed_scaling_factor``; ``x += sum_e w_e SwiGLU_e(g) +
SwiGLU_shared(g)``. Final RMSNorm, untied output head.

No kernels, no cache, no batching, no sorting, no absorbed product.
float32 throughout under ``jax.default_matmul_precision("highest")``.

Sized so that 16,392 tokens at the published widths fit beside a live
engine, none of which changes the arithmetic: attention goes by blocks
of ``QUERY_BLOCK`` queries one after another (``jax.lax.map``: scores
[heads, 256, S] float32 a block); the experts go one at a time over all
tokens (``jax.lax.scan`` over the expert axis, each expert's matrices
upcast as it is used), every expert computed for every token and weighed
by the token's weight for it, 0 where it was not chosen; the layers'
parameters arrive STACKED and each layer cuts its slice behind an
``optimization_barrier``, so the float32 copies exist a layer at a time;
the embedding is gathered before it is upcast; and ``forward_rows``
hands back the output head UNAPPLIED (``RowsOfLogits``), which computes
the logits of the rows it is asked for: the harness slices a few rows of
[S, 128,256] float32 logits that would not fit whole (8.4 GB at 16,392).

Departures from HF's code, none of which changes the function: matrices
are input-first ([d, H, k]) as the system stores them, where HF stores
[out, in]; RoPE's interleave is written as the rotation of ADJACENT
pairs of lanes (2i, 2i+1) by theta_i and the lanes keep their order,
where HF de-interleaves q and k alike and then rotates halves (every q.k
is the same); ``n_group`` = ``topk_group`` = 1, so the group-limited
choice is the plain top-k and is left out; ties in the top-k go to the
lower expert index (``jax.lax.top_k``); no dropout, no auxiliary loss.

Takes the SYSTEM'S OWN parameter arrays under the names below
(``benchmark/builders/kanana.py`` maps them). Attention, each layer:
``attn_norm [d]``, ``q_proj [d, H, nope + rope]``, ``kv_a_proj [d, R +
rope]``, ``kv_a_layernorm [R]``, ``kv_b_proj [R, H, nope + v]``, ``o_proj
[H, v, d]``, ``mlp_norm [d]``. A dense layer: ``gate/up [d, f]``, ``down
[f, d]``. An expert layer: ``router [d, E]``, ``router_bias [E]``,
``e_gate/e_up [E, d, f]``, ``e_down [E, f, d]``, ``s_gate/s_up [d, fs]``,
``s_down [fs, d]``. Model: ``embed [V, d]``, ``dense_layers`` and
``moe_layers`` (dicts of those, stacked over the layers of a kind),
``norm_f [d]``, ``lm_head [d, V]``.

``fault`` names ONE deliberate departure, for the controls that the
comparison has to refuse (``FAULTS``).
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

QUERY_BLOCK = 256

FAULTS = ("softmax_router", "weights_with_bias", "no_renorm",
          "no_routed_scale", "no_shared_expert", "rope_half_split",
          "scale_by_nope", "no_kv_norm", "rope_on_latent",
          "dense_as_expert", "skip_last_layer")


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _rope(x, theta: float, half_split: bool = False):
    """x [B, S, H, r] at positions 0..S-1: lanes (2i, 2i+1) turn by
    ``theta**(-2i/r)`` a position. ``half_split`` (a fault): lanes (i, i +
    r/2) instead, which is right only for weights laid out for it."""
    r = x.shape[-1]
    inv = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    if half_split:
        a, b = x[..., :r // 2], x[..., r // 2:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def _attention(q, k, v, scale: float):
    """q, k [B, S, H, dk], v [B, S, H, dv] -> [B, S, H, dv]; causal. By
    blocks of queries; each block sees every key."""
    B, S, H, dk = q.shape
    cols = jnp.arange(S)
    blocks = -(-S // QUERY_BLOCK)
    # queries padded to whole blocks; a padding row sees every key and
    # is cut off again below
    q = jnp.pad(q, ((0, 0), (0, blocks * QUERY_BLOCK - S), (0, 0), (0, 0)))

    def one_block(block):
        rows, qb = block                       # [256], [B, 256, H, dk]
        s = jnp.einsum("bqhk,bthk->bhqt", qb, k) * scale
        s = jnp.where((rows[:, None] >= cols[None, :])[None, None], s,
                      -jnp.inf)
        return jnp.einsum("bhqt,bthk->bqhk", jax.nn.softmax(s, -1), v)

    out = jax.lax.map(one_block, (
        jnp.arange(blocks * QUERY_BLOCK).reshape(blocks, QUERY_BLOCK),
        jnp.moveaxis(q.reshape(B, blocks, QUERY_BLOCK, H, dk), 1, 0)))
    return jnp.moveaxis(out, 0, 1).reshape(B, -1, H, v.shape[-1])[:, :S]


def _latent_attention(h, lp, *, nope: int, rope: int, rank: int,
                      theta: float, eps: float, fault):
    q = jnp.einsum("bsd,dhk->bshk", h, _f32(lp["q_proj"]))
    half = fault == "rope_half_split"
    q_nope, q_pe = q[..., :nope], _rope(q[..., nope:], theta, half)
    down = h @ _f32(lp["kv_a_proj"])                      # [B, S, R + rope]
    c = down[..., :rank]
    if fault != "no_kv_norm":
        c = _rms_norm(c, _f32(lp["kv_a_layernorm"]), eps)
    if fault == "rope_on_latent":
        c = _rope(c[:, :, None, :], theta)[:, :, 0, :]
    k_pe = _rope(down[..., None, rank:], theta, half)     # [B, S, 1, rope]
    kv = jnp.einsum("bsr,rhk->bshk", c, _f32(lp["kv_b_proj"]))
    k_nope, v = kv[..., :nope], kv[..., nope:]
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe, k_nope.shape[:3] + (rope,))], -1)
    scale = 1.0 / math.sqrt(nope if fault == "scale_by_nope"
                            else nope + rope)
    o = _attention(jnp.concatenate([q_nope, q_pe], -1), k, v, scale)
    return jnp.einsum("bqhk,hkd->bqd", o, _f32(lp["o_proj"]))


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ _f32(gate)) * (h @ _f32(up))) @ _f32(down)


def _expert_block(h, lp, *, top_k: int, norm_topk_prob: bool,
                  routed_scale: float, forced, fault):
    """h [B, S, d] -> (out, chosen experts [B, S, K])."""
    logits = h @ _f32(lp["router"])                              # [B, S, E]
    if fault == "softmax_router":
        scores = jax.nn.softmax(logits, axis=-1)
    else:
        scores = jax.nn.sigmoid(logits)
    biased = scores + _f32(lp["router_bias"])
    experts = jax.lax.top_k(biased, top_k)[1] if forced is None else forced
    weights = jnp.take_along_axis(
        biased if fault == "weights_with_bias" else scores, experts, axis=-1)
    if norm_topk_prob and fault != "no_renorm":
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    if fault != "no_routed_scale":
        weights = weights * routed_scale
    dense_w = jnp.sum(jax.nn.one_hot(experts, scores.shape[-1])
                      * weights[..., None], axis=-2)             # [B, S, E]

    def add_expert(total, e):
        gate, up, down, w = e
        return total + _swiglu(h, gate, up, down) * w[..., None], None

    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(h), (
        lp["e_gate"], lp["e_up"], lp["e_down"], jnp.moveaxis(dense_w, -1, 0)))
    if fault != "no_shared_expert":
        out = out + _swiglu(h, lp["s_gate"], lp["s_up"], lp["s_down"])
    return out, experts


@jax.tree_util.register_pytree_node_class
class RowsOfLogits:
    """The logits of ``forward_rows``, head unapplied: ``self[index]``
    (an index into ``[B, S]``, as into the logits' first two axes) is the
    final norm's rows at ``index`` times the head, float32 at "highest":
    ``forward(...)[index]`` without the [B, S, V] array."""

    def __init__(self, x, head):
        self.x, self.head = x, head

    def __getitem__(self, index):
        with jax.default_matmul_precision("highest"):
            return self.x[index] @ _f32(self.head)

    def tree_flatten(self):
        return (self.x, self.head), None

    @classmethod
    def tree_unflatten(cls, _, leaves):
        return cls(*leaves)


def forward_rows(params, tokens, *, qk_nope_head_dim: int,
                 qk_rope_head_dim: int, kv_lora_rank: int, rope_theta: float,
                 rms_norm_eps: float, top_k: int, routed_scaling_factor: float,
                 norm_topk_prob: bool = True, forced_experts=None,
                 with_routing: bool = False, fault: Optional[str] = None):
    """tokens [B, S] int32 -> ``RowsOfLogits`` over [B, S]; with
    ``with_routing`` also ``{"experts": [L_moe, B, S, K]}``.
    ``forced_experts`` [L_moe, B, S, K] makes every expert layer use those
    experts instead of its own top-k (for comparing a lower-precision
    system whose near-tied choices differ)."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"no fault {fault!r}; known: {FAULTS}")
    attn = dict(nope=qk_nope_head_dim, rope=qk_rope_head_dim,
                rank=kv_lora_rank, theta=rope_theta, eps=rms_norm_eps,
                fault=fault)
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"][tokens])
        stacks = {"dense": params["dense_layers"] or {},
                  "moe": params["moe_layers"]}
        plan = [(kind, i) for kind in ("dense", "moe")
                for i in range(len(stacks[kind].get("attn_norm", ())))]
        if fault == "skip_last_layer":
            plan = plan[:-1]
        chosen = []
        for kind, i in plan:
            x, stacks = jax.lax.optimization_barrier((x, stacks))
            is_moe = kind == "moe"
            lp = {name: a[i] for name, a in stacks[kind].items()}
            h = _rms_norm(x, _f32(lp["attn_norm"]), rms_norm_eps)
            x = x + _latent_attention(h, lp, **attn)
            h = _rms_norm(x, _f32(lp["mlp_norm"]), rms_norm_eps)
            if not is_moe and fault == "dense_as_expert":
                # the dense layer's FFN replaced by the first expert
                # layer's block
                lp = {name: a[0] for name, a in stacks["moe"].items()}
                is_moe = None
            if is_moe is False:
                x = x + _swiglu(h, lp["gate"], lp["up"], lp["down"])
                continue
            out, experts = _expert_block(
                h, lp, top_k=top_k, norm_topk_prob=norm_topk_prob,
                routed_scale=routed_scaling_factor, fault=fault,
                forced=(None if forced_experts is None or is_moe is None
                        else forced_experts[i]))
            x = x + out
            if is_moe:
                chosen.append(experts)
        rows = RowsOfLogits(_rms_norm(x, _f32(params["norm_f"]),
                                      rms_norm_eps), params["lm_head"])
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
