"""Plain float32 reference of the DeepSeek-V3.2 block (``model_type:
deepseek_v32``; huggingface.co/deepseek-ai/DeepSeek-V3.2), as ISSUE 43
writes the layer down from the published inference code of V3.2-Exp.

Independent of ``ray_tpu/models`` and ``ray_tpu/ops``: straightforward
``jax.numpy`` in the EXPANDED form only, the selection as a MASK from a
full float32 ``I``. With x the residual stream and ``h = RMSNorm(x)``:

- compressed query: ``qr = RMSNorm(h Wq_a; q_a_layernorm)``, ``q = qr
  Wq_b`` -> heads x (nope + rope), ``q_pe = RoPE(q_pe)``;
- latent row: ``[c~ | k~_pe] = h Wkv_a``, ``c = RMSNorm(c~;
  kv_a_layernorm)``, ``k_pe = RoPE(k~_pe)``, one key part for all heads.
  RoPE here turns ADJACENT pairs of lanes, by YaRN's frequencies, cos and
  sin unscaled;
- softmax scale ``(nope + rope)^-0.5 m^2``, ``m = 0.1 mscale_all_dim
  ln(factor) + 1``;
- lightning indexer: ``q_I = qr W_qI`` -> Hi x Di, ``k_I = LayerNorm(h
  W_kI)`` (with a bias, eps 1e-6), the first ``rope`` lanes of each
  turned in the HALF-SPLIT form by the same frequencies; ``w = h W_w
  Hi^-0.5 Di^-0.5``; ``I[t, s] = sum_j w[t, j] relu(q_I[t, j] . k_I[s])``
  for ``s <= t``; ``S_t`` = the ``min(index_topk, t + 1)`` rows of largest
  ``I[t, .]``, ties to the earlier row;
- ``[k_nope | v] = c Wkv_b``; scores over ``S_t`` alone, softmax, ``o = P
  v``, ``x += o Wo``;
- leading dense layers: SwiGLU; expert layers: ``s = sigmoid(g Wr)``,
  ``s' = s + b``; ``n_group`` groups of neighbours, a group's score the
  sum of its two largest ``s'``, the ``topk_group`` best stay and ``s'``
  of the others is 0; the ``top_k`` largest ``s'``; weights ``s`` of the
  chosen over their sum + 1e-20, times ``routed_scaling_factor``; ``x +=
  sum over the HELD experts + SwiGLU_shared(g)``: ``experts_held`` =
  (first, count) is this chip's share of the router's experts and what
  the others would have added is left out ((0, E) is the uncut layer).

Departures from the published code, none of which changes the function
but the last: no Hadamard turn of ``q_I`` and ``k_I`` (orthogonal: every
``q_I . k_I`` is as it was); no FP8 of the indexer's operands or of the
cache (a deployment's precision; the configuration states bf16); the
multi-token-prediction module is not run (it lies behind the last
layer); matrices are input-first as the system stores them; ``kv_b_proj``
arrives as [R, H, nope + v].

Sized as ``reference/kanana.py`` is (queries in blocks, an expert at a
time, one layer's float32 copies at a time, the head unapplied:
``RowsOfLogits``). ``forced_experts`` [L_moe, B, S, K] and
``forced_selection`` [L, B, S, S] bool make the layers use the SYSTEM's
chosen experts / rows (bf16 swaps near-ties at the 8th expert and at the
2,048th row); ``fault`` names ONE deliberate departure (``FAULTS``).
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

QUERY_BLOCK = 128
HEAD_GROUP = 16          # heads whose keys and values are expanded at once
FFN_CHUNK = 2048         # columns of a wide feed-forward at once

FAULTS = ("no_group_limit", "group_score_one_best", "groups_without_bias",
          "weights_with_bias", "no_routed_scale", "no_mscale", "plain_rope",
          "indexer_rope_interleaved", "latent_rope_half_split", "no_relu",
          "no_index_weights", "topk_minus_one", "previous_selection",
          "no_q_norm", "no_index_k_norm", "dense_attention",
          "half_topk", "skip_last_layer")


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _layer_norm(x, w, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w + b


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


def _rope(x, inv_freq, half_split: bool):
    """x [B, S, H, r] at positions 0..S-1. Adjacent pairs (2i, 2i + 1)
    turn by frequency i; ``half_split``: lanes (i, i + r/2) instead."""
    r = x.shape[-1]
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq[None]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    if half_split:
        a, b = x[..., :r // 2], x[..., r // 2:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def topk_rows(scores, seen, k: int):
    """The ``k`` largest of ``scores`` [..., S] among ``seen``, ties to
    the earlier row, as a mask; every seen row where there are fewer.
    The k-th largest value comes from a full sort; the rows above it are
    in, and of the rows AT it the first by position that fill ``k``."""
    if k >= scores.shape[-1]:
        return seen
    masked = jnp.where(seen, scores, -jnp.inf)
    kth = -jnp.sort(-masked, axis=-1)[..., k - 1:k]
    above, at = masked > kth, masked == kth
    room = k - jnp.sum(above, axis=-1, keepdims=True)
    return seen & (above | (at & (jnp.cumsum(at, axis=-1) <= room)))


def _by_blocks(fn, S: int, *rows_of):
    """``fn(rows, *blocks)`` over blocks of ``QUERY_BLOCK`` positions
    ``rows`` (the last padded; its padding cut off again): arrays
    ``rows_of`` [B, S, ...] go in by blocks, results [B, block, ...]
    come back joined to [B, S, ...]."""
    blocks = -(-S // QUERY_BLOCK)
    pad = blocks * QUERY_BLOCK - S

    def split(a):
        a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        return jnp.moveaxis(a.reshape(a.shape[0], blocks, QUERY_BLOCK,
                                      *a.shape[2:]), 1, 0)

    out = jax.lax.map(lambda args: fn(*args), (
        jnp.arange(blocks * QUERY_BLOCK).reshape(blocks, QUERY_BLOCK),
        *map(split, rows_of)))
    return jax.tree.map(
        lambda o: jnp.moveaxis(o, 0, 1).reshape(
            o.shape[1], -1, *o.shape[3:])[:, :S], out)


def _sparse_latent_attention(h, lp, *, nope: int, rope: int, rank: int,
                             theta: float, yarn, mscale_all_dim: float,
                             eps: float, index_topk: int, forced, fault):
    """-> (the attention's output [B, S, d], the selection [B, S, S])."""
    B, S, _ = h.shape
    inv = yarn_inv_freq(rope, theta, None if fault == "plain_rope" else yarn)
    qr = h @ _f32(lp["q_a_proj"])
    if fault != "no_q_norm":
        qr = _rms_norm(qr, _f32(lp["q_a_layernorm"]), eps)
    split = fault == "latent_rope_half_split"
    down = h @ _f32(lp["kv_a_proj"])
    c = _rms_norm(down[..., :rank], _f32(lp["kv_a_layernorm"]), eps)
    k_pe = _rope(down[..., None, rank:], inv, split)          # [B, S, 1, r]

    # the indexer
    idx_split = fault != "indexer_rope_interleaved"
    q_i = jnp.einsum("bsr,rhk->bshk", qr, _f32(lp["indexer_wq_b"]))
    q_i = jnp.concatenate([_rope(q_i[..., :rope], inv, idx_split),
                           q_i[..., rope:]], -1)
    k_i = h @ _f32(lp["indexer_wk"])
    if fault != "no_index_k_norm":
        norm = _f32(lp["indexer_k_norm"])
        k_i = _layer_norm(k_i, norm[0], norm[1], 1e-6)
    k_i = jnp.concatenate(
        [_rope(k_i[:, :, None, :rope], inv, idx_split)[:, :, 0],
         k_i[..., rope:]], -1)
    Hi, Di = q_i.shape[-2:]
    w = (h @ _f32(lp["indexer_weights_proj"])) * (Hi ** -0.5 * Di ** -0.5)
    if fault == "no_index_weights":
        w = jnp.ones_like(w)
    if fault == "previous_selection":
        # the query BEFORE's scores pick among the rows before this one
        q_i = jnp.concatenate([q_i[:, :1], q_i[:, :-1]], axis=1)
        w = jnp.concatenate([w[:, :1], w[:, :-1]], axis=1)
    cols = jnp.arange(S)
    keep = {"topk_minus_one": index_topk - 1,
            "half_topk": index_topk // 2}.get(fault, index_topk)

    def select(rows, q_i, w):
        seen = jnp.broadcast_to(rows[:, None] >= cols[None, :],
                                (B, len(rows), S))
        if fault == "dense_attention":
            return seen
        s = jnp.einsum("bqhk,btk->bhqt", q_i, k_i)
        if fault != "no_relu":
            s = jax.nn.relu(s)
        score = jnp.einsum("bhqt,bqh->bqt", s, w)
        if fault != "previous_selection":
            return topk_rows(score, seen, keep)
        own = jnp.broadcast_to(rows[:, None] == cols[None, :], seen.shape)
        return topk_rows(score, seen & ~own, keep) | own

    # the selection of every query, [B, S, S]: the same for all heads
    mask = (_by_blocks(select, S, q_i, w) if forced is None
            else forced & (cols[:, None] >= cols[None, :]))

    m = 1.0
    if yarn is not None and mscale_all_dim and fault != "no_mscale":
        m = 0.1 * mscale_all_dim * math.log(yarn[0]) + 1.0
    scale = m * m / math.sqrt(nope + rope)
    H = lp["q_b_proj"].shape[1]
    group = math.gcd(H, HEAD_GROUP)

    def heads(total, weights):
        """``group`` heads at a time: their q, expanded keys and values,
        the masked softmax by blocks of queries, their part of ``o Wo``."""
        q_b, kv_b, o_w = map(_f32, weights)
        q = jnp.einsum("bsr,rhk->bshk", qr, q_b)
        q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], inv, split)],
                            -1)
        kv = jnp.einsum("bsr,rhk->bshk", c, kv_b)
        k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
            k_pe, kv.shape[:3] + (rope,))], -1)
        v = kv[..., nope:]

        def block(rows, qb, mb):
            s = jnp.einsum("bqhk,bthk->bhqt", qb, k) * scale
            s = jnp.where(mb[:, None], s, -jnp.inf)
            # a padding query sees nothing: its row is cut off again
            p = jnp.where(mb[:, None], jax.nn.softmax(s, -1), 0.0)
            return jnp.einsum("bhqt,bthk->bqhk", p, v)

        o = _by_blocks(block, S, q, mask)
        return total + jnp.einsum("bqhk,hkd->bqd", o, o_w), None

    def grouped(a, axis):
        a = jnp.moveaxis(a, axis, 0)
        return a.reshape(H // group, group, *a.shape[1:])

    out, _ = jax.lax.scan(heads, jnp.zeros_like(h), (
        jnp.moveaxis(grouped(lp["q_b_proj"], 1), 1, 2),
        jnp.moveaxis(grouped(lp["kv_b_proj"], 1), 1, 2),
        grouped(lp["o_proj"], 0)))
    return out, mask


def _swiglu_wide(h, gate, up, down):
    """``_swiglu`` of a WIDE feed-forward, ``FFN_CHUNK`` of its columns
    at a time (the float32 copies of an 18,432-wide layer and its
    [S, 18,432] activations would not fit beside a live engine)."""
    f = gate.shape[-1]
    chunk = math.gcd(f, FFN_CHUNK)

    def add(total, w):
        g, u, d = w
        return total + _swiglu(h, g, u, d), None

    out, _ = jax.lax.scan(add, jnp.zeros_like(h), (
        jnp.moveaxis(gate.reshape(-1, f // chunk, chunk), 1, 0),
        jnp.moveaxis(up.reshape(-1, f // chunk, chunk), 1, 0),
        down.reshape(f // chunk, chunk, -1)))
    return out


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ _f32(gate)) * (h @ _f32(up))) @ _f32(down)


def grouped_sigmoid_topk(scores, bias, *, top_k: int, n_group: int,
                         topk_group: int, fault=None):
    """scores [..., E] (sigmoid of the logits), bias [E] -> the chosen
    experts [..., K]: the group-limited top-k of ``scores + bias``."""
    biased = scores + bias
    if n_group > 1 and fault != "no_group_limit":
        E = scores.shape[-1]
        by = (scores if fault == "groups_without_bias" else biased).reshape(
            *scores.shape[:-1], n_group, E // n_group)
        best = jax.lax.top_k(by, 1 if fault == "group_score_one_best"
                             else 2)[0].sum(-1)             # [..., groups]
        kept = jax.lax.top_k(best, topk_group)[1]
        stays = jnp.any(jax.nn.one_hot(kept, n_group, dtype=bool), axis=-2)
        biased = jnp.where(jnp.repeat(stays, E // n_group, axis=-1),
                           biased, 0.0)
    return jax.lax.top_k(biased, top_k)[1]


def _expert_block(h, lp, *, top_k: int, n_group: int, topk_group: int,
                  norm_topk_prob: bool, routed_scale: float, held, forced,
                  fault):
    """h [B, S, d] -> (the HELD experts' part + the shared expert, the
    chosen experts [B, S, K])."""
    scores = jax.nn.sigmoid(h @ _f32(lp["router"]))              # [B, S, E]
    bias = _f32(lp["router_bias"])
    experts = forced if forced is not None else grouped_sigmoid_topk(
        scores, bias, top_k=top_k, n_group=n_group, topk_group=topk_group,
        fault=fault)
    weights = jnp.take_along_axis(
        scores + bias if fault == "weights_with_bias" else scores, experts,
        axis=-1)
    if norm_topk_prob:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    if fault != "no_routed_scale":
        weights = weights * routed_scale
    dense_w = jnp.sum(jax.nn.one_hot(experts, scores.shape[-1])
                      * weights[..., None], axis=-2)             # [B, S, E]
    first, count = held
    dense_w = dense_w[..., first:first + count]   # the others: left out

    def add_expert(total, e):
        gate, up, down, w = e
        return total + _swiglu(h, gate, up, down) * w[..., None], None

    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(h), (
        lp["e_gate"], lp["e_up"], lp["e_down"], jnp.moveaxis(dense_w, -1, 0)))
    return (out + _swiglu_wide(h, lp["s_gate"], lp["s_up"], lp["s_down"]),
            experts)


@jax.tree_util.register_pytree_node_class
class RowsOfLogits:
    """The logits of ``forward_rows``, head unapplied: ``self[index]``
    (an index into ``[B, S]``) is the final norm's rows at ``index``
    times the head, float32 at "highest"."""

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
                 yarn, mscale_all_dim: float, rms_norm_eps: float,
                 index_topk: int, top_k: int, n_group: int, topk_group: int,
                 routed_scaling_factor: float, norm_topk_prob: bool = True,
                 experts_held=None, forced_experts=None,
                 forced_selection=None, with_choices: bool = False,
                 fault: Optional[str] = None):
    """tokens [B, S] int32 -> ``RowsOfLogits`` over [B, S]; with
    ``with_choices`` also ``{"experts": [L_moe, B, S, K], "selection":
    [L, B, S, S] bool}``. ``experts_held`` (first, count): the share of
    the router's experts whose weights ``params`` hold (None: all)."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"no fault {fault!r}; known: {FAULTS}")
    attn = dict(nope=qk_nope_head_dim, rope=qk_rope_head_dim,
                rank=kv_lora_rank, theta=rope_theta, yarn=yarn,
                mscale_all_dim=mscale_all_dim, eps=rms_norm_eps,
                index_topk=index_topk, fault=fault)
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"][tokens])
        stacks = {"dense": params["dense_layers"] or {},
                  "moe": params["moe_layers"]}
        plan = [(kind, i) for kind in ("dense", "moe")
                for i in range(len(stacks[kind].get("attn_norm", ())))]
        if fault == "skip_last_layer":
            plan = plan[:-1]
        if experts_held is None:
            experts_held = (0, stacks["moe"]["router"].shape[-1])
        chosen, selected = [], []
        for n, (kind, i) in enumerate(plan):
            x, stacks = jax.lax.optimization_barrier((x, stacks))
            lp = {name: a[i] for name, a in stacks[kind].items()}
            h = _rms_norm(x, _f32(lp["attn_norm"]), rms_norm_eps)
            out, mask = _sparse_latent_attention(
                h, lp, forced=(None if forced_selection is None
                               else forced_selection[n]), **attn)
            x = x + out
            if with_choices:
                selected.append(mask)
            h = _rms_norm(x, _f32(lp["mlp_norm"]), rms_norm_eps)
            if kind == "dense":
                x = x + _swiglu_wide(h, lp["gate"], lp["up"], lp["down"])
                continue
            out, experts = _expert_block(
                h, lp, top_k=top_k, n_group=n_group, topk_group=topk_group,
                norm_topk_prob=norm_topk_prob,
                routed_scale=routed_scaling_factor, held=experts_held,
                fault=fault, forced=(None if forced_experts is None
                                     else forced_experts[i]))
            x = x + out
            chosen.append(experts)
        rows = RowsOfLogits(_rms_norm(x, _f32(params["norm_f"]),
                                      rms_norm_eps), params["lm_head"])
    if with_choices:
        return rows, {"experts": jnp.stack(chosen),
                      "selection": jnp.stack(selected)}
    return rows


def forward(params, tokens, **kw):
    """tokens [B, S] int32 -> logits [B, S, V] float32."""
    out = forward_rows(params, tokens, **kw)
    if kw.get("with_choices"):
        return out[0][:, :], out[1]
    return out[:, :]
