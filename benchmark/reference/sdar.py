"""Plain float32 reference of SDAR-30B-A3B-Chat (``sdar_moe``): its block
and its generation by diffusion over blocks.

Independent of ``ray_tpu/models``: straightforward ``jax.numpy`` after
the published configuration and the family's public ``generate.py``
(github.com/JetAstra/SDAR). THE LAYER is Qwen3-MoE's: pre-RMSNorm; q, k,
v projections without bias; RMSNorm of q and of k over EACH HEAD's lanes
(one weight of ``head_dim`` shared by the heads) before the rotary
embedding (rotate-half, theta from the config, the TRUE positions);
grouped-query softmax attention scaled by ``head_dim ** -0.5``; a router
that is one matrix, softmax in float32 over all experts, top-k, the
chosen probabilities renormalised to sum to 1 (``norm_topk_prob``);
every chosen expert a SwiGLU MLP, none dropped, no shared expert; final
RMSNorm, untied head. THE MASK is block-causal: key j is visible to
query i iff ``j // n <= i // n`` for blocks of ``n`` positions, and THE
LOGITS OF POSITION i SCORE THE TOKEN AT i (masked-token prediction, no
autoregressive shift).

No kernels, no cache, no sorting, no scan over layers: every forward
recomputes everything from the tokens under an EXPLICIT mask matrix,
queries a chunk at a time and ONE EXPERT AT A TIME (a loop over experts,
each computed for every token and weighed by the router: nothing of the
size tokens x experts x width is ever built, so the reference fits on
the chip beside the engine it checks). float32 throughout under
``jax.default_matmul_precision("highest")``.

- ``forward(params, tokens, n)``: the block-causal full forward.
- ``denoise_logits(params, prefix, block)``: a pass over one block, the
  last ``len(block)`` rows of ``forward`` over ``prefix + block``.
- ``teacher_forced(params, clean, noised, start, n)``: EVERY block of
  ``clean[start:]`` at once, each in the states ``noised`` gives (rows
  of ``clean[start:]`` with mask ids in): one forward over ``[clean;
  noised_0; noised_1; ...]`` in which a noised block sees the clean
  blocks before it and itself, the mask matrix spelled out. Row for row
  what ``denoise_logits`` gives block by block
  (``benchmark/tests/test_sdar.py`` holds the two equal), at one
  forward's cost.
- ``generate(params, prompt, n_tokens, ...)``: the published procedure
  as a Python loop over ``denoise_logits``: prefill nothing (there is no
  cache), the prompt's last partial block decodes with the first
  generated block, each pass proposes the greedy token with its
  probability at every masked position and keeps the proposals surer
  than the threshold or, where those are fewer than the pass's quota,
  the quota's surest (``low_confidence_dynamic``; ``low_confidence_
  static``: the quota alone; ties to the earlier position); when no mask
  is left the block is done. ``sequential`` is left out.

Departures, none of which changes the function: projections are laid
out input-first ([d, H, hd], experts [E, d, f]) as the system stores
them; ties in the top-k go to the lower expert index
(``jax.lax.top_k``); a pass never rewrites a position the prompt gave
(the quota is cut to what is still masked; ``torch.topk`` over the
published confidences would reach into the given positions).

``forced_experts`` [L, B, S, K] makes every layer use those experts
(for comparing bf16 compute, whose near-tied choices differ);
``fault`` names ONE deliberate departure, for the controls that the
comparison has to refuse (``FAULTS``).

Takes the SYSTEM'S OWN parameter arrays (``benchmark/builders/sdar.py``
maps the names), the layer stacks as they are: ``layers`` holds
``attn_norm [L, d]``, ``wq [L, d, H, hd]``, ``wk/wv [L, d, Hkv, hd]``,
``q_norm/k_norm [L, hd]``, ``wo [L, H, hd, d]``, ``mlp_norm [L, d]``,
``router [L, d, E]``, ``e_gate/e_up [L, E, d, f]``, ``e_down [L, E, f,
d]``; beside it ``embed [V, d]``, ``norm_f [d]``, ``lm_head [d, V]``.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

QUERY_CHUNK = 512

FAULTS = ("causal_in_block", "skip_commit", "qk_norm_all_lanes",
          "no_renormalisation", "autoregressive_shift", "skip_last_layer",
          "int8_weights")


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _w(a, fault=None, axis=-2):
    """A matmul weight in float32; under ``int8_weights`` through int8
    first, one scale per output channel (the largest magnitude over the
    input ``axis``): the nearest precision below the stated bf16. (The
    router, which the configuration states in float32, is no matmul
    weight in this sense.)"""
    f = _f32(a)
    if fault != "int8_weights":
        return f
    scale = jnp.max(jnp.abs(f), axis=axis, keepdims=True) / 127.0
    return jnp.round(f / jnp.maximum(scale, 1e-30)) * scale


def _rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _rope(x, positions, theta):
    """x [B, S, H, hd] at ``positions`` [S]; rotate-half convention."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions.astype(jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(h, lp, positions, mask, theta, eps, fault):
    B, S, _ = h.shape
    q = jnp.einsum("bsd,dhk->bshk", h, _w(lp["wq"], fault, 0))
    k = jnp.einsum("bsd,dhk->bshk", h, _w(lp["wk"], fault, 0))
    v = jnp.einsum("bsd,dhk->bshk", h, _w(lp["wv"], fault, 0))
    if fault == "qk_norm_all_lanes":        # OLMoE's, not this model's
        q = _rms_norm(q.reshape(B, S, -1), jnp.tile(
            _f32(lp["q_norm"]), q.shape[2]), eps).reshape(q.shape)
        k = _rms_norm(k.reshape(B, S, -1), jnp.tile(
            _f32(lp["k_norm"]), k.shape[2]), eps).reshape(k.shape)
    else:
        q = _rms_norm(q, _f32(lp["q_norm"]), eps)
        k = _rms_norm(k, _f32(lp["k_norm"]), eps)
    q, k = _rope(q, positions, theta), _rope(k, positions, theta)
    groups = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(a, groups, axis=2) for a in (k, v))
    out = []
    for at in range(0, S, QUERY_CHUNK):           # queries a chunk at a time
        s = jnp.einsum("bqhk,bthk->bhqt", q[:, at:at + QUERY_CHUNK], k)
        s = s / (q.shape[-1] ** 0.5)
        s = jnp.where(mask[None, None, at:at + QUERY_CHUNK], s, -jnp.inf)
        out.append(jnp.einsum("bhqt,bthk->bqhk", jax.nn.softmax(s, -1), v))
    o = jnp.concatenate(out, axis=1)
    return jnp.einsum("bqhk,hkd->bqd", o, _w(lp["wo"], fault, (0, 1)))


def _expert_mlp(h, lp, top_k, norm_topk_prob, forced, fault):
    """h [B, S, d] -> (out, chosen experts [B, S, K], gap [B, S] between
    the K-th and (K+1)-th router probability). ``forced`` [B, S, K]
    replaces the router's own choice (its probabilities still weigh)."""
    probs = jax.nn.softmax(h @ _f32(lp["router"]), axis=-1)      # [B, S, E]
    ranked, experts = jax.lax.top_k(probs, top_k + 1)
    gap = ranked[..., top_k - 1] - ranked[..., top_k]
    experts = experts[..., :top_k] if forced is None else forced
    weights = jnp.take_along_axis(probs, experts, axis=-1)
    if norm_topk_prob and fault != "no_renormalisation":
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    dense_w = jnp.sum(jax.nn.one_hot(experts, probs.shape[-1])
                      * weights[..., None], axis=-2)             # [B, S, E]

    def one_expert(out, e):
        act = (jax.nn.silu(h @ _w(lp["e_gate"][e], fault))
               * (h @ _w(lp["e_up"][e], fault)))
        return out + (act @ _w(lp["e_down"][e], fault)) * jnp.take(
            dense_w, e, axis=-1)[..., None], None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(h),
                          jnp.arange(probs.shape[-1]))
    return out, experts, gap


def hidden(params, tokens, positions, mask, *, rope_theta: float,
           rms_norm_eps: float, top_k: int, norm_topk_prob: bool = True,
           forced_experts=None, fault: Optional[str] = None):
    """The decoder under an explicit mask: tokens [B, S] at ``positions``
    [S], ``mask`` [S, S] bool (query row, key column) -> the final
    normed hidden rows [B, S, d] and the routing ``{"experts": [L, B,
    S, K], "gap": [L, B, S]}``."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"no fault {fault!r}; known: {FAULTS}")
    layers = params["layers"]
    depth = len(layers["attn_norm"]) - (fault == "skip_last_layer")
    with jax.default_matmul_precision("highest"):
        x = _w(params["embed"][tokens], fault, axis=-1)
        chosen, gaps = [], []
        for i in range(depth):
            x, layers = jax.lax.optimization_barrier((x, layers))
            lp = {name: a[i] for name, a in layers.items()}
            h = _rms_norm(x, _f32(lp["attn_norm"]), rms_norm_eps)
            x = x + _attention(h, lp, positions, mask, rope_theta,
                               rms_norm_eps, fault)
            h = _rms_norm(x, _f32(lp["mlp_norm"]), rms_norm_eps)
            out, experts, gap = _expert_mlp(
                h, lp, top_k, norm_topk_prob,
                None if forced_experts is None else forced_experts[i], fault)
            x = x + out
            chosen.append(experts)
            gaps.append(gap)
        x = _rms_norm(x, _f32(params["norm_f"]), rms_norm_eps)
    return x, {"experts": jnp.stack(chosen), "gap": jnp.stack(gaps)}


def logits_of(params, rows, fault: Optional[str] = None):
    """The head over normed hidden ``rows`` [..., d] -> [..., V]."""
    with jax.default_matmul_precision("highest"):
        return rows @ _w(params["lm_head"], fault)


def block_causal_mask(positions, n: int, fault: Optional[str] = None):
    """[S, S]: key j visible to query i iff ``j // n <= i // n``; under
    ``causal_in_block`` iff ``j <= i``."""
    if fault == "causal_in_block":
        return positions[None, :] <= positions[:, None]
    return positions[None, :] // n <= positions[:, None] // n


def _shifted(x, fault):
    """Row i as it is, or under ``autoregressive_shift`` row i - 1 (the
    first its own): what a program reads that takes the logits of a
    position for the NEXT one's."""
    if fault != "autoregressive_shift":
        return x
    return jnp.concatenate([x[:, :1], x[:, :-1]], axis=1)


def forward(params, tokens, block_length: int, *, last: Optional[int] = None,
            with_routing: bool = False, fault: Optional[str] = None, **kw):
    """tokens [B, S] int32 -> logits [B, S, V] float32 under the
    block-causal mask (``last``: of the last so many positions alone);
    with ``with_routing`` also the routing of every position."""
    positions = jnp.arange(tokens.shape[1])
    x, routing = hidden(params, tokens, positions,
                        block_causal_mask(positions, block_length, fault),
                        fault=fault, **kw)
    x = _shifted(x, fault)
    logits = logits_of(params, x if last is None else x[:, -last:], fault)
    return (logits, routing) if with_routing else logits


def denoise_logits(params, prefix, block, **kw):
    """One pass over ``block`` [B, n] (mask ids where nothing stands
    yet) behind ``prefix`` [B, P], P a multiple of n: logits [B, n, V]
    of the block's own positions."""
    n = block.shape[1]
    return forward(params, jnp.concatenate([prefix, block], axis=1), n,
                   last=n, **kw)


def teacher_forced(params, clean, noised: Sequence, start: int,
                   block_length: int, *, fault: Optional[str] = None,
                   with_routing: bool = False, **kw):
    """clean [B, S]; ``noised``: arrays [B, S - start], each the rows
    ``clean[:, start:]`` in some masked state; ``start`` a multiple of
    the block. -> normed hidden rows ``[commit, state_0, state_1, ...]``,
    each [B, S - start, d] (``logits_of`` makes logits of them):
    ``commit`` the clean rows' own (what a pass over the clean block
    reads), ``state_v`` those of every block in state v behind the clean
    blocks before it. Under ``skip_commit`` the clean blocks from
    ``start`` on are NOT what later blocks see: they see the LAST
    state's rows instead (a cache that kept rows computed from masked
    inputs)."""
    n, S = block_length, clean.shape[1]
    tail = S - start
    tokens = jnp.concatenate([clean, *noised], axis=1)
    copies = len(noised)
    positions = jnp.concatenate(
        [jnp.arange(S)] + [jnp.arange(start, S)] * copies)
    copy = jnp.concatenate(
        [jnp.zeros(S, jnp.int32)]
        + [jnp.full(tail, v + 1, jnp.int32) for v in range(copies)])
    blk_q, blk_k = positions[:, None] // n, positions[None, :] // n
    same_copy = copy[:, None] == copy[None, :]
    # the rows that stand for the past: the clean ones, or under the
    # fault the last state's from ``start`` on
    if fault == "skip_commit" and copies:
        past = jnp.where(positions < start, copy == 0, copy == copies)
    else:
        past = copy == 0
    inside = same_copy & (blk_k == blk_q)
    if fault == "causal_in_block":
        inside &= positions[None, :] <= positions[:, None]
    mask = inside | (past[None, :] & (blk_k < blk_q))
    x, routing = hidden(params, tokens, positions, mask, fault=fault, **kw)
    parts = [x[:, start:S]] + [x[:, S + v * tail:S + (v + 1) * tail]
                               for v in range(copies)]
    if fault == "autoregressive_shift":
        before = x[:, start - 1:start]
        parts = [jnp.concatenate([before, p[:, :-1]], axis=1) for p in parts]
    return (parts, routing) if with_routing else parts


def transfer_quotas(block_length: int, steps: int) -> Tuple[int, ...]:
    base, more = divmod(block_length, steps)
    return tuple(base + (k < more) for k in range(steps))


def unmask(block: np.ndarray, x0: np.ndarray, confidence: np.ndarray,
           quota: int, threshold: float, dynamic: bool, mask_id: int):
    """One pass's verdict on ONE block (numpy, [n]): the positions whose
    proposals stay."""
    masked = block == mask_id
    conf = np.where(masked, confidence, -np.inf)
    quota = min(quota, int(masked.sum()))
    order = np.argsort(-conf, kind="stable")[:quota]
    placed = np.zeros(len(block), bool)
    placed[order] = True
    if dynamic:
        sure = masked & (conf > threshold)
        if sure.sum() >= quota:
            placed = sure
    return placed


def generate(params, prompt: List[int], n_tokens: int, *, block_length: int,
             denoising_steps: int, mask_id: int,
             remasking: str = "low_confidence_dynamic",
             confidence_threshold: float = 0.9,
             stop_token_ids: Sequence[int] = (), **kw):
    """Greedy generation by diffusion over blocks: ``(tokens, passes)``,
    up to ``n_tokens`` generated tokens (cut at the first of
    ``stop_token_ids``) and for each the denoising pass of its block
    that placed it (1 the first)."""
    if remasking not in ("low_confidence_dynamic", "low_confidence_static"):
        raise ValueError(f"remasking {remasking!r} is not implemented")
    n = block_length
    quotas = transfer_quotas(n, denoising_steps)
    run = jax.jit(lambda p, pre, blk: denoise_logits(p, pre, blk, **kw))
    context, out, placed_at = list(prompt), [], []
    while len(out) < n_tokens:
        cached = len(context) - len(context) % n
        given = context[cached:]
        block = np.full(n, mask_id, np.int64)
        block[:len(given)] = given
        at = np.zeros(n, np.int64)
        passes = 0
        while (block == mask_id).any():
            logits = np.asarray(run(
                params, jnp.asarray([context[:cached]], jnp.int32),
                jnp.asarray([block], jnp.int32)))[0].astype(np.float64)
            x0 = logits.argmax(-1)
            top = logits.max(-1, keepdims=True)
            confidence = 1.0 / np.exp(logits - top).sum(-1)
            keep = unmask(block, x0, confidence,
                          quotas[min(passes, len(quotas) - 1)],
                          confidence_threshold,
                          remasking == "low_confidence_dynamic", mask_id)
            block[keep] = x0[keep]
            passes += 1
            at[keep] = passes
        for tok, k in zip(block[len(given):].tolist(),
                          at[len(given):].tolist()):
            out.append(tok)
            placed_at.append(k)
            if tok in stop_token_ids or len(out) >= n_tokens:
                return out, placed_at
        context = context[:cached] + block.tolist()
    return out, placed_at
