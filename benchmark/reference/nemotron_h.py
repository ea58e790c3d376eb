"""The plain reference for the hybrid state-space configuration
(published ``nemotron_h``): float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``, independent of
``ray_tpu/models`` and ``ray_tpu/ops``.

Every layer is ``x <- x + mixer(RMSNorm(x; eps))`` with ONE mixer, by its
letter in ``pattern``:

- ``M`` (Mamba-2): ``[z | xBC | dt] = h in_proj``; ``xBC <-
  silu(conv(xBC))``, the depthwise causal convolution written as
  ``conv_kernel`` SHIFTED PRODUCTS over a zero-padded sequence; ``dt =
  softplus(dt + dt_bias)``; the recurrence as a plain ``lax.scan`` OVER
  POSITIONS (``S_t = exp(dt_t a) S_{t-1} + dt_t x_t (x) B_t``, ``y_t =
  S_t C_t + D x_t``, a head's ``S`` [P, N] in float32, head ``j`` using
  group ``j // (H / G)``), NOT the chunked form the program runs, so the
  chunked form is checked against another formulation; ``y <-
  RMSNorm_groups(y silu(z)) * weight``; ``out = y out_proj``.
- ``*``: GQA, causal, scale ``head_dim^-0.5``, no positional encoding;
  computed a block of queries at a time.
- ``E``: ``s = sigmoid(h gate)``; the top-k of ``s + bias``; weights
  ``s`` of the chosen over their sum (+ 1e-20) times the scaling factor;
  EVERY HELD EXPERT evaluated densely on ``u = h fc1_latent`` (``relu(u
  W1)^2 W2``, no gate matrix) and weighted (an expert not chosen weighs
  0), one expert at a time; the sum through ``fc2_latent``; plus the
  shared expert ``relu(h V1)^2 V2`` on ``h``.

DEPARTURES from the published description, each the configuration's
(``benchmark/configs/nemotron-3-super-d11.json``, ``reduced`` /
``assumed``): the share (``experts_held``: the weights are the held
experts' alone, the router keeps its width, what an absent expert would
have added is left out); the vocabulary slice; no multi-token-prediction
layers; the latent projections bare (no norm, no bias); no rotary turn.

``forward_rows`` hands the head back UNAPPLIED (``RowsOfLogits``), takes
attention a block of queries and the experts one at a time, so that it
fits beside a live engine on the chip. ``FAULTS`` are deliberate
departures for the controls.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

FAULTS = ("no_gate", "no_D", "no_conv_bias", "bf16_state", "no_scaling",
          "int8_weights")
QUERY_BLOCK = 512


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


def recurrence(x, dt, a, Bm, Cm, state=None, round_state: bool = False):
    """The recurrence a position at a time. x [B, S, H, P]; dt [B, S, H];
    a [H]; Bm, Cm [B, S, G, N]; ``state`` [B, H, P, N] before position 0
    (None: zeros). -> (y [B, S, H, P] without ``D x``, the final state)."""
    B, S, H, P = x.shape
    G, N = Bm.shape[2:]
    if state is None:
        state = jnp.zeros((B, H, P, N), jnp.float32)

    def step(s, inp):
        xt, dtt, bt, ct = inp
        bh = jnp.repeat(bt, H // G, axis=1)               # [B, H, N]
        ch = jnp.repeat(ct, H // G, axis=1)
        s = (jnp.exp(dtt * a)[..., None, None] * s
             + (dtt[..., None] * xt)[..., None] * bh[:, :, None, :])
        if round_state:
            # (not a pair of casts: the TPU compiler may keep the excess
            # precision of one)
            s = jax.lax.reduce_precision(s, exponent_bits=8,
                                         mantissa_bits=7)
        return s, jnp.sum(s * ch[:, :, None, :], axis=-1)

    state, ys = jax.lax.scan(
        step, state, tuple(jnp.moveaxis(v, 1, 0) for v in (x, dt, Bm, Cm)))
    return jnp.moveaxis(ys, 0, 1), state


def causal_conv(x, weight, bias):
    """x [B, S, C]; weight [C, K]; bias [C]: K shifted products over the
    sequence padded with K - 1 zeros in front."""
    K = weight.shape[1]
    S = x.shape[1]
    padded = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return bias + sum(padded[:, j:j + S] * weight[:, j] for j in range(K))


def _mamba(h, lp, *, heads: int, head_dim: int, groups: int, state: int,
           eps: float, fault, keep=None):
    B, S, _ = h.shape
    inner, gn = heads * head_dim, groups * state
    zxd = h @ _w(lp["in_proj"], fault)
    z, xbc, dt = (zxd[..., :inner], zxd[..., inner:2 * inner + 2 * gn],
                  zxd[..., 2 * inner + 2 * gn:])
    bias = _f32(lp["conv1d_bias"])
    if fault == "no_conv_bias":
        bias = jnp.zeros_like(bias)
    conv_in = xbc
    xbc = jax.nn.silu(causal_conv(xbc, _f32(lp["conv1d_weight"]), bias))
    x = xbc[..., :inner].reshape(B, S, heads, head_dim)
    Bm = xbc[..., inner:inner + gn].reshape(B, S, groups, state)
    Cm = xbc[..., inner + gn:].reshape(B, S, groups, state)
    dt = jax.nn.softplus(dt + _f32(lp["dt_bias"]))
    y, final = recurrence(x, dt, -jnp.exp(_f32(lp["A_log"])), Bm, Cm,
                          round_state=fault == "bf16_state")
    if fault != "no_D":
        y = y + _f32(lp["D"])[:, None] * x
    y = y.reshape(B, S, inner)
    if fault != "no_gate":
        y = y * jax.nn.silu(z)
    y = _rms_norm(y.reshape(B, S, groups, inner // groups), 1.0, eps)
    y = y.reshape(B, S, inner) * _f32(lp["mixer_norm"])
    if keep is not None:
        keep.append({"state": final, "conv_in": conv_in})
    return y @ _w(lp["out_proj"], fault)


def _attention(h, lp, *, n_heads: int, n_kv_heads: int, head_dim: int,
               keep=None, fault=None):
    B, S, _ = h.shape
    q = (h @ _w(lp["q_proj"], fault)).reshape(B, S, n_heads, head_dim)
    k = (h @ _w(lp["k_proj"], fault)).reshape(B, S, n_kv_heads, head_dim)
    v = (h @ _w(lp["v_proj"], fault)).reshape(B, S, n_kv_heads, head_dim)
    if keep is not None:
        keep.append({"k": k, "v": v})
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
    return o @ _w(lp["o_proj"], fault)


def sigmoid_topk(scores, bias, top_k: int):
    """The experts a token chooses: the top-k of ``scores + bias``."""
    return jax.lax.top_k(scores + bias, top_k)[1]


def _experts(h, lp, *, top_k: int, scaling: float, norm_topk_prob: bool,
             held, forced=None, fault=None):
    """-> (out, chosen [B, S, K])."""
    scores = jax.nn.sigmoid(h @ _f32(lp["gate"]))            # [B, S, E]
    chosen = (sigmoid_topk(scores, _f32(lp["e_score_correction_bias"]),
                           top_k) if forced is None else forced)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    if norm_topk_prob:
        picked = picked / (jnp.sum(picked, -1, keepdims=True) + 1e-20)
    if fault != "no_scaling":
        picked = picked * scaling
    # [B, S, E]: an expert's weight for a token, 0 where not chosen
    E = scores.shape[-1]
    weight = jnp.sum(jax.nn.one_hot(chosen, E, dtype=jnp.float32)
                     * picked[..., None], axis=-2)
    first, count = held
    u = h @ _w(lp["fc1_latent_proj"], fault)

    def one(acc, e):
        w1, w2 = _w(lp["up_proj"][e], fault), _w(lp["down_proj"][e], fault)
        out = jnp.square(jax.nn.relu(u @ w1)) @ w2
        w = jax.lax.dynamic_index_in_dim(weight, first + e, axis=-1)
        return acc + w * out, None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(u), jnp.arange(count))
    shared = jnp.square(jax.nn.relu(h @ _w(lp["shared_up"], fault))) @ _w(
        lp["shared_down"], fault)
    return routed @ _w(lp["fc2_latent_proj"], fault) + shared, chosen


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


def forward_rows(params, tokens, *, pattern: str, mamba_heads: int,
                 mamba_head_dim: int, n_groups: int, ssm_state: int,
                 num_heads: int, num_kv_heads: int, head_dim: int,
                 top_k: int, routed_scaling_factor: float,
                 norm_topk_prob: bool, eps: float, experts_held=None,
                 forced_experts=None, with_kept: bool = False,
                 fault: Optional[str] = None):
    """tokens [B, S] int32 -> ``RowsOfLogits`` over [B, S]; with
    ``with_kept`` also ``{"experts": [Le, B, S, K], "mamba": [{"state":
    [B, H, P, N] after the last position, "conv_in": [B, S, C] the
    convolution's inputs}], "attn": [{"k", "v"}]}``, a list a kind in
    layer order. ``experts_held`` (first, count): the share of the
    router's experts whose weights ``params`` hold (None: all);
    ``forced_experts`` [Le, B, S, K]: the experts to take instead of the
    reference's own choice (the routing floor apart from the arithmetic).
    """
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"no fault {fault!r}; known: {FAULTS}")
    kept = {"experts": [], "mamba": [], "attn": []}
    with jax.default_matmul_precision("highest"):
        x = _w(params["embed"][tokens], fault, axis=-1)
        stacks = {"M": params.get("mamba"), "*": params.get("attn"),
                  "E": params.get("moe")}
        seen = {letter: 0 for letter in stacks}
        for letter in pattern:
            i = seen[letter]
            seen[letter] += 1
            x, stacks = jax.lax.optimization_barrier((x, stacks))
            lp = {name: a[i] for name, a in stacks[letter].items()}
            h = _rms_norm(x, _f32(lp["norm"]), eps)
            if letter == "M":
                out = _mamba(h, lp, heads=mamba_heads,
                             head_dim=mamba_head_dim, groups=n_groups,
                             state=ssm_state, eps=eps, fault=fault,
                             keep=kept["mamba"] if with_kept else None)
            elif letter == "*":
                out = _attention(h, lp, n_heads=num_heads,
                                 n_kv_heads=num_kv_heads, head_dim=head_dim,
                                 keep=kept["attn"] if with_kept else None,
                                 fault=fault)
            else:
                held = experts_held or (0, lp["gate"].shape[-1])
                out, chosen = _experts(
                    h, lp, top_k=top_k, scaling=routed_scaling_factor,
                    norm_topk_prob=norm_topk_prob, held=held,
                    forced=(None if forced_experts is None
                            else forced_experts[i]), fault=fault)
                kept["experts"].append(chosen)
            x = x + out
        x = _rms_norm(x, _f32(params["norm_f"]), eps)
    rows = RowsOfLogits(x, params["lm_head"], fault)
    if with_kept:
        if kept["experts"]:
            kept["experts"] = jnp.stack(kept["experts"])
        return rows, kept
    return rows


def forward(params, tokens, **kw):
    """tokens [B, S] int32 -> float32 logits [B, S, V]."""
    return forward_rows(params, tokens, **kw)[:]
