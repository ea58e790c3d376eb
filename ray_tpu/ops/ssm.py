"""State-space (Mamba-2) sequence mixing: what a layer carries from one
call to the next is of FIXED size, whatever the context's length.

A head's state ``S`` [P, N] (P the head's width, N the state size)
follows ``S_t = exp(dt_t a) S_{t-1} + dt_t x_t (x) B_t`` and gives
``y_t = S_t C_t`` (``D x_t`` and the gate are the model's). ``a`` < 0 is
one number a head; ``B_t``, ``C_t`` [N] belong to a GROUP of heads. Three
functions, each the same function of the same inputs as the recurrence
a position at a time (``benchmark/reference/nemotron_h.py``):

- ``causal_conv``: the depthwise causal convolution before the
  recurrence, with the window of the last ``K - 1`` inputs carried in
  and out, and a length a row: padding behind it neither enters the
  window nor shifts it.
- ``chunked_scan`` (prefill, training): the SSD form. Within a chunk of
  ``chunk`` positions everything is products on the MXU (``C B^T`` under
  the decay's mask, times ``x``); ``S`` is carried from chunk to chunk
  by a scan, comes IN (a chunked prefill continues where the chunk
  before stopped) and goes OUT. A length a row: ``dt`` is 0 behind it,
  which is decay 1 and no input, so ``S`` stands still over padding.
- ``state_step`` (a decode batch): read ``S``, decay, rank-one update,
  contract with ``C``, write back. 2 x 4 MiB moved a slot a layer at
  128 heads x 64 x 128 in float32 and ~6 FLOPs a number: bound by bytes.
  One algorithm, two implementations, chosen by the caller's ``impl``
  (the model's, from the platform: ``paged_decode_impl``): "pallas", a
  Mosaic kernel that ALIASES the whole stack of state rows and rewrites
  the batch's rows in place, and "xla", its twin.

THE STATE'S LAYOUT (``state_from_heads`` / ``state_to_heads``): a row is
``[G, N, hg * P]``, a group's ``hg`` heads side by side in the lanes,
the state size in the sublanes. A decode step then needs ``B`` and ``C``
as columns (small, transposed outside the kernel) and everything else as
rows: the decay and ``dt x`` broadcast down the sublanes, ``y`` is a sum
over them, and no lane holds padding (``[H, P, N]`` with P = 64 would
want ``x`` and ``y`` as columns a head).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def state_from_heads(s: jax.Array, groups: int) -> jax.Array:
    """``[..., H, P, N]`` -> ``[..., G, N, hg * P]``."""
    *lead, H, P, N = s.shape
    s = s.reshape(*lead, groups, H // groups, P, N)
    return jnp.moveaxis(s, -1, -3).reshape(*lead, groups, N,
                                           (H // groups) * P)


def state_to_heads(s: jax.Array, head_dim: int) -> jax.Array:
    """``[..., G, N, hg * P]`` -> ``[..., H, P, N]``."""
    *lead, G, N, W = s.shape
    s = s.reshape(*lead, G, N, W // head_dim, head_dim)
    return jnp.moveaxis(s, -3, -1).reshape(*lead, G * (W // head_dim),
                                           head_dim, N)


def causal_conv(x: jax.Array, window: jax.Array, weight: jax.Array,
                bias: jax.Array, lengths: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, jax.Array]:
    """Depthwise causal convolution over the last ``K`` positions, then
    SiLU. x [B, T, C]; ``window`` [B, K-1, C] the inputs just before
    ``x``; weight [C, K] (column K-1 multiplies the current position);
    bias [C]; ``lengths`` [B] the rows' valid positions (None: T).
    -> (out [B, T, C] in x's dtype, the window after ``lengths``
    positions [B, K-1, C] in the window's dtype). Sums in float32."""
    B, T, C = x.shape
    K = weight.shape[1]
    full = jnp.concatenate([window.astype(x.dtype), x], axis=1)
    w = weight.astype(jnp.float32)
    acc = bias.astype(jnp.float32)
    for j in range(K):
        acc = acc + full[:, j:j + T].astype(jnp.float32) * w[:, j]
    if lengths is None:
        new = full[:, T:]
    else:
        new = jax.vmap(lambda f, n: jax.lax.dynamic_slice_in_dim(
            f, n, K - 1, axis=0))(full, lengths.astype(jnp.int32))
    return jax.nn.silu(acc).astype(x.dtype), new.astype(window.dtype)


def chunked_scan(x: jax.Array, dt: jax.Array, a: jax.Array, Bm: jax.Array,
                 Cm: jax.Array, state: jax.Array, *, chunk: int,
                 lengths: Optional[jax.Array] = None, dtype=jnp.bfloat16
                 ) -> Tuple[jax.Array, jax.Array]:
    """The recurrence over T positions in chunks (module docstring).

    x [B, T, H, P]; dt [B, T, H] float32 (after softplus); a [H] float32
    (negative); Bm, Cm [B, T, G, N]; ``state`` [B, G, N, hg*P] float32,
    the state before position 0; ``lengths`` [B] (None: T).
    -> (y [B, T, H, P] float32 without ``D x``, the state after
    ``lengths`` positions). The products' operands are in ``dtype``
    (float32 sums); decays and ``S`` stay float32."""
    B, T, H, P = x.shape
    G, N = Bm.shape[2:]
    hg = H // G
    f32 = jnp.float32
    dt = dt.astype(f32)
    if lengths is not None:
        dt = jnp.where(jnp.arange(T)[None, :, None] < lengths[:, None, None],
                       dt, 0.0)
    pad = -T % chunk
    if pad:
        x, dt, Bm, Cm = (jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2))
                         for v in (x, dt, Bm, Cm))
    nc = (T + pad) // chunk

    def chunks(v, *tail):        # [B, nc*Q, ...] -> [nc, B, Q, *tail]
        return jnp.moveaxis(v.reshape(B, nc, chunk, *tail), 1, 0)

    xs = (chunks(x.astype(dtype), G, hg, P), chunks(dt, G, hg),
          chunks(Bm.astype(dtype), G, N), chunks(Cm.astype(dtype), G, N))
    a = a.astype(f32).reshape(G, hg)
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    einsum = functools.partial(jnp.einsum, preferred_element_type=f32)

    def step(S, c):
        xc, dtc, Bc, Cc = c                         # S [B, G, N, hg, P]
        cum = jnp.cumsum(dtc * a, axis=1)           # [B, Q, G, hg], <= 0
        # position q sees position s <= q through exp(cum_q - cum_s)
        diff = cum[:, :, None] - cum[:, None]       # [B, Q, S, G, hg]
        decay = jnp.exp(jnp.where(causal[None, :, :, None, None], diff,
                                  -jnp.inf))
        cb = einsum("bqgn,bsgn->bqsg", Cc, Bc)
        m = cb[..., None] * decay * dtc[:, None]    # [B, Q, S, G, hg]
        y = einsum("bqsgh,bsghp->bqghp", m.astype(dtype), xc)
        # what the state before the chunk adds
        y = y + jnp.exp(cum)[..., None] * einsum(
            "bqgn,bgnhp->bqghp", Cc, S.astype(dtype))
        # the state after the chunk
        w = jnp.exp(cum[:, -1:] - cum) * dtc        # [B, Q, G, hg]
        S = (jnp.exp(cum[:, -1])[:, :, None, :, None] * S
             + einsum("bqgn,bqghp->bgnhp", Bc,
                      (w[..., None] * xc).astype(dtype)))
        return S, y

    S0 = state.astype(f32).reshape(B, G, N, hg, P)
    S, ys = jax.lax.scan(step, S0, xs)
    y = jnp.moveaxis(ys, 0, 1).reshape(B, nc * chunk, H, P)[:, :T]
    return y, S.reshape(B, G, N, hg * P)


def state_step_reference(stack, first_row: int, decay, dtx, Bm, Cm):
    """``state_step`` in XLA. stack [R, G, N, W] float32; the batch's
    rows are ``first_row ... first_row + B``; decay, dtx [B, G, W]
    (``exp(dt a)`` and ``dt x`` a head, laid out as the state's lanes);
    Bm, Cm [B, G, N]. -> (stack, y [B, G, W] float32)."""
    B = decay.shape[0]
    S = jax.lax.dynamic_slice_in_dim(stack, first_row, B, axis=0)
    new = (S * decay[:, :, None, :].astype(S.dtype)
           + Bm[:, :, :, None].astype(S.dtype)
           * dtx[:, :, None, :].astype(S.dtype)).astype(stack.dtype)
    y = jnp.sum(new.astype(jnp.float32)
                * Cm[:, :, :, None].astype(jnp.float32), axis=2)
    return jax.lax.dynamic_update_slice_in_dim(stack, new, first_row,
                                               axis=0), y


def _state_step_kernel(s_ref, bc_ref, decay_ref, dtx_ref, o_ref, y_ref, *,
                       groups: int):
    import jax.experimental.pallas as pl

    g = pl.program_id(1)
    bc = bc_ref[0]                               # [N, 2G]: B's and C's columns
    lane = jax.lax.broadcasted_iota(jnp.int32, bc.shape, 1)
    b_col = jnp.sum(jnp.where(lane == g, bc, 0.0), axis=1, keepdims=True)
    c_col = jnp.sum(jnp.where(lane == groups + g, bc, 0.0), axis=1,
                    keepdims=True)               # [N, 1]
    decay = decay_ref[0, pl.ds(g, 1), :]         # [1, W]
    dtx = dtx_ref[0, pl.ds(g, 1), :]
    new = s_ref[0, 0] * decay + b_col * dtx      # [N, W]
    o_ref[0, 0] = new
    y_ref[0, pl.ds(g, 1), :] = jnp.sum(new * c_col, axis=0, keepdims=True)


def state_step_pallas(stack, first_row: int, decay, dtx, Bm, Cm, *,
                      interpret: bool = False):
    """``state_step`` as a Mosaic kernel: a grid of (batch row, group),
    one group's ``[N, W]`` of one row's state a step, read, updated and
    written where it lies: the stack is the kernel's input AND its
    output (aliased), so the rows of other layers and of no slot are
    never touched, and nothing stack-sized is copied. ``B`` and ``C``
    come as columns ``[B, N, 2G]`` (64 KB a row beside its 4 MiB)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, G, W = decay.shape
    N = Bm.shape[-1]
    if stack.dtype != jnp.float32:
        raise ValueError("the state-update kernel holds S in float32, got "
                         f"{stack.dtype}")
    f32 = jnp.float32
    bc = jnp.concatenate([jnp.swapaxes(Bm.astype(f32), 1, 2),
                          jnp.swapaxes(Cm.astype(f32), 1, 2)], axis=-1)
    row = pl.BlockSpec((1, G, W), lambda b, g: (b, 0, 0))
    state = pl.BlockSpec((1, 1, N, W), lambda b, g: (first_row + b, g, 0, 0))
    return pl.pallas_call(
        functools.partial(_state_step_kernel, groups=G),
        grid=(B, G),
        in_specs=[state, pl.BlockSpec((1, N, 2 * G), lambda b, g: (b, 0, 0)),
                  row, row],
        out_specs=[state, row],
        out_shape=[jax.ShapeDtypeStruct(stack.shape, stack.dtype),
                   jax.ShapeDtypeStruct((B, G, W), f32)],
        input_output_aliases={0: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="ssm_state_update_pallas",
        interpret=interpret,
    )(stack, bc, decay.astype(f32), dtx.astype(f32))


def state_step(stack, first_row: int, decay, dtx, Bm, Cm, *, impl: str):
    """One decode step of a batch's recurrent state (module docstring):
    ``impl`` "pallas" (the kernel, interpreted where the backend is the
    CPU; it refuses a state that is not float32) or "xla" (its twin)."""
    if impl == "pallas":
        # (one place knows what the platform is, so the chip-less tools
        # that describe a chip to it describe it to this too)
        from ray_tpu.ops import paged_attention
        return state_step_pallas(
            stack, first_row, decay, dtx, Bm, Cm,
            interpret=paged_attention.pallas_interpret())
    return state_step_reference(stack, first_row, decay, dtx, Bm, Cm)
