"""State-space (Mamba-1) sequence mixing: the selective scan whose decay
is a MATRIX a channel. Beside ``ops/ssm.py`` (Mamba-2: one decay number a
head, so a chunk is products on the MXU), and not a case of it.

A channel ``d`` of ``W`` carries ``N`` numbers, ``S[n, d]``:

    S_t[n, d] = exp(dt_t[d] A[n, d]) S_{t-1}[n, d] + dt_t[d] B_t[n] u_t[d]
    y_t[d]    = sum_n S_t[n, d] C_t[n]

(``D u_t`` and the gate are the model's). ``A`` [N, W] < 0 is the layer's,
``dt_t`` [W] a position's (after softplus), ``B_t``, ``C_t`` [N] a
position's: the decay ``exp(dt_t[d] A[n, d])`` is another number for every
(state index, channel) pair at every position, so no product of a chunk's
rows has a matmul form: the work is ``N W`` exponentials a position, on the
EUP, not on the MXU. There are no heads and no groups.

THE STATE'S LAYOUT is ``[N, W]``: the state index in the sublanes, the
channels in the lanes (``ops/ssm.py``'s, with one group and no heads):
``dt`` and ``dt u`` are rows broadcast down the sublanes, ``B`` and ``C``
columns broadcast along the lanes, ``y`` a sum over the sublanes.

Two functions, each one algorithm with two implementations chosen by the
caller's ``impl`` ("pallas": a Mosaic kernel, interpreted where the
backend is the CPU; "xla": its twin), both the same function of the same
inputs as the recurrence a position at a time
(``benchmark/reference/jamba.py``):

- ``state_update`` (a decode batch): read a slot's ``S``, make the decay IN
  THE KERNEL from the slot's ``dt`` row and the layer's ``A`` (handed in as
  a ``[B, N, W]`` array from HBM the decay would add half again to the 8 B
  a number the update moves), update, contract with ``C``, write back where
  it lies: the stack of every layer's rows is the kernel's input AND its
  output, and the layer (a traced index: the model scans its layers) picks
  the rows.
- ``selective_scan`` (prefill): ``T`` positions from a state that comes IN
  (a chunked prefill continues where the chunk before stopped) and goes
  OUT, a length a row: ``dt`` is 0 behind it, which is decay 1 and no
  input, so ``S`` stands still over padding. The kernel keeps a slab of
  channels' ``S`` in registers (a register a state index) and walks the
  positions; its grid is (row, slab) parallel over chunks of positions in
  order. Its twin is a ``lax.scan`` over positions. Neither materialises
  ``[B, T, N, W]``.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

LANES = 128
SUBLANES = 8
# slots the decode kernel updates a grid step, positions the scan kernel
# walks a grid step: 1-8 slots and 128-512 positions read the same on the
# v5e (PERF.md, PR 57)
UPDATE_ROWS = 4
SCAN_CHUNK = 256


def _interpret() -> bool:
    # (one place knows what the platform is, so the chip-less tools that
    # describe a chip to it describe it to this too)
    from ray_tpu.ops import paged_attention
    return paged_attention.pallas_interpret()


# -- the decode update ---------------------------------------------------------
def state_update_reference(stack, layer, a, dt, dtu, Bm, Cm):
    """``state_update`` in XLA. stack [L, R, N, W] float32; ``layer`` (it
    may be traced) the layer whose rows the batch's are, R = B; a [N, W];
    dt, dtu [B, W] (``dt`` and ``dt u``); Bm, Cm [B, N].
    -> (stack, y [B, W] float32)."""
    f32 = jnp.float32
    S = jax.lax.dynamic_index_in_dim(stack, layer, 0, keepdims=False)
    decay = jnp.exp(dt.astype(f32)[:, None, :] * a.astype(f32)[None])
    new = (S.astype(f32) * decay + Bm.astype(f32)[:, :, None]
           * dtu.astype(f32)[:, None, :]).astype(stack.dtype)
    y = jnp.sum(new.astype(f32) * Cm.astype(f32)[:, :, None], axis=1)
    return jax.lax.dynamic_update_index_in_dim(stack, new, layer, 0), y


def _state_update_kernel(layer_ref, s_ref, a_ref, bc_ref, rows_ref, o_ref,
                         y_ref):
    del layer_ref                                # the index maps read it
    dt = rows_ref[:, 0:1, :]                     # [Bt, 1, W]
    dtu = rows_ref[:, 1:2, :]
    bc = bc_ref[...]                             # [Bt, N, 2]: B's, C's column
    new = (s_ref[...] * jnp.exp(dt * a_ref[...][None])
           + bc[:, :, 0:1] * dtu)                # [Bt, N, W]
    o_ref[...] = new
    y_ref[...] = jnp.sum(new * bc[:, :, 1:2], axis=1, keepdims=True)


def state_update_pallas(stack, layer, a, dt, dtu, Bm, Cm, *,
                        interpret: bool = False):
    """``state_update`` as a Mosaic kernel: a grid over the batch,
    ``UPDATE_ROWS`` slots' ``[N, W]`` a step, read, updated and written
    where they lie: the stack (all layers' rows, ``[L*R, N, W]``) is the
    kernel's input AND its output (aliased), ``layer`` a prefetched scalar
    that the index maps add, so nothing stack-sized is copied and the
    rows of other layers are never touched. ``A`` [N, W] is ONE block for
    every step (read once a call); the decay is made here."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    L, R, N, W = stack.shape
    B = dt.shape[0]
    if stack.dtype != jnp.float32:
        raise ValueError("the state-update kernel holds S in float32, got "
                         f"{stack.dtype}")
    if R != B:
        raise ValueError(f"a batch of {B} rows for a state of {R} a layer")
    f32 = jnp.float32
    bt = math.gcd(B, UPDATE_ROWS)
    steps = B // bt
    bc = jnp.stack([Bm.astype(f32), Cm.astype(f32)], axis=-1)    # [B, N, 2]
    rows = jnp.stack([dt.astype(f32), dtu.astype(f32)], axis=1)  # [B, 2, W]
    state = pl.BlockSpec((bt, N, W),
                         lambda b, layer: (layer[0] * steps + b, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(steps,),
        in_specs=[state,
                  pl.BlockSpec((N, W), lambda b, layer: (0, 0)),
                  pl.BlockSpec((bt, N, 2), lambda b, layer: (b, 0, 0)),
                  pl.BlockSpec((bt, 2, W), lambda b, layer: (b, 0, 0))],
        out_specs=[state,
                   pl.BlockSpec((bt, 1, W), lambda b, layer: (b, 0, 0))])
    new, y = pl.pallas_call(
        _state_update_kernel, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((L * R, N, W), f32),
                   jax.ShapeDtypeStruct((B, 1, W), f32)],
        # (the scalar is operand 0)
        input_output_aliases={1: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="ssm1_state_update_pallas",
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), stack.reshape(L * R, N, W),
      a.astype(f32), bc, rows)
    return new.reshape(stack.shape), y[:, 0]


def state_update(stack, layer, a, dt, dtu, Bm, Cm, *, impl: str):
    """One decode step of a batch's recurrent state (module docstring):
    ``impl`` "pallas" (the kernel; it refuses a state that is not
    float32) or "xla" (its twin)."""
    if impl == "pallas":
        return state_update_pallas(stack, layer, a, dt, dtu, Bm, Cm,
                                   interpret=_interpret())
    return state_update_reference(stack, layer, a, dt, dtu, Bm, Cm)


# -- the prefill scan ------------------------------------------------------------
def selective_scan_reference(u, dt, a, Bm, Cm, state):
    """``selective_scan`` in XLA: a ``lax.scan`` over positions whose carry
    is ``S`` [B, N, W]. Shapes as ``selective_scan``'s, ``dt`` already 0
    behind each row's length."""
    f32 = jnp.float32
    a = a.astype(f32)

    def step(S, inp):
        ut, dtt, bt, ct = inp                       # [B, W] x2, [B, N] x2
        S = (S * jnp.exp(dtt[:, None, :] * a[None])
             + bt[:, :, None] * (dtt * ut)[:, None, :])
        return S, jnp.sum(S * ct[:, :, None], axis=1)

    S, ys = jax.lax.scan(
        step, state.astype(f32),
        tuple(jnp.moveaxis(v.astype(f32), 1, 0) for v in (u, dt, Bm, Cm)))
    return jnp.moveaxis(ys, 0, 1), S


def _scan_kernel(b_ref, c_ref, dt_ref, dtu_ref, a_ref, s0_ref, y_ref,
                 s_out_ref, s_scr, *, chunk: int):
    import jax.experimental.pallas as pl

    N = a_ref.shape[0]

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_scr[...] = s0_ref[0]

    def position(t, S):
        dt, dtu = dt_ref[0, t], dtu_ref[0, t]           # [sub, lanes] slabs
        y, new = None, []
        for n in range(N):                              # a slab a state index
            s = (S[n] * jnp.exp(dt * a_ref[n]) + b_ref[0, t, n] * dtu)
            new.append(s)
            y = s * c_ref[0, t, n] if y is None else y + s * c_ref[0, t, n]
        y_ref[0, t] = y
        return tuple(new)

    S = jax.lax.fori_loop(0, chunk, position,
                          tuple(s_scr[n] for n in range(N)))
    for n in range(N):
        s_scr[n] = S[n]
        s_out_ref[0, n] = S[n]


def selective_scan_pallas(u, dt, a, Bm, Cm, state, *,
                          interpret: bool = False):
    """``selective_scan`` as a Mosaic kernel. The channels are laid out
    as SLABS: ``[.., W]`` viewed as ``[.., 8, W/8]`` (a free reshape:
    everything here is elementwise in the channel), and a grid step owns
    a slab of ``8 x 128`` channels, one vector register a state index: a
    position is, for each of the ``N`` state indices, an exponential and
    six multiply-adds on full registers, with ``B_t[n]`` and ``C_t[n]``
    SCALARS read from SMEM, and ``y`` a sum over the state index that is
    plain adds: nothing crosses lanes or sublanes. Grid (row, slab, chunk
    of positions); the slab's ``S`` (``N`` registers) is the position
    loop's carry and waits in VMEM from one chunk to the next. ``dt`` and
    ``dt u`` come in float32, ``y`` goes out in float32; T is padded to
    whole chunks with ``dt`` 0 (``S`` stands still)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    B, T, W = u.shape
    N = a.shape[0]
    dt = dt.astype(f32)
    dtu = dt * u.astype(f32)
    chunk = min(SCAN_CHUNK, -(-T // SUBLANES) * SUBLANES)
    pad = -T % chunk
    if pad:
        dt, dtu, Bm, Cm = (jnp.pad(v, [(0, 0), (0, pad), (0, 0)])
                           for v in (dt, dtu, Bm, Cm))
    Tp = T + pad
    sub = SUBLANES if W % SUBLANES == 0 else 1
    row = W // sub
    lanes = LANES if row % LANES == 0 else row

    def slabs(v):           # [..., W] -> [..., sub, W / sub]
        return v.reshape(v.shape[:-1] + (sub, row))

    seq = pl.BlockSpec((1, chunk, sub, lanes), lambda b, c, t: (b, t, 0, c))
    scalars = pl.BlockSpec((1, chunk, N), lambda b, c, t: (b, t, 0),
                           memory_space=pltpu.SMEM)
    tile = pl.BlockSpec((1, N, sub, lanes), lambda b, c, t: (b, 0, 0, c))
    y, S = pl.pallas_call(
        functools.partial(_scan_kernel, chunk=chunk),
        grid=(B, row // lanes, Tp // chunk),
        in_specs=[scalars, scalars, seq, seq,
                  pl.BlockSpec((N, sub, lanes), lambda b, c, t: (0, 0, c)),
                  tile],
        out_specs=[seq, tile],
        out_shape=[jax.ShapeDtypeStruct((B, Tp, sub, row), f32),
                   jax.ShapeDtypeStruct((B, N, sub, row), f32)],
        scratch_shapes=[pltpu.VMEM((N, sub, lanes), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="ssm1_scan_pallas",
        interpret=interpret,
    )(Bm.astype(f32), Cm.astype(f32), slabs(dt), slabs(dtu),
      slabs(a.astype(f32)), slabs(state.astype(f32)))
    return y.reshape(B, Tp, W)[:, :T], S.reshape(B, N, W)


def selective_scan(u: jax.Array, dt: jax.Array, a: jax.Array, Bm: jax.Array,
                   Cm: jax.Array, state: jax.Array,
                   lengths: Optional[jax.Array] = None, *, impl: str
                   ) -> Tuple[jax.Array, jax.Array]:
    """The recurrence over T positions (module docstring).

    u [B, T, W] (after the convolution); dt [B, T, W] float32 (after
    softplus); a [N, W] float32 (negative); Bm, Cm [B, T, N]; ``state``
    [B, N, W] float32, the state before position 0; ``lengths`` [B]
    (None: T). -> (y [B, T, W] float32 without ``D u``; rows behind a
    length hold whatever the standing state gives; the state after
    ``lengths`` positions [B, N, W] float32)."""
    dt = dt.astype(jnp.float32)
    if lengths is not None:
        T = u.shape[1]
        dt = jnp.where(jnp.arange(T)[None, :, None] < lengths[:, None, None],
                       dt, 0.0)
    if impl == "pallas":
        return selective_scan_pallas(u, dt, a, Bm, Cm, state,
                                     interpret=_interpret())
    return selective_scan_reference(u, dt, a, Bm, Cm, state)
