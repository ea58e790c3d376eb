"""Attention: reference JAX implementation + Pallas TPU flash kernels.

Reference capability: the reference repo delegates attention to vLLM /
flash-attn CUDA kernels (outside its tree). Here it is in-tree and
TPU-native:

- ``attention``      — dispatcher; GQA-aware, causal or not,
  differentiable. Takes ``flash_attention`` on a TPU where ``kernels_tile``
  says the shapes tile, the reference everywhere else.
- ``flash_attention``— a fused pair of Pallas kernels behind one
  ``custom_vjp``: the forward keeps ``o`` and a row of log-sum-exp, the
  backward rebuilds ``p`` from q, k and that row a tile at a time and
  gives dq, dk and dv from one pass. No S x S (nor S x block) array
  reaches HBM in either direction; tiles above the causal diagonal are
  never visited. The kernels keep a head's whole sequence in VMEM: a
  longer one (``_stays_resident``) takes ``blockwise_attention``.
- ``blockwise_attention`` — the XLA online-softmax scan, O(S x block)
  memory at any length: what a mesh runs (a Mosaic call cannot be
  partitioned), what Ulysses runs, and the long sequences' path.

Shapes follow the JAX convention [batch, seq, heads, head_dim].
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu._private.platform import on_chip, pallas_interpret

NEG_INF = -1e30


def _repeat_kv(k: jax.Array, num_q_heads: int) -> jax.Array:
    """GQA: repeat kv heads to match q heads. [B,S,Hkv,D] -> [B,S,H,D]."""
    num_kv = k.shape[-2]
    if num_kv == num_q_heads:
        return k
    return jnp.repeat(k, num_q_heads // num_kv, axis=-2)


def reference_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True,
                        positions_q: Optional[jax.Array] = None,
                        positions_k: Optional[jax.Array] = None,
                        scale: Optional[float] = None,
                        window=None) -> jax.Array:
    """Plain softmax attention in f32; XLA fuses this well on TPU for
    moderate sequence lengths and it is fully differentiable.

    A query sees a key iff its position is >= the key's. Positions are
    one vector for the batch ([T] / [S]) or PER ROW ([B, T] / [B, S]):
    the serving prefills' masked attention over a slot cache or a
    gathered prefix, where every row has its own offset (a key to be
    dropped carries a position past every query's). ``window`` (an int,
    it may be traced) is a sliding-window layer's: a query at ``i``
    sees a key at ``j`` only if ``i - j < window`` besides."""
    head_dim = q.shape[-1]
    scale = scale if scale is not None else head_dim ** -0.5
    k = _repeat_kv(k, q.shape[-2])
    v = _repeat_kv(v, q.shape[-2])
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        if positions_q is None:
            positions_q = jnp.arange(q.shape[1])
        if positions_k is None:
            positions_k = jnp.arange(k.shape[1])
        mask = positions_q[..., :, None] >= positions_k[..., None, :]
        if window is not None:
            mask &= (positions_q[..., :, None] - positions_k[..., None, :]
                     < window)
        # [T, S] or [B, T, S] -> broadcast over heads
        s = jnp.where(jnp.expand_dims(mask, -3), s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)


def blockwise_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True, block_k: int = 512,
                        scale: Optional[float] = None) -> jax.Array:
    """Memory-efficient differentiable attention: online-softmax scan over
    key chunks with a rematerialized body, so both forward AND backward are
    O(S·block_k) memory instead of O(S²). This is the training path for
    long sequences (and the flash kernel's VJP)."""
    head_dim = q.shape[-1]
    scale = scale if scale is not None else head_dim ** -0.5
    k = _repeat_kv(k, q.shape[-2])
    v = _repeat_kv(v, q.shape[-2])
    seq_k = k.shape[1]
    bk = min(block_k, seq_k)
    if seq_k % bk != 0:  # pad keys; padding masked out below
        pad = bk - seq_k % bk
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nk = k.shape[1] // bk
    rows = jnp.arange(q.shape[1])
    batch, seq_q, heads, _ = q.shape

    # [nk, B, bk, H, D] chunks scanned as the leading axis.
    kc = k.reshape(batch, nk, bk, heads, head_dim).swapaxes(0, 1)
    vc = v.reshape(batch, nk, bk, heads, head_dim).swapaxes(0, 1)

    @jax.checkpoint
    def body(carry, chunk):
        acc, m, l = carry
        ki, kb, vb = chunk
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kb,
                       preferred_element_type=jnp.float32) * scale
        cols = ki * bk + jnp.arange(bk)
        mask = cols[None, :] < seq_k
        if causal:
            mask = mask & (rows[:, None] >= cols[None, :])
        s = jnp.where(mask[None, None], s, NEG_INF)
        m_c = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m, m_c)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        a = jnp.einsum("bhqk,bkhd->bqhd", p, vb.astype(jnp.float32),
                       preferred_element_type=jnp.float32)
        acc = acc * jnp.swapaxes(alpha, 1, 2) + a
        return (acc, m_new, l), None

    acc = jnp.zeros((batch, seq_q, heads, head_dim), jnp.float32)
    m = jnp.full((batch, heads, seq_q, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((batch, heads, seq_q, 1), jnp.float32)
    (acc, m, l), _ = jax.lax.scan(
        body, (acc, m, l), (jnp.arange(nk), kc, vc))
    l = jnp.where(l == 0.0, 1.0, l)
    return (acc / jnp.swapaxes(l, 1, 2)).astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas flash attention: ONE fused pair, a forward and a backward kernel
# ---------------------------------------------------------------------------
# A grid step holds one head whole in VMEM (q, K, V; in the backward do
# and the two rows of statistics besides) and walks the score matrix in
# row blocks whose tiles are STATIC slices: a block's tiles stop at the
# causal diagonal, only the tile that crosses the mask's edge pays for a
# mask, and the rest of the row is taken in tiles up to ``_WIDE`` columns.
# The loops are unrolled as Python, so the kernel is straight-line code
# the scheduler can overlap (square tiles under ``fori_loop`` with traced
# bounds ran 1.45x slower on the v5e, PERF.md PR 48). Nothing of
# S x S or S x block size is written to HBM in either direction; what the
# forward keeps for the backward is ``o`` and a row of log-sum-exp.

_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_TN = (((0,), (0,)), ((), ()))      # a.T @ b
# block sizes by shape, measured at 16 x 1,024 x 16 x 64 and x 8 x 128
# on the v5e: query rows of a forward block, key rows of a backward block
# (never more than the sequence), and the widest tile of either
_BLOCK_Q, _BLOCK_K, _WIDE = 512, 256, 1024
_LANES = 128
# the kernels are straight-line code, and Mosaic gives every unrolled
# tile's temporaries VMEM of their own: past this many (query, key) pairs
# a head (4,096 causal positions, some 50 tiles in the backward and 15-40
# s of compiling; 2,896 where every key is seen) the forward no longer
# compiles inside ``_vmem_limit`` on the v5e
_MAX_UNROLLED = 4096 * 4096 // 2
# the live tiles of one step of a block's walk (scores, p, dp, ds in
# float32 at [_BLOCK_K, _WIDE] and their casts), beside what is resident
_TILES_BYTES = 8 << 20
# the v5e's (and v6e's) VMEM: what a program that is interpreted, or
# compiled with no chip, is sized for
_V5E_VMEM_BYTES = 128 << 20


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    """MXU matmul: operands in their own dtype, float32 accumulation."""
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _visible(q0, k0, shape, *, keys_axis, causal, seq_k):
    """The mask of one tile: a key past ``seq_k`` is padding, and under
    ``causal`` a query sees the keys at or before its own position.
    ``q0``/``k0``: the tile's first query and key; ``keys_axis``: the
    axis of ``shape`` the keys lie along."""
    keys = k0 + jax.lax.broadcasted_iota(jnp.int32, shape, keys_axis)
    mask = keys < seq_k
    if causal:
        mask &= q0 + jax.lax.broadcasted_iota(
            jnp.int32, shape, 1 - keys_axis) >= keys
    return mask


def _tiles(*spans):
    """A row block's tiles as (start, stop, masked), from its spans
    (start, stop, whether the span crosses the mask's edge): no tile
    wider than ``_WIDE``; a span that is empty gives none."""
    return [(a, min(a + _WIDE, stop), masked)
            for start, stop, masked in spans
            for a in range(start, stop, _WIDE)]


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale: float,
                      block: int, causal: bool, seq_k: int):
    """One head: q [Sq, D] against K and V [Sk, D], a block of ``block``
    query rows at a time, online softmax over the block's key tiles."""
    pad_q, pad_k = q_ref.shape[0], k_ref.shape[0]
    for q0 in range(0, pad_q, block):
        rows = min(block, pad_q - q0)
        q = q_ref[q0:q0 + rows, :]
        # keys [0, edge) are seen by every row of the block (whole
        # ``block``s of them: the tiles stay aligned), [edge, end) by some
        edge, end = seq_k // block * block, pad_k
        if causal:
            edge = min(edge, (q0 + 1) // block * block)
            end = min(end, q0 + rows)
        acc = None
        for a, b, masked in _tiles((0, edge, False), (edge, end, True)):
            v = v_ref[a:b, :]
            s = _dot(q, k_ref[a:b, :], _NT) * scale           # [rows, b-a]
            if masked:
                s = jnp.where(_visible(q0, a, s.shape, keys_axis=1,
                                       causal=causal, seq_k=seq_k),
                              s, NEG_INF)
            m_tile = jnp.max(s, axis=-1, keepdims=True)
            if acc is None:
                m = m_tile
                p = jnp.exp(s - m)
                l = jnp.sum(p, axis=-1, keepdims=True)
                acc = _dot(p.astype(v.dtype), v)
            else:
                m_new = jnp.maximum(m, m_tile)
                alpha = jnp.exp(m - m_new)
                p = jnp.exp(s - m_new)
                l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
                acc = acc * alpha + _dot(p.astype(v.dtype), v)
                m = m_new
        o_ref[q0:q0 + rows, :] = (acc * (1.0 / l)).astype(o_ref.dtype)
        # a ROW of the array, positions along the lanes: what the
        # backward's transposed tiles broadcast, and S floats a head in
        # HBM (a column would be padded to 128 lanes there)
        lse = jnp.broadcast_to(m + jnp.log(l), (rows, _LANES))
        lse_ref[:, q0:q0 + rows] = jnp.transpose(lse)[:1]


def _flash_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc, *,
                      scale: float, block: int, causal: bool, seq_k: int):
    """One q head's dq and its share of its kv head's dk and dv, a block
    of ``block`` keys at a time. The tiles are the TRANSPOSED scores
    [keys, queries]: ``p`` is rebuilt from q, k and the log-sum-exp row
    and feeds dv, dk and dq from one pass. The q heads of a GQA group are
    consecutive grid steps that add into one dk and dv."""
    import jax.experimental.pallas as pl

    g, group = pl.program_id(1), pl.num_programs(1)
    pad_q, pad_k = q_ref.shape[0], k_ref.shape[0]
    for k0 in range(0, pad_k, block):
        ks = slice(k0, min(k0 + block, pad_k))
        k, v = k_ref[ks, :], v_ref[ks, :]
        # queries [first, edge) see some of these keys, [edge, Sq) all
        first, edge = 0, pad_q if ks.stop > seq_k else 0
        if causal:
            first = k0
            edge = max(edge, ks.stop)
        edge = min(edge, pad_q)
        dk = dv = jnp.zeros(k.shape, jnp.float32)
        for a, b, masked in _tiles((first, edge, True),
                                   (edge, pad_q, False)):
            q, do = q_ref[a:b, :], do_ref[a:b, :]
            st = _dot(k, q, _NT) * scale                      # [keys, b-a]
            if masked:
                st = jnp.where(_visible(a, k0, st.shape, keys_axis=0,
                                        causal=causal, seq_k=seq_k),
                               st, NEG_INF)
            pt = jnp.exp(st - lse_ref[:, a:b])
            dv += _dot(pt.astype(do.dtype), do)
            dpt = _dot(v, do, _NT)
            dst = (pt * (dpt - delta_ref[:, a:b]) * scale).astype(q.dtype)
            dk += _dot(dst, q)
            dq = _dot(dst, k, _TN)                            # [b-a, D]
            if k0 == 0:         # the first key block is seen by every row
                dq_acc[a:b, :] = dq
            else:
                dq_acc[a:b, :] += dq

        @pl.when(g == 0)
        def _first_of_group():
            dk_acc[ks, :] = dk
            dv_acc[ks, :] = dv

        @pl.when(g > 0)
        def _rest_of_group():
            dk_acc[ks, :] += dk
            dv_acc[ks, :] += dv

    dq_ref[...] = dq_acc[...].astype(dq_ref.dtype)

    @pl.when(g == group - 1)
    def _last_of_group():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _vmem_limit() -> int:
    """What a kernel may take of VMEM: half of the chip's (the backend's
    TPU, as Pallas describes it: 128 MiB on a v5e or v6e, 64 on a v5p,
    16 before), so that Mosaic's own buffers and the next grid step's
    fetches have the rest. The blocks and ``_MAX_UNROLLED`` were measured
    on the v5e alone (PERF.md PR 48)."""
    # the backend itself, not ``on_chip``: a program compiled for a
    # topology with no chip behind it is the v5e's
    if jax.default_backend() != "tpu":
        return _V5E_VMEM_BYTES // 2
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.get_tpu_info().vmem_capacity_bytes // 2


def _stays_resident(seq_q: int, seq_k: int, head_dim: int, dtype,
                    causal: bool) -> bool:
    """Whether the kernels take these sequences: no more pairs than they
    are unrolled for, and the backward's VMEM (the larger of the two)
    inside ``_vmem_limit``: q, do, k, v in and dq, dk, dv out, each
    buffered twice, the two rows of statistics (8 sublanes of float32)
    twice, and three float32 accumulators; a tile's lanes are padded to
    128."""
    hidden = min(seq_q, seq_k) if causal else 0     # above the diagonal
    pairs = seq_q * seq_k - hidden * hidden // 2
    pad_q, pad_k = (s + -s % _LANES for s in (seq_q, seq_k))
    width, item = max(head_dim, _LANES), jnp.dtype(dtype).itemsize
    resident = (2 * item * width * (3 * pad_q + 4 * pad_k)
                + 2 * 2 * 8 * 4 * pad_q
                + 4 * width * (pad_q + 2 * pad_k))
    return (pairs <= _MAX_UNROLLED
            and resident + _TILES_BYTES <= _vmem_limit())


def _by_head(x: jax.Array, num_kv: int) -> jax.Array:
    """[B, S, H, D] -> [B * Hkv, H // Hkv, S', D]: a kv head's q heads
    side by side (``_repeat_kv``'s order), S padded with zeros to whole
    ``_LANES`` (a block's last tile may be short, a row of log-sum-exp
    is stored in whole lanes)."""
    batch, seq, heads, head_dim = x.shape
    x = jnp.pad(x, ((0, 0), (0, -seq % _LANES), (0, 0), (0, 0)))
    return x.transpose(0, 2, 1, 3).reshape(
        batch * num_kv, heads // num_kv, x.shape[1], head_dim)


def _from_heads(x: jax.Array, batch: int, seq: int) -> jax.Array:
    """``_by_head``'s inverse, the padding dropped."""
    return x.reshape(batch, -1, *x.shape[2:])[:, :, :seq] \
        .transpose(0, 2, 1, 3)


def _head_specs(pad_q: int, pad_k: int, head_dim: int):
    """Block specs over the grid (kv head, q head of its group): a q
    head's [Sq, D], its row of statistics [1, Sq], a kv head's [Sk, D]
    (the same block for the whole group: fetched once)."""
    import jax.experimental.pallas as pl
    return (pl.BlockSpec((None, None, pad_q, head_dim),
                         lambda i, g: (i, g, 0, 0)),
            pl.BlockSpec((None, None, 1, pad_q), lambda i, g: (i, g, 0, 0)),
            pl.BlockSpec((None, pad_k, head_dim), lambda i, g: (i, 0, 0)))


def _compiler_params(*semantics: str):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=_vmem_limit())


@functools.partial(jax.jit, static_argnames=("causal", "block", "interpret"))
def _flash_forward(q, k, v, *, causal: bool, block: Optional[int],
                   interpret: bool):
    """-> (o [B, S, H, D], log-sum-exp [B * Hkv, H // Hkv, 1, S']).
    ``block``: query rows a block."""
    import jax.experimental.pallas as pl

    batch, seq_q, num_heads, head_dim = q.shape
    seq_k, num_kv = k.shape[1], k.shape[2]
    qh = _by_head(q, num_kv)
    kh, vh = (_by_head(x, num_kv)[:, 0] for x in (k, v))
    q_spec, row_spec, kv_spec = _head_specs(qh.shape[2], kh.shape[1],
                                            head_dim)
    o, lse = pl.pallas_call(
        functools.partial(_flash_fwd_kernel, scale=head_dim ** -0.5,
                          block=block or _BLOCK_Q, causal=causal,
                          seq_k=seq_k),
        grid=(batch * num_kv, num_heads // num_kv),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[q_spec, row_spec],
        out_shape=[jax.ShapeDtypeStruct(qh.shape, q.dtype),
                   jax.ShapeDtypeStruct((*qh.shape[:2], 1, qh.shape[2]),
                                        jnp.float32)],
        compiler_params=_compiler_params("parallel", "parallel"),
        name="flash_attention_fwd_pallas", interpret=interpret,
    )(qh, kh, vh)
    return _from_heads(o, batch, seq_q), lse


@functools.partial(jax.jit, static_argnames=("causal", "block", "interpret"))
def _flash_backward(q, k, v, o, lse, do, *, causal: bool,
                    block: Optional[int], interpret: bool):
    """-> (dq, dk, dv), shaped as q, k, v. ``block``: key rows a block."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, seq_q, num_heads, head_dim = q.shape
    seq_k, num_kv = k.shape[1], k.shape[2]
    qh, doh = (_by_head(x, num_kv) for x in (q, do))
    kh, vh = (_by_head(x, num_kv)[:, 0] for x in (k, v))
    # delta = rowsum(o * do): what softmax's backward subtracts from dp
    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1,
                    keepdims=True)
    delta = _by_head(delta, num_kv).reshape(lse.shape)
    q_spec, row_spec, kv_spec = _head_specs(qh.shape[2], kh.shape[1],
                                            head_dim)
    dq, dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_kernel, scale=head_dim ** -0.5,
                          block=block or _BLOCK_K, causal=causal,
                          seq_k=seq_k),
        grid=(batch * num_kv, num_heads // num_kv),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=[q_spec, kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype)
                   for x in (qh, kh, vh)],
        scratch_shapes=[pltpu.VMEM(x.shape[-2:], jnp.float32)
                        for x in (qh, kh, vh)],
        compiler_params=_compiler_params("parallel", "arbitrary"),
        name="flash_attention_bwd_pallas", interpret=interpret,
    )(qh, kh, vh, doh, lse, delta)
    return (_from_heads(dq, batch, seq_q),
            _from_heads(dk[:, None], batch, seq_k),
            _from_heads(dv[:, None], batch, seq_k))


def flash_attention(q, k, v, causal: bool = True, block_q=None,
                    block_k=None, interpret: bool | None = None):
    """Flash attention, forward and backward, at any length: no S x S
    array in either. The fused Pallas kernels where a
    head's sequence stays in VMEM (``_stays_resident``: up to 4,096
    causal positions on the v5e), the same online softmax as an XLA scan
    (``blockwise_attention``) beyond. ``block_q`` (the query rows of a
    forward block), ``block_k`` (the key rows of a backward block) and
    ``interpret`` are the tests': by shape and by backend when None."""
    if not _stays_resident(q.shape[1], k.shape[1], q.shape[-1], q.dtype,
                           causal):
        return blockwise_attention(q, k, v, causal=causal)
    return _flash_kernels(q, k, v, causal, block_q, block_k, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_kernels(q, k, v, causal, block_q, block_k, interpret):
    return _flash_fwd_rule(q, k, v, causal, block_q, block_k, interpret)[0]


def _interpreted(interpret: Optional[bool]) -> bool:
    # decided OUTSIDE the jitted calls: it is part of their cache key
    return pallas_interpret() if interpret is None else interpret


# ``checkpoint_name``s of what the forward kernel makes, named where the
# backward rule takes them as residuals: a ``jax.checkpoint`` policy that
# keeps both runs the forward kernel once; under every other policy they
# are inert
FLASH_RESIDUALS = ("flash_attention.o", "flash_attention.lse")


def _flash_fwd_rule(q, k, v, causal, block_q, block_k, interpret):
    o, lse = _flash_forward(q, k, v, causal=causal, block=block_q,
                            interpret=_interpreted(interpret))
    o, lse = map(checkpoint_name, (o, lse), FLASH_RESIDUALS)
    return o, (q, k, v, o, lse)


def _flash_bwd_rule(causal, block_q, block_k, interpret, res, g):
    return _flash_backward(*res, g, causal=causal, block=block_k,
                           interpret=_interpreted(interpret))


_flash_kernels.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def kernels_tile(q: jax.Array, k: jax.Array) -> bool:
    """Where ``flash_attention`` is what to run, read from the arguments'
    shapes: a head width the MXU contracts whole (64: half its depth,
    128, 256), q heads in whole GQA groups, and at least 256 queries.
    The kernels take shorter sequences too (padded to 128, masked both
    ways), but there the reference's S x S arrays are small and XLA's
    fusions win: at 16,384 tokens of head_dim 64 on the v5e the
    reference's forward + backward takes 0.39 of the kernels' time at
    128 positions, 0.81 at ViT's 197, 1.01 at 256, 1.99 at 512 (at
    head_dim 128: 0.63, 1.16, 1.41, 2.86; PERF.md PR 48). Causal or not,
    a sequence that ends inside a block, and one too long to stay in
    VMEM (it takes the scan) are all inside."""
    (_, seq_q, heads, head_dim), num_kv = q.shape, k.shape[2]
    return (head_dim in (64, 128, 256) and heads % num_kv == 0
            and seq_q >= 256)


def use_flash_on(mesh) -> Optional[bool]:
    """``attention``'s ``use_flash`` for a model built on ``mesh``, asked
    once by its constructor. Under a mesh False: the reference, because a
    Mosaic call carries no partitioning rule. Off one None: the dispatcher
    reads the shapes; on a TPU it will most likely find kernels to trace,
    so the import of Pallas, a second of Python that pulls the GPU
    dialects in, starts here on a daemon thread and runs under the
    weights' init instead of inside the first trace. Whoever imports
    Pallas meanwhile waits on the module's import lock for this thread."""
    if mesh is not None:
        return False
    if on_chip():
        import threading

        def load():
            import jax.experimental.pallas      # noqa: F401
            import jax.experimental.pallas.tpu  # noqa: F401

        threading.Thread(target=load, name="pallas-import",
                         daemon=True).start()
    return None


def attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
              causal: bool = True,
              positions_q: Optional[jax.Array] = None,
              positions_k: Optional[jax.Array] = None,
              use_flash: Optional[bool] = None) -> jax.Array:
    """Dispatcher: ``flash_attention`` on a TPU where the shapes tile
    (``kernels_tile``), the reference otherwise. Explicit position vectors
    force the reference path (the kernels assume contiguous 0..S-1
    positions), and so does a caller whose mesh would partition the call
    (``use_flash=False``, from ``use_flash_on``)."""
    if use_flash is None:
        use_flash = (on_chip() and positions_q is None and positions_k is None
                     and kernels_tile(q, k))
    if use_flash:
        return flash_attention(q, k, v, causal)
    return reference_attention(q, k, v, causal=causal,
                               positions_q=positions_q,
                               positions_k=positions_k)
