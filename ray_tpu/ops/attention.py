"""Attention: reference JAX implementation + Pallas TPU flash kernels.

Reference capability: the reference repo delegates attention to vLLM /
flash-attn CUDA kernels (outside its tree). Here it is in-tree and
TPU-native:

- ``attention``      — dispatcher; GQA-aware, causal or not,
  differentiable. Takes ``flash_attention`` on a TPU where ``kernels_tile``
  says the shapes tile, the reference everywhere else.
  ``packed_attention`` is the same for a qkv projection's one product.
- ``flash_attention``— a fused pair of Pallas kernels behind one
  ``custom_vjp``: the forward keeps ``o`` and a row of log-sum-exp, the
  backward rebuilds ``p`` from q, k and that row a tile at a time and
  gives dq, dk and dv from one pass. No S x S (nor S x block) array
  reaches HBM in either direction; tiles above the causal diagonal are
  never visited. q, k, v, dO, O, dq, dk and dv are read and written as
  the projections hold them, ``[B, S, H * D]`` a lane tile at a time (two
  64-wide heads a tile): no transpose or copy stands around the calls.
  The kernels keep a head's whole sequence in VMEM: a longer one
  (``_stays_resident``) takes ``blockwise_attention``.
- ``blockwise_attention`` — the XLA online-softmax scan, O(S x block)
  memory at any length: what a mesh runs (a Mosaic call cannot be
  partitioned), what Ulysses runs, and the long sequences' path.

Shapes follow the JAX convention [batch, seq, heads, head_dim].
"""

from __future__ import annotations

import functools
import operator
from typing import NamedTuple, Optional

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
# The kernels read q, k, v and dO and write O, dq, dk and dv where the
# projections keep them: ``[B, S, H * D]``, a block the whole sequence of
# one LANE TILE of it. A head of 128 or 256 lanes is a tile; narrower
# heads lie side by side in one (two of 64), each in its SLOT, and are
# never cut apart: a matmul contracts the tile's 128 lanes with every lane
# outside the head's slot zeroed, which costs the 128-deep MXU what a
# 64-deep contraction does, and a product that comes out 128 lanes wide
# keeps the slot that is the head's. Under GQA a q head and its kv head
# may lie in different slots of their tiles: the one is rolled to the
# other's. Nothing is transposed, padded or copied on the way in or out
# where S is whole lanes and the heads fill their tiles (``_lane_tiles``).
#
# A grid step holds a tile whole in VMEM (q, K, V; in the backward do
# and the rows of statistics besides) and walks the score matrix in
# row blocks whose tiles are STATIC slices: a block's tiles stop at the
# causal diagonal, only the tile that crosses the mask's edge pays for a
# mask, and the rest of the row is taken in tiles up to ``_WIDE`` columns.
# The loops are unrolled as Python, so the kernel is straight-line code
# the scheduler can overlap (square tiles under ``fori_loop`` with traced
# bounds ran 1.45x slower on the v5e, PERF.md PR 48). Nothing of
# S x S or S x block size is written to HBM in either direction; what the
# forward keeps for the backward is ``o`` and a row of log-sum-exp a head.

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


def _lane_tiles(head_dim: int) -> tuple[int, int]:
    """(heads a lane tile, lanes a head): a width that divides ``_LANES``
    shares its tile (64: two heads side by side), every other is padded
    to whole tiles (128 and 256 are one and two as they are)."""
    if _LANES % head_dim == 0:
        return _LANES // head_dim, head_dim
    return 1, head_dim + -head_dim % _LANES


def _kv_slot(tile, slot, per_tile: int, group: int):
    """The slot, in its kv tile, of the kv head of the q head in ``slot``
    of q tile ``tile``: ``slot`` itself where every head has its own."""
    if group == 1 or per_tile == 1:
        return slot
    return (tile * per_tile + slot) // group % per_tile


def _in_slot(shape, slot, width: int):
    """[rows, lanes] of bool: the lanes of ``slot``, ``width`` a slot."""
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return (lane >= slot * width) & (lane < (slot + 1) * width)


def _to_slot(x, src, dst, width: int, beside=None):
    """[rows, lanes]: the head in slot ``src`` moved to slot ``dst``,
    the other lanes zeros, or ``beside``'s (a slot is ``width`` lanes,
    and may be traced). A tile of one head is returned as it is."""
    from jax.experimental.pallas import tpu as pltpu

    lanes = x.shape[1]
    if lanes == width:
        return x
    if src is not dst:
        # Mosaic rotates 32-bit lanes only
        x = pltpu.roll(x.astype(jnp.float32), (dst - src) * width % lanes,
                       1).astype(x.dtype)
    return jnp.where(_in_slot(x.shape, dst, width), x,
                     jnp.zeros_like(x) if beside is None else beside)


def _q_tile(group: int):
    """This grid step's q tile, over the grid (batch, kv tile, q tile of
    the kv tile's ``group``, ...): asked at a kernel's top level."""
    import jax.experimental.pallas as pl
    return pl.program_id(1) * group + pl.program_id(2)


def _each_head(tile, per_tile: int, group: int, head) -> None:
    """``head(slot, kv_slot)`` for every q head of q tile ``tile``. The
    heads of a shared tile run under ONE loop: Mosaic gives every
    unrolled tile of the walk VMEM of its own, and a loop's body is laid
    out once."""
    def body(slot, carry=None):
        head(slot, _kv_slot(tile, slot, per_tile, group))

    if per_tile == 1:
        body(0)
    else:
        jax.lax.fori_loop(0, per_tile, body, None)


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale: float,
                      block: int, causal: bool, seq_k: int, width: int,
                      group: int):
    """One lane tile of q heads ([Sq, lanes], a head ``width`` lanes)
    against its kv tile's K and V [Sk, lanes]: a head at a time, a block
    of ``block`` query rows at a time, online softmax over the block's
    key tiles."""
    pad_q, pad_k = q_ref.shape[0], k_ref.shape[0]
    tile, per_tile = _q_tile(group), q_ref.shape[1] // width

    def head(slot, kv_slot):
        for q0 in range(0, pad_q, block):
            rows = min(block, pad_q - q0)
            # the head of q where its kv head lies, alone in the tile:
            # the contraction over all the lanes is the head's own
            q = _to_slot(q_ref[q0:q0 + rows, :], slot, kv_slot, width)
            # keys [0, edge) are seen by every row of the block (whole
            # ``block``s of them: the tiles stay aligned), [edge, end) by
            # some
            edge, end = seq_k // block * block, pad_k
            if causal:
                edge = min(edge, (q0 + 1) // block * block)
                end = min(end, q0 + rows)
            acc = None
            for a, b, masked in _tiles((0, edge, False), (edge, end, True)):
                v = v_ref[a:b, :]
                s = _dot(q, k_ref[a:b, :], _NT) * scale       # [rows, b-a]
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
            # p @ v is the tile's lanes wide: the kv head's slot of it is
            # this head's, and goes to the slot the head has in q's tile,
            # beside what the tile's other heads wrote
            o_ref[q0:q0 + rows, :] = _to_slot(
                (acc * (1.0 / l)).astype(o_ref.dtype), kv_slot, slot, width,
                beside=o_ref[q0:q0 + rows, :])
            # a ROW of the array, positions along the lanes: what the
            # backward's transposed tiles broadcast, and S floats a head
            # in HBM (a column would be padded to 128 lanes there)
            lse = jnp.broadcast_to(m + jnp.log(l), (rows, _LANES))
            lse_ref[slot, :, q0:q0 + rows] = jnp.transpose(lse)[:1]

    _each_head(tile, per_tile, group, head)


def _flash_bwd_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, *refs,
                      scale: float, block: int, causal: bool, seq_k: int,
                      width: int, group: int):
    """One lane tile of q heads' dq and its share of its kv tile's dk and
    dv, a block of ``block`` keys at a time. The tiles are the TRANSPOSED
    scores [keys, queries]: ``p`` is rebuilt from q, k and the log-sum-exp
    row and feeds dv, dk and dq from one pass, a head of the tile after
    the other over each tile's q and dO (unrolled, unlike the forward's:
    this stack fits, and a tile's loads, its mask and its dq are the
    heads' together: 1.60 against 1.75 ms a layer at the cell's shape,
    PERF.md PR 51). The q tiles of a kv tile are consecutive grid steps
    that add into one dk and dv.

    ``refs``: the outputs, three float32 accumulators and delta's rows
    (8 sublanes a head, as a row of log-sum-exp is held). Three outputs
    are dq, dk and dv; ONE is the packed gradient, whose lane tiles of
    dq, dk and dv are three more grid steps' blocks (the grid's last
    axis: the first computes, each writes the accumulator it names)."""
    import jax.experimental.pallas as pl

    *outs, dq_acc, dk_acc, dv_acc, delta_rows = refs
    g = pl.program_id(2)
    pad_q, pad_k = q_ref.shape[0], k_ref.shape[0]
    tile, per_tile = _q_tile(group), q_ref.shape[1] // width
    kv_slots = [_kv_slot(tile, slot, per_tile, group)
                for slot in range(per_tile)]

    def accumulate():
        # delta = rowsum(o * do), what softmax's backward subtracts from
        # dp: a ROW a head, as the log-sum-exp is. Ones over a head's
        # lanes against o * do sum the head's lanes and lay the sums out
        # along the lanes in one matmul; the float32 product goes in as
        # two bfloat16 halves, which hold the 16 bits it has
        for a in range(0, pad_q, _BLOCK_Q):
            b = min(a + _BLOCK_Q, pad_q)
            product = (o_ref[a:b, :].astype(jnp.float32)
                       * do_ref[a:b, :].astype(jnp.float32))
            high = product.astype(jnp.bfloat16)
            low = (product - high.astype(jnp.float32)).astype(jnp.bfloat16)
            for slot in range(per_tile):
                ones = _in_slot((8, q_ref.shape[1]), slot,
                                width).astype(jnp.bfloat16)
                delta_rows[slot, :, a:b] = (_dot(ones, high, _NT)
                                            + _dot(ones, low, _NT))
        for k0 in range(0, pad_k, block):
            ks = slice(k0, min(k0 + block, pad_k))
            # a kv head where its q head lies, alone in the tile: the
            # scores contract the head's lanes only, and dq comes out in
            # the q head's slot with zeros beside it
            kvs = [(_to_slot(k_ref[ks, :], kv_slot, slot, width),
                    _to_slot(v_ref[ks, :], kv_slot, slot, width))
                   for slot, kv_slot in enumerate(kv_slots)]
            # queries [first, edge) see some of these keys, [edge, Sq) all
            first, edge = 0, pad_q if ks.stop > seq_k else 0
            if causal:
                first = k0
                edge = max(edge, ks.stop)
            edge = min(edge, pad_q)
            zeros = jnp.zeros((ks.stop - k0, q_ref.shape[1]), jnp.float32)
            dks, dvs = [zeros] * per_tile, [zeros] * per_tile
            for a, b, masked in _tiles((first, edge, True),
                                       (edge, pad_q, False)):
                q, do = q_ref[a:b, :], do_ref[a:b, :]
                if masked:
                    visible = _visible(a, k0, (ks.stop - k0, b - a),
                                       keys_axis=0, causal=causal,
                                       seq_k=seq_k)
                dq = None
                for slot, (k, v) in enumerate(kvs):
                    st = _dot(k, q, _NT) * scale              # [keys, b-a]
                    if masked:
                        st = jnp.where(visible, st, NEG_INF)
                    pt = jnp.exp(st - lse_ref[slot, :, a:b])
                    dvs[slot] += _dot(pt.astype(do.dtype), do)
                    dpt = _dot(v, do, _NT)
                    dst = (pt * (dpt - delta_rows[slot, :1, a:b])
                           * scale).astype(q.dtype)
                    dks[slot] += _dot(dst, q)
                    part = _dot(dst, k, _TN)                  # [b-a, lanes]
                    dq = part if dq is None else dq + part
                if k0 == 0:     # the first key block is seen by every row
                    dq_acc[a:b, :] = dq
                else:
                    dq_acc[a:b, :] += dq
            # dst.T @ q and p.T @ do are the tile's lanes wide: the q
            # head's slot of each is its kv head's share, and goes to the
            # kv head's slot
            dk, dv = (functools.reduce(operator.add, (
                _to_slot(d, slot, kv_slot, width)
                for slot, (kv_slot, d) in enumerate(zip(kv_slots, ds))))
                for ds in (dks, dvs))

            @pl.when(g == 0)
            def _first_of_group():
                dk_acc[ks, :] = dk
                dv_acc[ks, :] = dv

            @pl.when(g > 0)
            def _rest_of_group():
                dk_acc[ks, :] += dk
                dv_acc[ks, :] += dv

    if len(outs) == 1:
        pl.when(pl.program_id(3) == 0)(accumulate)
        for step, acc in enumerate((dq_acc, dk_acc, dv_acc)):
            @pl.when(pl.program_id(3) == step)
            def _write(acc=acc):
                outs[0][...] = acc[...].astype(outs[0].dtype)
        return
    accumulate()
    dq_ref, dk_ref, dv_ref = outs
    dq_ref[...] = dq_acc[...].astype(dq_ref.dtype)

    @pl.when(g == pl.num_programs(2) - 1)
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
    inside ``_vmem_limit``: q, do, o, k, v in and dq, dk, dv out, each
    buffered twice, the row of log-sum-exp twice and delta's once (8
    sublanes of float32), and three float32 accumulators; a tile is 128
    lanes or the head's."""
    hidden = min(seq_q, seq_k) if causal else 0     # above the diagonal
    pairs = seq_q * seq_k - hidden * hidden // 2
    pad_q, pad_k = (s + -s % _LANES for s in (seq_q, seq_k))
    width, item = max(head_dim, _LANES), jnp.dtype(dtype).itemsize
    resident = (2 * item * width * (4 * pad_q + 4 * pad_k)
                + 3 * 8 * 4 * pad_q
                + 4 * width * (pad_q + 2 * pad_k))
    return (pairs <= _MAX_UNROLLED
            and resident + _TILES_BYTES <= _vmem_limit())


class _Layout(NamedTuple):
    """Where a call's lane tiles are, read from its operands' shapes."""
    width: int          # lanes a head
    per_tile: int       # heads a lane tile
    kv_tiles: int       # lane tiles of k, and of v
    group: int          # q tiles a kv tile: q heads a kv head
    # the lane tile at which q, k and v begin in their arrays: three
    # arrays, or the qkv projection's one
    first: tuple = (0, 0, 0)

    @property
    def lanes(self) -> int:
        return self.width * self.per_tile

    # heads in whole tiles: kv heads up to the next tile and q heads with
    # them (heads of zeros, and none where ``kernels_tile``)
    @property
    def kv_heads(self) -> int:
        return self.kv_tiles * self.per_tile

    @property
    def q_heads(self) -> int:
        return self.kv_heads * self.group

    def specs(self, pad_q: int, pad_k: int, held=lambda b, i, *step: (b, i)):
        """Block specs over the grid (batch, kv tile, q tile of the kv
        tile's group, ...): ``of_q(first)``, a q tile's [Sq', lanes] of
        an array whose q tiles begin at lane tile ``first``; ``of_kv``,
        a kv tile's [Sk', lanes] (the same block for the whole group:
        fetched once); the q tile's heads' rows [heads a tile, 1, Sq'].
        ``held(batch, kv tile, ...)``: whose blocks a grid step holds."""
        import jax.experimental.pallas as pl

        def of_q(first=0):
            def index(b, i, g, *step):
                b, i = held(b, i, *step)
                return b, 0, first + i * self.group + g
            return pl.BlockSpec((None, pad_q, self.lanes), index)

        def of_kv(first=0):
            def index(b, i, g, *step):
                b, i = held(b, i, *step)
                return b, 0, first + i
            return pl.BlockSpec((None, pad_k, self.lanes), index)

        def rows_index(b, i, g, *step):
            b, i = held(b, i, *step)
            return b, i * self.group + g, 0, 0

        return of_q, of_kv, pl.BlockSpec((None, self.per_tile, 1, pad_q),
                                         rows_index)


def _in_lanes(x: jax.Array, heads: int, width: int) -> jax.Array:
    """[B, S, ..., h, D] -> [B, S', ... * heads * width]: the array as
    the projection wrote it, reshaped, where S is whole ``_LANES`` (a
    block's last tile may be short, a row of log-sum-exp is stored in
    whole lanes), h is ``heads`` and D is ``width``; padded with zeros
    where not."""
    batch, seq, *_, h, head_dim = x.shape
    x = jnp.pad(x, ((0, 0), (0, -seq % _LANES), *[(0, 0)] * (x.ndim - 4),
                    (0, heads - h), (0, width - head_dim)))
    return x.reshape(batch, x.shape[1], -1)


def _from_lanes(x: jax.Array, width: int, shape) -> jax.Array:
    """``_in_lanes``'s inverse, to an array of ``shape``."""
    (_, seq, *packed, heads, head_dim) = shape
    return x.reshape(*x.shape[:2], *packed, -1, width)[
        :, :seq, ..., :heads, :head_dim]


def _operands(qkv, heads: Optional[int]):
    """((q, k, v) in lanes, their ``_Layout``, o's shape) of q
    [B, S, H, D], k and v [B, S, Hkv, D], or of ONE qkv projection's
    [B, S, 3 * heads * D]: then q, k and v are the same array, begun at
    three lane tiles, and o is [B, S, heads * D]."""
    if len(qkv) == 1:
        batch, seq, _ = qkv[0].shape
        qkv = (qkv[0].reshape(batch, seq, 3, heads, -1),)
    q_heads, kv_heads, head_dim = (qkv[0].shape[-2], qkv[-1].shape[-2],
                                   qkv[0].shape[-1])
    per_tile, width = _lane_tiles(head_dim)
    lay = _Layout(width, per_tile, -(-kv_heads // per_tile),
                  q_heads // kv_heads)
    shape = (*qkv[0].shape[:2], q_heads, head_dim)
    if len(qkv) == 3:
        return tuple(_in_lanes(x, n, width) for x, n in zip(
            qkv, (lay.q_heads, lay.kv_heads, lay.kv_heads))), lay, shape
    packed = _in_lanes(qkv[0], lay.kv_heads, width)
    return (packed,) * 3, lay._replace(
        first=(0, lay.kv_tiles, 2 * lay.kv_tiles)), shape


def _compiler_params(*semantics: str):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=_vmem_limit())


_STATIC = ("heads", "causal", "block", "interpret")


@functools.partial(jax.jit, static_argnames=_STATIC)
def _flash_forward(*qkv, heads: Optional[int] = None, causal: bool,
                   block: Optional[int], interpret: bool):
    """``_operands``'s q, k, v, or packed qkv of ``heads`` -> (o
    [B, S, H, D], or [B, S, H * D] of a packed qkv; log-sum-exp
    [B, H', 1, S']), over the grid (batch, kv tile, q tile of the kv
    tile's group): a q tile's [Sq', lanes] and its heads' rows, a kv
    tile's [Sk', lanes] (the same block for the whole group: fetched
    once). ``block``: query rows a block."""
    import jax.experimental.pallas as pl

    (q, k, v), lay, shape = _operands(qkv, heads)
    batch, pad_q, pad_k = q.shape[0], q.shape[1], k.shape[1]
    seq_k, head_dim = qkv[-1].shape[1], shape[-1]
    of_q, of_kv, rows = lay.specs(pad_q, pad_k)
    o, lse = pl.pallas_call(
        functools.partial(_flash_fwd_kernel, scale=head_dim ** -0.5,
                          block=block or _BLOCK_Q, causal=causal,
                          seq_k=seq_k, width=lay.width, group=lay.group),
        grid=(batch, lay.kv_tiles, lay.group),
        in_specs=[of_q(lay.first[0]), of_kv(lay.first[1]),
                  of_kv(lay.first[2])],
        out_specs=[of_q(), rows],
        out_shape=[jax.ShapeDtypeStruct(
                       (batch, pad_q, lay.q_heads * lay.width), q.dtype),
                   jax.ShapeDtypeStruct((batch, lay.q_heads, 1, pad_q),
                                        jnp.float32)],
        compiler_params=_compiler_params("parallel", "parallel", "parallel"),
        name="flash_attention_fwd_pallas", interpret=interpret,
    )(q, k, v)
    o = _from_lanes(o, lay.width, shape)
    return o.reshape(*shape[:2], -1) if len(qkv) == 1 else o, lse


@functools.partial(jax.jit, static_argnames=_STATIC)
def _flash_backward(qkv, o, lse, do, *, heads: Optional[int] = None,
                    causal: bool, block: Optional[int], interpret: bool):
    """-> the gradients of ``qkv`` (``_operands``'s three arrays or one),
    shaped as they are, over the forward's grid. The ONE gradient of a
    packed qkv takes three grid steps a tile: the first computes and
    writes dq's lane tile, the two after it dk's and dv's, and while
    those two run the NEXT tile's operands are what is fetched.
    ``block``: key rows a block."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (q, k, v), lay, shape = _operands(qkv, heads)
    batch, pad_q, pad_k = q.shape[0], q.shape[1], k.shape[1]
    tiles, packed = lay.kv_tiles, len(qkv) == 1
    seq_k, head_dim = qkv[-1].shape[1], shape[-1]
    o, do = (_in_lanes(x.reshape(shape), lay.q_heads, lay.width)
             for x in (o, do))

    def ahead(b, i, step):
        """While a tile's dk and dv are written, the NEXT tile's blocks
        are held: fetched beside the work, not after it."""
        nxt = jnp.minimum(b * tiles + i + jnp.minimum(step, 1),
                          batch * tiles - 1)
        return nxt // tiles, nxt % tiles

    if packed:
        of_q, of_kv, rows = lay.specs(pad_q, pad_k, ahead)
        grid = (batch, tiles, lay.group, 3)
        out_specs = pl.BlockSpec(
            (None, pad_q, lay.lanes),
            lambda b, i, g, step: (b, 0, step * tiles + i))
        out_shape = jax.ShapeDtypeStruct(q.shape, q.dtype)
    else:
        of_q, of_kv, rows = lay.specs(pad_q, pad_k)
        grid = (batch, tiles, lay.group)
        out_specs = [of_q(), of_kv(), of_kv()]
        out_shape = [jax.ShapeDtypeStruct(x.shape, x.dtype)
                     for x in (q, k, v)]
    grads = pl.pallas_call(
        functools.partial(_flash_bwd_kernel, scale=head_dim ** -0.5,
                          block=block or _BLOCK_K, causal=causal,
                          seq_k=seq_k, width=lay.width, group=lay.group),
        grid=grid,
        in_specs=[of_q(lay.first[0]), of_kv(lay.first[1]),
                  of_kv(lay.first[2]), of_q(), of_q(), rows],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[*(pltpu.VMEM((pad, lay.lanes), jnp.float32)
                          for pad in (pad_q, pad_k, pad_k)),
                        pltpu.VMEM((lay.per_tile, 8, pad_q), jnp.float32)],
        compiler_params=_compiler_params(
            "parallel", "parallel", *["arbitrary"] * (len(grid) - 2)),
        name="flash_attention_bwd_pallas", interpret=interpret,
    )(q, k, v, do, o, lse)
    if packed:
        grad = _from_lanes(grads, lay.width, (*shape[:2], 3, *shape[2:]))
        return (grad.reshape(qkv[0].shape),)
    return tuple(_from_lanes(g, lay.width, x.shape)
                 for g, x in zip(grads, qkv))


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
    return _flash_kernels((q, k, v), None, causal, block_q, block_k,
                          interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5))
def _flash_kernels(qkv, heads, causal, block_q, block_k, interpret):
    """``_operands``'s ``qkv`` (a tuple of three, or of the packed one
    and its ``heads``) -> o."""
    return _flash_fwd_rule(qkv, heads, causal, block_q, block_k,
                           interpret)[0]


def _interpreted(interpret: Optional[bool]) -> bool:
    # decided OUTSIDE the jitted calls: it is part of their cache key
    return pallas_interpret() if interpret is None else interpret


# ``checkpoint_name``s of what the forward kernel makes, named where the
# backward rule takes them as residuals: a ``jax.checkpoint`` policy that
# keeps both runs the forward kernel once; under every other policy they
# are inert
FLASH_RESIDUALS = ("flash_attention.o", "flash_attention.lse")


def _flash_fwd_rule(qkv, heads, causal, block_q, block_k, interpret):
    o, lse = _flash_forward(*qkv, heads=heads, causal=causal, block=block_q,
                            interpret=_interpreted(interpret))
    o, lse = map(checkpoint_name, (o, lse), FLASH_RESIDUALS)
    return o, (qkv, o, lse)


def _flash_bwd_rule(heads, causal, block_q, block_k, interpret, res, g):
    return (_flash_backward(*res, g, heads=heads, causal=causal,
                            block=block_k,
                            interpret=_interpreted(interpret)),)


_flash_kernels.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def kernels_tile(q: jax.Array, k: jax.Array) -> bool:
    """Where ``flash_attention`` is what to run, read from the arguments'
    shapes: a head width the MXU contracts whole (64: half its depth, two
    heads a lane tile; 128; 256), q heads in whole GQA groups, kv heads
    that fill their lane tiles (an odd number of 64-wide heads would be
    padded with one of zeros, a copy and a head's work for nothing: it
    takes the reference), and at least 256 queries.
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
            and num_kv % _lane_tiles(head_dim)[0] == 0 and seq_q >= 256)


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


def packed_attention(qkv: jax.Array, num_heads: int, *, causal: bool = True,
                     use_flash: Optional[bool] = None) -> jax.Array:
    """``attention`` of a qkv projection's ONE product
    [B, S, 3 * H * D] -> o [B, S, H * D], the heads side by side in the
    lanes on both sides: the form in which XLA holds the two row-major,
    as the kernels read and write them (an array [..., H, 64] it lays out
    S-minor, because 64 lanes of a tile's 128 would be padding, and
    re-lays for every Mosaic call). Where the dispatcher takes the kernels
    they read q, k and v out of the product where it lies and give its
    gradient whole, as the projection's two gradient matmuls take it:
    nothing is cut out of the product or put together for it. Everywhere
    else the product is cut and ``attention`` runs."""
    batch, seq, lanes = qkv.shape
    head_dim = lanes // (3 * num_heads)
    shapes = [jax.ShapeDtypeStruct((batch, seq, num_heads, head_dim),
                                   qkv.dtype)] * 2
    if use_flash is None:
        use_flash = on_chip() and kernels_tile(*shapes)
    if use_flash and _stays_resident(seq, seq, head_dim, qkv.dtype, causal):
        return _flash_kernels((qkv,), num_heads, causal, None, None, None)
    q, k, v = (qkv.reshape(batch, seq, 3, num_heads, head_dim)[:, :, n]
               for n in range(3))
    return attention(q, k, v, causal=causal,
                     use_flash=use_flash).reshape(batch, seq, -1)
