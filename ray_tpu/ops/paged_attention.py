"""Paged decode attention: one query token vs a block-pool KV cache.

Reference capability: vLLM's paged-attention kernel (the engine behind
`ray.llm`'s serving tier, outside the reference tree; config surface at
`python/ray/llm/_internal/serve/deployments/llm/vllm/vllm_models.py:126`).
TPU-native design:

- K/V live in a shared BLOCK POOL ``[num_blocks, block_size, Hkv, D]``;
  each slot's logical sequence is a list of physical block ids (the
  block table). Blocks are immutable once full, so identical prompt
  prefixes SHARE physical blocks (see ``llm/paged_cache.py``).
- ``paged_decode_attention`` — the Mosaic kernel or the XLA gather
  reference (the kernel's oracle), as ``impl`` says; ``default_impl`` is
  the platform's side of that choice (``LlamaModel.paged_decode_impl``
  is the one place that makes it).
- ``paged_decode_attention_reference`` — gathers a slot's blocks into a
  dense view and runs ``ragged_decode_attention_reference`` (one query
  token against a length-masked dense cache) over it.
- ``paged_decode_attention_pallas`` — flash-style online softmax, grid
  (batch,). The pools stay in HBM; the block table and the lengths ride
  scalar prefetch, and each slot loops over ITS OWN live chunks of
  ``CHUNK_ROWS`` rows of pages: the pages' copies (one contiguous
  ``[bs*Hkv, D]`` tile each) start together into one contiguous
  ``[pages*bs*Hkv, D]`` VMEM tile, double-buffered across chunks and
  across slots (a slot's last chunk starts the next slot's first). A
  block past ``ceil(length/bs)`` is never copied, there is no grid step
  per dead block, and no dense view of the pool ever materializes.
- GQA stays grouped: the pool keeps Hkv heads and nothing is repeated,
  in HBM or in VMEM. A page is read as the 2-D tile ``[bs*Hkv, D]`` it
  already is in HBM (rows are (token, kv head) pairs), one 2-D dot
  scores all H query heads against a chunk's rows, an additive bias
  drops the rows of the other KV heads, and one more dot weighs V.
- Heads narrower than the 128 lanes (D 64: llama3_1b, LFM2) are read
  ``128 // D`` KV heads to a row: q sits in its own head's lanes of a
  zero row, so the same dot scores it against that head alone, and the
  wrapper takes each q head's own lanes of the output. Such a pool LIES
  in those rows, ``[NB, bs, Hkv/pack, pack*D]`` (``packed_row``: what a
  model's ``kv_row_shapes`` gives its cache; ``pack_rows`` /
  ``unpack_rows`` are the two views of one position's K or V, a reshape
  of its ``Hkv*D`` numbers either way), so the kernel's page view is as
  free as at 128 lanes. (Laid ``[.., Hkv, 64]`` the rows sit padded to
  the lanes in HBM, twice their bytes, and the packed view is a copy of
  a layer's whole window a layer a step: PERF.md, PR 25 and PR 61.)

Shapes: q [B, H, D]; k_pool/v_pool [NB, bs, Hkv/pack, pack*D]
(``packed_row``: [NB, bs, Hkv, D] wherever ``pack`` is 1; the kernel
refuses narrow heads laid otherwise, ``pool_heads``; the XLA reference
views any rows back as heads);
block_tables [B, MAXB] int32 (physical ids; entries past a slot's
length are ignored); lengths [B] int32. The pools may be a STACK of
windows of NB blocks (every layer's, ``[L*NB, bs, Hkv, D]``): the tables
then count from ``first_block``.

A SLIDING-WINDOW layer hands ``starts`` [B] int32 besides, each slot's
first visible position: the query sees positions ``[start, length)``.
The kernel begins its walk at the page that holds ``start``, masks that
page's rows behind it, and reads nothing older (the table's entries
before that page may point anywhere: the engine has freed their blocks);
the reference masks the same rows of its gather. Without ``starts``
both are the programs they were.

An EVA layer (``ops/eva.py``) attends TWO page lists under one softmax,
the exact rows of its window and the summary rows of everything before
it: each list is one call with ``stats=True``, which hands back the
softmax's output in float32 with its running max and sum (the kernel
keeps both anyway), and ``ops.eva.merge_softmax_parts`` joins the parts.
Without ``stats`` both sides are the programs they were.
"""

from __future__ import annotations

import functools
import logging
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu._private.platform import on_chip, pallas_interpret
from ray_tpu.ops.attention import NEG_INF, _repeat_kv

logger = logging.getLogger(__name__)


def ragged_decode_attention_reference(q, k, v, lengths, *, starts=None,
                                      scale: Optional[float] = None,
                                      stats: bool = False):
    """One query token against a dense, length-bounded cache, masked
    past each row's length (and, with ``starts`` [B], before each row's
    first visible position): q [B, H, D] x k/v [B, S, Hkv, D], lengths
    [B] -> [B, H, D]. The arithmetic of the paged reference below, and
    the tests' oracle. ``stats``: ``(o, m, l)`` instead, float32, the
    softmax's running max and sum [B, H] beside its output (``NEG_INF``
    and 0 where a row sees nothing), for ``ops.eva.merge_softmax_parts``."""
    head_dim = q.shape[-1]
    scale = scale if scale is not None else head_dim ** -0.5
    k = _repeat_kv(k, q.shape[1])
    v = _repeat_kv(v, q.shape[1])
    s = jnp.einsum("bhd,bshd->bhs", q, k,
                   preferred_element_type=jnp.float32) * scale
    mask = jnp.arange(k.shape[1])[None, :] < lengths[:, None]   # [B,S]
    if starts is not None:
        mask &= jnp.arange(k.shape[1])[None, :] >= starts[:, None]
    s = jnp.where(mask[:, None, :], s, NEG_INF)
    if stats:
        m = jnp.max(s, axis=-1)
        p = jnp.where(mask[:, None, :], jnp.exp(s - m[..., None]), 0.0)
        l = jnp.sum(p, axis=-1)
        o = jnp.einsum("bhs,bshd->bhd", p.astype(v.dtype), v,
                       preferred_element_type=jnp.float32)
        return o / jnp.maximum(l, 1e-30)[..., None], m, l
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhs,bshd->bhd", p.astype(v.dtype), v)


def paged_decode_attention_reference(q, k_pool, v_pool, block_tables,
                                     lengths, *, starts=None,
                                     scale: Optional[float] = None,
                                     stats: bool = False):
    """XLA fallback: gather the slot's blocks into a dense view, then
    run the masked ragged reference. One extra HBM round-trip of the
    active context vs the Pallas path — correct everywhere, slower.
    The rows are viewed back as heads, after the gather."""
    B, maxb = block_tables.shape
    bs = k_pool.shape[1]
    k = k_pool[block_tables]                     # [B, MAXB, bs, Hkv, D]
    v = v_pool[block_tables]
    k = unpack_rows(k.reshape(B, maxb * bs, *k.shape[3:]), q.shape[-1])
    v = unpack_rows(v.reshape(B, maxb * bs, *v.shape[3:]), q.shape[-1])
    return ragged_decode_attention_reference(q, k, v, lengths, starts=starts,
                                             scale=scale, stats=stats)


def _paged_kernel(*refs, block_size: int, pages: int, max_blocks: int,
                  scale: float, row_heads: int, windowed: bool,
                  stats: bool = False):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    # ``windowed``: a third prefetched scalar array, each slot's first
    # visible position; without it the kernel is the one it was
    if windowed:
        lens_ref, tables_ref, starts_ref, *refs = refs
    else:
        lens_ref, tables_ref, *refs = refs
    # ``stats``: two more outputs, the softmax's running max and sum
    if stats:
        (q_ref, bias_ref, k_hbm, v_hbm, o_ref, m_out_ref, l_out_ref,
         *refs) = refs
    else:
        q_ref, bias_ref, k_hbm, v_hbm, o_ref, *refs = refs
    k_buf, v_buf, sems, first_buf_ref, m_ref, l_ref, acc_ref = refs

    b = pl.program_id(0)
    # a page: rows (token, kv head), or (token, group of packed kv heads)
    page_rows = block_size * row_heads
    chunk_len = pages * block_size
    length = lens_ref[b]

    def first_page(slot):
        """The page that holds the slot's first visible position: where
        its walk begins."""
        return jnp.minimum(starts_ref[slot] // block_size, max_blocks - 1)

    def live_pages(slot):
        n = (lens_ref[slot] + block_size - 1) // block_size
        if windowed:
            first = first_page(slot)
            return jnp.clip(n - first, 1, max_blocks - first)
        return jnp.clip(n, 1, max_blocks)

    def chunk_copies(slot, chunk, buf, act: str):
        """``act`` ("start" or "wait") on the page copies of one chunk of
        one slot: page ``j`` of the slot's table lands at rows
        ``[i*bs*Hkv, (i+1)*bs*Hkv)`` of buffer ``buf``, so the chunk is
        one contiguous ``[pages*bs*Hkv, D]`` tile. Dead pages are not
        copied at all."""
        n_live = live_pages(slot)
        for i in range(pages):
            j = chunk * pages + i

            @pl.when(j < n_live)
            def _():
                page = tables_ref[slot,
                                  first_page(slot) + j if windowed else j]
                rows = pl.ds(i * page_rows, page_rows)
                for n, (hbm, vmem) in enumerate(((k_hbm, k_buf),
                                                 (v_hbm, v_buf))):
                    getattr(pltpu.make_async_copy(
                        hbm.at[page], vmem.at[buf, rows],
                        sems.at[n, buf]), act)()

    @pl.when(b == 0)
    def _first():
        first_buf_ref[0] = 0
        # rows no copy ever fills meet p == 0 in the PV dot; what VMEM
        # held before the call must not be a NaN there
        v_buf[...] = jnp.zeros_like(v_buf)
        chunk_copies(0, 0, 0, "start")

    first_buf = first_buf_ref[0]
    n_chunks = (live_pages(b) + pages - 1) // pages
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def chunk_body(c, carry):
        buf = (first_buf + c) % 2
        # the next chunk's pages (this slot's, or the next slot's first)
        # fly while this one is computed
        @pl.when(c + 1 < n_chunks)
        def _():
            chunk_copies(b, c + 1, 1 - buf, "start")

        @pl.when(jnp.logical_and(c + 1 == n_chunks,
                                 b + 1 < pl.num_programs(0)))
        def _():
            chunk_copies(b + 1, 0, 1 - buf, "start")

        chunk_copies(b, c, buf, "wait")
        # Rows of the chunk are (token, kv head) pairs, the pool's own
        # order, so ONE 2-D dot scores every q head against every row
        # and ``bias`` (0 where the row's kv head is the q head's own,
        # NEG_INF elsewhere) drops the other heads' rows: GQA with no
        # repeat, no per-head loop and no relayout of a page. Operands
        # stay in the pool dtype (bf16 on the chip), accumulation is f32.
        k = k_buf[buf]                                     # [T*Hkv, D]
        v = v_buf[buf]
        s = jax.lax.dot_general(
            q_ref[0], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)            # [H, T*Hkv]
        s = s * scale + bias_ref[...]
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        if windowed:
            # position of the chunk's first row; rows before the first
            # visible position (the walk's first page only) drop too
            at = first_page(b) * block_size + c * chunk_len
            seen = jnp.logical_and(row >= (starts_ref[b] - at) * row_heads,
                                   row < (length - at) * row_heads)
            s = jnp.where(seen, s, NEG_INF)
        else:
            s = jnp.where(row < (length - c * chunk_len) * row_heads, s,
                          NEG_INF)
        m_prev = m_ref[:, :1]                              # [H, 1]
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # [H, D]
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)
        return carry

    jax.lax.fori_loop(0, n_chunks, chunk_body, 0)
    first_buf_ref[0] = (first_buf + n_chunks) % 2

    # length 0: every row was masked, and the output is 0, not their mean
    out = jnp.where(length > 0, acc_ref[...] / l_ref[:, :1], 0.0)
    o_ref[0] = out.astype(o_ref.dtype)
    if stats:
        # a slot that sees nothing: every row was masked, and the masked
        # rows' own max and count are not its statistics
        seen = length > (starts_ref[b] if windowed else 0)
        m_out_ref[0] = jnp.where(seen, m_ref[...], NEG_INF)
        l_out_ref[0] = jnp.where(seen, l_ref[...], 0.0)


# Rows of a chunk (a page is bs * Hkv rows): what bounds the kernel's
# VMEM (2 x 2 tiles of CHUNK_ROWS x lanes, and [H, CHUNK_ROWS] f32 of
# bias and scores) at any block_size. On the v5e at 32-token pages of
# 8 KV heads x 128 (kernel alone, 81 MB of live K/V), pages a chunk:
# 2 -> 0.225 ms, 4 -> 0.147, 8 -> 0.127, 16 -> 0.119, but 16 costs a
# one-page slot 0.055 ms for 0.038 (PERF.md, PR 25). 8 of those pages:
CHUNK_ROWS = 2048
LANES = 128
# Bytes a page copy should move. A copy's descriptor costs this chip
# ~17 ns to start and ~3 ns to wait for whatever it moves
# (``tools/dsa_row_copy_bench.py``), a chunk's are issued one after
# another, and HBM moves ~16 KB in that time: under it the descriptors,
# not the bytes, are the kernel's time. The kernel alone on the v5e at
# 32-row blocks copied 1 / 2 / 4 / 8 to a page, ms
# (``tools/paged_run_readings.py``, PERF.md, PR 58): ONE K/V head of 128
# (8 KB a block; 0.38 ms of bytes) 1.19 / 0.71 / 0.47 / 0.46; two heads
# (16 KB; 0.76) 1.42 / 0.94 / 0.90 / 0.90; four (32 KB; 0.80) 0.94 /
# 0.90 / 0.90, and under 128 query rows a slot (0.42) 0.70 / 0.57 /
# 0.56; eight (64 KB; 0.32) 0.375 / 0.381. A copy of 32 KB still gains
# from being 64, one of 64 KB gains nothing more: 64 KB, which is also
# the page the chunk sweep above was tuned at.
RUN_BYTES = 64 * 1024


def _lane_pack(head_dim: int, kv_heads: int) -> int:
    """KV heads ONE row of a pool holds: as many as fill the 128 lanes,
    where the head count divides so; 1 where no number of them fills a
    row (a row is then a head, and the kernel does not lower:
    ``kernel_lowers``)."""
    pack = LANES // head_dim if LANES % head_dim == 0 else 1
    return pack if kv_heads % pack == 0 else 1


def packed_row(kv_heads: int, head_dim: int) -> Tuple[int, int]:
    """One position's K (or V) as a pool holds it: ``(Hkv/pack,
    pack*D)``, which is ``(Hkv, D)`` for every width that packs 1."""
    pack = _lane_pack(head_dim, kv_heads)
    return kv_heads // pack, pack * head_dim


def pack_rows(x):
    """Heads x [..., Hkv, D] as the rows a pool holds, [..., Hkv/pack,
    pack*D]: the same numbers in the same order."""
    return x.reshape(x.shape[:-2] + packed_row(*x.shape[-2:]))


def unpack_rows(x, head_dim: int):
    """A pool's rows [..., Hkv/pack, pack*D] viewed back as heads [...,
    Hkv, D] (rows that pack 1 are that already)."""
    return x.reshape(x.shape[:-2] + (-1, head_dim))


def pool_heads(pool, head_dim: int) -> int:
    """The K/V heads of a pool ``[.., rows, lanes]`` laid as ``packed_row``
    lays it. THE KERNEL READS THAT ONE LAYOUT: narrow heads handed over as
    ``[.., Hkv, D]`` sit padded to the lanes in HBM and would have to be
    copied into rows, the whole stack a layer a step, so they are refused
    (``pack_rows`` the pool once, where it is built)."""
    rows, lanes = pool.shape[-2:]
    kv_heads = rows * lanes // head_dim
    if lanes % head_dim or (rows, lanes) != packed_row(kv_heads, head_dim):
        raise ValueError(
            f"a pool of {kv_heads} K/V heads of {head_dim} lies in rows of "
            f"{packed_row(kv_heads, head_dim)}, got {(rows, lanes)}: lay it "
            f"by ops.paged_attention.pack_rows / packed_row")
    return kv_heads


def run_blocks(block_size: int, kv_heads: int, head_dim: int,
               itemsize: int) -> int:
    """Blocks of a pool the kernel should copy as ONE page, a RUN: the
    smallest power of two whose K (or V) rows reach ``RUN_BYTES``, as far
    as one chunk holds it. 1 where a block alone is that large already
    (8 K/V heads of 128 in bf16 at 32 rows). A run is a page only where
    the allocator lays a slot's blocks in aligned, contiguous runs
    (``llm/paged_cache.py``, ``BlockPool(run=...)``)."""
    row_heads = kv_heads // _lane_pack(head_dim, kv_heads)
    run = 1
    while (run * block_size * kv_heads * head_dim * itemsize < RUN_BYTES
           and 2 * run * block_size * row_heads <= CHUNK_ROWS):
        run *= 2
    return run


def kernel_lowers(head_dim: int, kv_heads: int) -> bool:
    """Mosaic copies a page as the 2-D tile it is in HBM, and a tile's
    lanes are 128 wide: the (packed) row has to fill them."""
    return _lane_pack(head_dim, kv_heads) * head_dim % LANES == 0


@functools.partial(jax.jit, static_argnames=("scale", "interpret", "stats"))
def paged_decode_attention_pallas(q, k_pool, v_pool, block_tables,
                                  lengths, starts=None, *, first_block=0,
                                  scale: Optional[float] = None,
                                  interpret: bool = False,
                                  stats: bool = False):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, D = q.shape
    NB, bs, row_heads, lanes = k_pool.shape
    Hkv = pool_heads(k_pool, D)
    pack = lanes // D                            # KV heads read as one row
    # a page's view below is free: the kernel reads the window's pages
    # out of the pools where they lie
    block_tables = block_tables.astype(jnp.int32) + first_block
    maxb = block_tables.shape[1]
    if not (interpret or kernel_lowers(D, Hkv)):
        raise ValueError(
            f"the paged decode kernel reads rows of 128 lanes on the chip: "
            f"head_dim {D} x {Hkv} KV heads does not fill them; use the "
            f"XLA reference (impl='xla')")
    scale = scale if scale is not None else D ** -0.5
    kv_head = jnp.arange(H) // (H // Hkv)        # of each q head
    if pack > 1:
        # q head h in the lanes of its KV head's place in the row, zeros
        # in the other heads' lanes: the row's dot is q . k of that head
        place = jax.nn.one_hot(kv_head % pack, pack, dtype=q.dtype)
        q = (q[:, :, None, :] * place[None, :, :, None]).reshape(B, H, lanes)
    page_rows = bs * row_heads
    pages = max(1, min(maxb, CHUNK_ROWS // page_rows))
    chunk_rows = pages * page_rows
    lengths = lengths.astype(jnp.int32)
    windowed = starts is not None
    scalars = ((lengths, block_tables, starts.astype(jnp.int32)) if windowed
               else (lengths, block_tables))

    # q head h attends row r of a chunk iff r's KV heads hold its own
    own = (kv_head[:, None] // pack
           == jnp.arange(chunk_rows)[None, :] % row_heads)
    bias = jnp.where(own, 0.0, NEG_INF).astype(jnp.float32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, H, lanes), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec((H, chunk_rows), lambda b, *_: (0, 0)),
            # the pools stay in HBM; the kernel copies the live pages
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=(
            [pl.BlockSpec((1, H, lanes), lambda b, *_: (b, 0, 0))]
            + [pl.BlockSpec((1, H, 128), lambda b, *_: (b, 0, 0))] * 2
            if stats else pl.BlockSpec((1, H, lanes), lambda b, *_: (b, 0, 0))),
        scratch_shapes=[
            pltpu.VMEM((2, chunk_rows, lanes), k_pool.dtype),
            pltpu.VMEM((2, chunk_rows, lanes), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((H, 128), jnp.float32),
            pltpu.VMEM((H, 128), jnp.float32),
            pltpu.VMEM((H, lanes), jnp.float32),
        ],
    )
    # A pool block as one 2-D [bs*rows, lanes] tile, rows (token, kv
    # head or group of packed kv heads): at lanes % 128 == 0 the pool's
    # own order in HBM under XLA's tiling of its two minor dims, so this
    # view is free. ([bs, Hkv*D] is NOT: XLA copies the whole pool to
    # build it, 0.6 ms a layer at 201 MB; PERF.md, PR 25.)
    out = pl.pallas_call(
        functools.partial(_paged_kernel, block_size=bs, pages=pages,
                          max_blocks=maxb, scale=scale, row_heads=row_heads,
                          windowed=windowed, stats=stats),
        grid_spec=grid_spec,
        # with its statistics the output stays float32: it is a PART of
        # a softmax, weighed again by the caller
        out_shape=(
            [jax.ShapeDtypeStruct((B, H, lanes), jnp.float32)]
            + [jax.ShapeDtypeStruct((B, H, 128), jnp.float32)] * 2
            if stats else jax.ShapeDtypeStruct((B, H, lanes), q.dtype)),
        # slots run in order: each starts the next one's first copies
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*scalars, q, bias,
      k_pool.reshape(NB, page_rows, lanes),
      v_pool.reshape(NB, page_rows, lanes))
    if stats:
        out, m, l = out
    if pack > 1:     # each q head's own lanes of its packed row
        out = out.reshape(B, H, pack, D)[:, jnp.arange(H), kv_head % pack]
    return (out, m[..., 0], l[..., 0]) if stats else out


def default_impl(head_dim: int, kv_heads: int) -> str:
    """The platform's choice where nobody forces one: the Mosaic kernel
    on a TPU backend at widths it lowers for, the XLA reference (the
    gather over every slot's whole table) elsewhere."""
    if not on_chip():
        return "xla"
    if kernel_lowers(head_dim, kv_heads):
        return "pallas"
    logger.warning(
        "paged decode attention falls back to the XLA gather on the chip: "
        "head_dim %d x %d KV heads does not fill the kernel's 128 lanes",
        head_dim, kv_heads)
    return "xla"


def paged_decode_attention(q, k_pool, v_pool, block_tables, lengths, *,
                           impl: str, starts=None, first_block=0,
                           num_blocks: Optional[int] = None,
                           scale: Optional[float] = None,
                           stats: bool = False, run: int = 1):
    """One algorithm, two implementations: ``impl`` is "pallas" (the
    kernel, interpreted where the backend is the CPU) or "xla" (its
    oracle).

    ``first_block`` (it may be traced) and ``num_blocks`` name a
    WINDOW of the pools that the tables count from: blocks
    ``[first_block, first_block + num_blocks)``, one layer's of the
    stack ``[L*NB, bs, Hkv, D]`` that ``decode_step_paged`` carries.
    Both sides read it through ``first_block + block_tables`` with no
    slice of the stack built, at every head width: narrow heads lie
    packed in the pool (the module's docstring). ``starts`` [B]: a
    sliding-window layer's first visible positions (the module's
    docstring). ``stats``: ``(o, m, l)`` in float32 instead of ``o``,
    the softmax's output with its running max and sum [B, H]: ONE PART
    of a softmax over several page lists, which the caller joins
    (``ops.eva.merge_softmax_parts``: an EVA layer's exact pages and its
    summary pages). Without it both sides are the programs they were.

    ``run`` (static) > 1: every slot's blocks lie in aligned, contiguous
    RUNS of that many (``table[r*run + k] == table[r*run] + k``, the
    first a multiple of ``run``, as ``BlockPool(run=...)`` lays them; a
    window's first and number of blocks multiples too), and a run is the
    page: the pools are viewed as ``[NB/run, run*bs, Hkv, D]`` (the same
    bytes) under the table of runs, so a narrow pool's copies are
    ``run`` times as large and as few (``run_blocks``). The rows, their
    order and the chunks are those of ``run`` 1; the rows of a run past
    a slot's length are masked as a page's tail is."""
    if run > 1:
        if block_tables.shape[1] % run or k_pool.shape[0] % run or (
                num_blocks is not None and num_blocks % run):
            raise ValueError(
                f"runs of {run} blocks: the table's width "
                f"{block_tables.shape[1]}, the pools' {k_pool.shape[0]} "
                f"blocks and a window's {num_blocks} must be multiples")
        k_pool, v_pool = (pool.reshape(
            (pool.shape[0] // run, run * pool.shape[1]) + pool.shape[2:])
            for pool in (k_pool, v_pool))
        block_tables = block_tables[:, ::run] // run
        first_block = first_block // run
        num_blocks = None if num_blocks is None else num_blocks // run
    if impl == "pallas":
        return paged_decode_attention_pallas(
            q, k_pool, v_pool, block_tables, lengths, starts,
            first_block=first_block, scale=scale,
            interpret=pallas_interpret(), stats=stats)
    if impl != "xla":
        raise ValueError(f"impl must be 'xla' or 'pallas', got {impl!r}")
    return paged_decode_attention_reference(
        q, k_pool, v_pool, first_block + block_tables, lengths,
        starts=starts, scale=scale, stats=stats)
