"""Absorbed latent-attention (MLA) decode: one query token a slot against
a paged LATENT cache.

A latent-attention layer caches, a token, one row ``c`` of ``kv_lora_rank``
lanes (the normed down-projection that every head's keys AND values are
up-projected from) and one rotary key part ``k_pe`` shared by all heads.
A decode step never up-projects the cache: with ``W_uk`` folded into the
query (``q_lat[h] = q_nope[h] W_uk[h]^T``) the scores are

    s[h, t] = (q_lat[h] . c[t] + q_pe[h] . k_pe[t]) * scale

and the output ``o_lat[h] = sum_t p[h, t] c[t]`` is up-projected by
``W_uv`` afterwards (``models/mla.py``). So the cache is ONE K/V "head"
whose row is also its value, read by all H query heads: every live row
is read once for both.

Shapes: q_lat [B, H, R]; q_pe [B, H, P]; pool [NB, bs, R + P]; block_tables
[B, MAXB]; lengths [B]. The pool may be a stack of windows (every
layer's): the tables then count from ``first_block``.

THE CACHE ROW is ``c | k_pe``, ONE row of ONE pool (since PR 45; two pools,
``c`` under ``"k"`` and ``k_pe`` under ``"v"``, before): ``c`` in lanes
``[0, R)`` and ``k_pe`` zero-padded to the ``PE_LANES`` lanes of a tile
after it, so a page is the 2-D tile ``[bs, R + P]`` it is in HBM and the
kernel fetches it with ONE DMA descriptor. The pad costs a ninth of a
row's bytes, 1,280 for 1,152, where a head axis of 1 before the width
would pad every row to a sublane tile, 8-16 x. Why one descriptor a page:
a descriptor costs the kernel's instruction stream ~17 ns to START and ~3
to wait for, whatever it moves (``tools/dsa_row_copy_bench.py``, PR 44),
and the kernel issues a chunk's descriptors and then computes the chunk
before it on that ONE stream while the DMA engines move bytes beside it.
With two starts and two waits a page (PR 41) a 64-page chunk was 2.6 us
of descriptors + 1.7 us of dots and softmax against 3.5 us of bytes: bound
by its own instruction stream, 1.362 ms a layer at the cell's shape (32
slots x ~560 pages; 2.4 ns a row where the copies alone take 1.7). With one
start a page and one wait a chunk it is 1.1 + 1.7 us under the same 3.5:
bound by the bytes, 0.987 ms, the pace of a walk that only copies (0.982:
``tools/mla_page_copy_bench.py``, which also reads that the copies ALONE
cost the same either way: without the dots nothing competes for the
stream), the output bit-equal (PERF.md sections 5 and 6, PR 45).

- ``mla_decode_attention_reference``: the XLA twin (gathers every
  slot's whole table and slices the row's two lane ranges; the CPU path
  and the tests' oracle).
- ``mla_decode_attention_pallas``: the Mosaic kernel, ``ops/
  paged_attention.py:_paged_kernel``'s walk (grid over slots, lengths and
  tables as prefetched scalars, each slot's LIVE pages copied chunk by
  chunk into double-buffered VMEM, a slot's last chunk starting the next
  slot's first, online softmax) with the H heads as the rows of three
  dots a chunk: ``q_lat c^T``, ``q_pe k_pe^T``, ``p c``, on the two lane
  ranges of the one buffer. A chunk is waited for ONCE where it is full:
  a DMA semaphore counts bytes, so its live pages are waited for as the
  powers of two their number is the sum of.
- ``default_impl`` is the platform's side of the choice;
  ``MLAModel.paged_decode_impl`` is the one place that makes it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ray_tpu.ops import paged_attention
from ray_tpu.ops.attention import NEG_INF

# lanes of a pool row's rotary part: k_pe (64 in the published models)
# zero-padded to a whole lane tile
PE_LANES = 128
# rows of a chunk (a page is ``bs`` rows): bounds the kernel's VMEM (2 x
# CHUNK_ROWS x (R + PE_LANES) of pages, 5 MB at 2,048, and [H, CHUNK_ROWS]
# float32 of scores). On the v5e at the cell's shape (32 slots x ~17.9k
# live rows of 32-row pages, one layer, kernel alone, 100 calls queued):
# 1,024 rows a chunk 1.010 ms, 2,048 0.987, 4,096 1.034 (PERF.md, PR 45;
# with PR 41's two copies a page 1.491 / 1.362 / 1.456 in the same call)
CHUNK_ROWS = 2048


def mla_decode_attention_reference(q_lat, q_pe, pool, block_tables, lengths,
                                   *, scale: float):
    """The XLA twin: gather each slot's blocks into a dense view, mask
    past its length. -> o_lat [B, H, R] in ``q_lat``'s dtype."""
    B, maxb = block_tables.shape
    bs, R = pool.shape[1], q_lat.shape[-1]
    rows = pool[block_tables].reshape(B, maxb * bs, pool.shape[-1])
    c, pe = rows[..., :R], rows[..., R:]               # [B, S, R], [B, S, P]
    s = (jnp.einsum("bhr,bsr->bhs", q_lat, c,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bhp,bsp->bhs", q_pe, pe,
                      preferred_element_type=jnp.float32)) * scale
    mask = jnp.arange(maxb * bs)[None, :] < lengths[:, None]    # [B, S]
    p = jax.nn.softmax(jnp.where(mask[:, None, :], s, NEG_INF), axis=-1)
    return jnp.einsum("bhs,bsr->bhr", p.astype(c.dtype), c,
                      preferred_element_type=jnp.float32).astype(q_lat.dtype)


def _mla_kernel(lens_ref, tables_ref, q_lat_ref, q_pe_ref, pool_hbm, o_ref,
                buf, sems, first_buf_ref, m_ref, l_ref, acc_ref, *,
                block_size: int, pages: int, max_blocks: int, scale: float):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)
    R = q_lat_ref.shape[-1]
    chunk_len = pages * block_size
    length = lens_ref[b]

    def live_pages(slot):
        return jnp.clip((lens_ref[slot] + block_size - 1) // block_size, 1,
                        max_blocks)

    def start_chunk(slot, chunk, at):
        """One copy a LIVE page of one chunk of one slot: page ``j`` of
        the slot's table lands whole, ``c | k_pe``, at rows ``[i*bs,
        (i+1)*bs)`` of buffer ``at``. Dead pages are not copied at all."""
        n_live = live_pages(slot)
        for i in range(pages):
            j = chunk * pages + i

            @pl.when(j < n_live)
            def _():
                pltpu.make_async_copy(
                    pool_hbm.at[tables_ref[slot, j]],
                    buf.at[at, pl.ds(i * block_size, block_size)],
                    sems.at[at]).start()

    def wait_chunk(slot, chunk, at):
        """A DMA semaphore counts BYTES: the chunk's ``n`` live pages are
        waited for as the powers of two that ``n`` is the sum of, each
        ONE wait on a descriptor of that many pages' size, so a full
        chunk is one wait and no chunk more than ``log2(pages) + 1``."""
        n = jnp.clip(live_pages(slot) - chunk * pages, 0, pages)
        part = 1 << (pages.bit_length() - 1)
        while part:
            @pl.when((n & part) != 0)
            def _(part=part):
                landed = buf.at[at, pl.ds(0, part * block_size)]
                pltpu.make_async_copy(landed, landed, sems.at[at]).wait()
            part //= 2

    @pl.when(b == 0)
    def _first():
        first_buf_ref[0] = 0
        # rows no copy ever fills meet p == 0 in the value dot; what VMEM
        # held before the call must not be a NaN there
        buf[...] = jnp.zeros_like(buf)
        start_chunk(0, 0, 0)

    first_buf = first_buf_ref[0]
    n_chunks = (live_pages(b) + pages - 1) // pages
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def chunk_body(c, carry):
        at = (first_buf + c) % 2
        # the next chunk's pages (this slot's, or the next slot's first)
        # fly while this one is computed
        @pl.when(c + 1 < n_chunks)
        def _():
            start_chunk(b, c + 1, 1 - at)

        @pl.when(jnp.logical_and(c + 1 == n_chunks,
                                 b + 1 < pl.num_programs(0)))
        def _():
            start_chunk(b + 1, 0, 1 - at)

        wait_chunk(b, c, at)
        # the H heads are the rows of every dot; operands stay in the
        # pool dtype (bf16 on the chip), accumulation is float32. The
        # row's two parts are lane ranges of the one buffer (whole lane
        # tiles on the chip)
        rows = buf[at, :, :R]                              # [T, R]
        contract_lanes = (((1,), (1,)), ((), ()))
        s = jax.lax.dot_general(q_lat_ref[0], rows, contract_lanes,
                                preferred_element_type=jnp.float32)
        s = (s + jax.lax.dot_general(
            q_pe_ref[0], buf[at, :, R:], contract_lanes,
            preferred_element_type=jnp.float32)) * scale   # [H, T]
        at_row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(at_row < length - c * chunk_len, s, NEG_INF)
        m_prev = m_ref[:, :1]                              # [H, 1]
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        # the row is its own value: the latent part again
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(rows.dtype), rows, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # [H, R]
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)
        return carry

    jax.lax.fori_loop(0, n_chunks, chunk_body, 0)
    first_buf_ref[0] = (first_buf + n_chunks) % 2
    # length 0: every row was masked, and the output is 0, not their mean
    out = jnp.where(length > 0, acc_ref[...] / l_ref[:, :1], 0.0)
    o_ref[0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def mla_decode_attention_pallas(q_lat, q_pe, pool, block_tables, lengths, *,
                                first_block=0, scale: float,
                                interpret: bool = False):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, R = q_lat.shape
    P = q_pe.shape[-1]
    bs = pool.shape[1]
    if pool.shape[-1] != R + P:
        raise ValueError(
            f"a pool row is c | k_pe, {R} + {P} lanes, got {pool.shape[-1]}")
    if not interpret and (R % 128 or P % 128):
        raise ValueError(
            f"the latent decode kernel copies pages as the 2-D tiles they "
            f"are on the chip: the latent ({R}) and rotary ({P}) parts have "
            f"to fill lanes of 128; use the XLA twin")
    # the kernel reads the window's pages out of the pool where they lie
    block_tables = block_tables.astype(jnp.int32) + first_block
    maxb = block_tables.shape[1]
    pages = max(1, min(maxb, CHUNK_ROWS // bs))
    chunk_rows = pages * bs

    def per_slot(width):
        return pl.BlockSpec((1, H, width), lambda b, *_: (b, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[per_slot(R), per_slot(P),
                  # the pool stays in HBM; the kernel copies the live pages
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=per_slot(R),
        scratch_shapes=[
            pltpu.VMEM((2, chunk_rows, R + P), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((H, 128), jnp.float32),
            pltpu.VMEM((H, 128), jnp.float32),
            pltpu.VMEM((H, R), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_mla_kernel, block_size=bs, pages=pages,
                          max_blocks=maxb, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, R), q_lat.dtype),
        # slots run in order: each starts the next one's first copies
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(lengths.astype(jnp.int32), block_tables, q_lat, q_pe, pool)


def default_impl() -> str:
    """The platform's choice where nobody forces one, which is
    ``ops.paged_attention``'s for a page of one head of whole lane tiles
    (one place knows what the platform is, so the chip-less tools that
    describe a chip to it describe it to this too): the Mosaic kernel on
    a TPU backend, the XLA twin elsewhere."""
    return paged_attention.default_impl(PE_LANES, 1)


def mla_decode_attention(q_lat, q_pe, pool, block_tables, lengths, *,
                         impl: str, scale: float, first_block=0):
    """One algorithm, two implementations: ``impl`` is "pallas" (the
    kernel, interpreted where the backend is the CPU) or "xla" (its
    twin). ``first_block`` (it may be traced): where the window of the
    pool that the tables count from begins, one layer's of the stack
    that ``decode_step_paged`` carries."""
    if impl == "pallas":
        return mla_decode_attention_pallas(
            q_lat, q_pe, pool, block_tables, lengths,
            first_block=first_block, scale=scale,
            interpret=paged_attention.pallas_interpret())
    if impl != "xla":
        raise ValueError(f"impl must be 'xla' or 'pallas', got {impl!r}")
    return mla_decode_attention_reference(
        q_lat, q_pe, pool, first_block + block_tables, lengths, scale=scale)
