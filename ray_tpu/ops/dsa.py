"""Learned sparse attention (DeepSeek's DSA) over a paged LATENT cache.

A layer with a LIGHTNING INDEXER caches, a token, one index key ``k_I``
(``index_head_dim`` lanes) beside the latent row ``c`` and the rotary key
part ``k_pe`` (``ops/mla_attention.py``). A query scores EVERY cached row
with the indexer,

    I[t, s] = sum_j w[t, j] * relu(q_I[t, j] . k_I[s]),

keeps the ``index_topk`` rows of largest ``I`` (all of them while there
are no more), and runs the absorbed latent attention over THOSE rows
alone. Three pieces, each one algorithm with an XLA form that runs
anywhere; ``models/mla.py`` resolves which runs (``paged_decode_impl``):

- THE ROW ON THE CHIP is 32-bit WORDS, two bf16 numbers a word
  (``pack_words``: element ``j`` of a part in the low half of word ``j``,
  element ``j + n/2`` in the high half), in sub-rows of 128 words, ALL
  UNDER ``"k"`` [..., 4, 128]: sub-row 0 holds ``k_pe`` with its padding
  (64 words: ``k_pe`` is their low halves) and ``k_I`` (64 words),
  sub-rows 1 and 2 hold ``c``, sub-row 3 is spare; ``"v"`` is a
  zero-width row. Why words: a kernel that reads ROWS BY NUMBER copies
  one row a DMA, and Mosaic slices an HBM array one row at a time only
  where its rows are contiguous, which on the chip's tiled layouts is
  32-bit rows of exactly 128 lanes, or a run of such sub-rows seen as
  ``[tokens, sub, 1, 128]`` ("Slice shape along dimension 0 must be
  aligned to tiling (8)" for [N, 512] bf16 and for [N, 256] uint32
  alike); XLA's own row gather took 59 ms a layer at the cell's shape,
  1.8 us a row (PERF.md, PR 43). Why ONE array (PR 44): a copy costs ~17
  ns to START whatever its bytes (512 B and 2,048 B alike; a wait ~3 ns;
  ``tools/dsa_row_copy_bench.py``), so what the attention reads of a
  token is one contiguous run and one copy; PR 43 held ``c`` under "k"
  and ``k_pe | k_I`` under "v", two copies and two waits a row. Why the
  spare sub-row, 2,048 B a token for 1,536: XLA lays ``[..., n, 128]``
  uint32 out token by token, in tiles of (n, 128), only where ``n`` is a
  power of two (``word_row_subrows``); three sub-rows it holds
  sub-row-major as a parameter and pads to four for the decode step's
  scatter, with two copies of the whole pool a step (so do ``[..., 3, 1,
  128]`` and ``[..., 1, 384]``: PERF.md, PR 44). A kernel unpacks a word
  with a shift and a mask and never shuffles lanes: the QUERY is laid
  out to match (zeros where a word holds another part). Off the chip's
  dtype and widths the row is the plain ``c`` and ``k_pe | k_I`` and the
  XLA forms run.
- ``indexer_scores`` (decode): ``I[slot, 0..len)`` through the block
  table: the Mosaic kernel walks a slot's LIVE pages as
  ``ops/mla_attention.py``'s walks its pages, copying of every token of
  a page the keys' sub-row alone, ONE strided copy a page (32 runs of
  512 B, 2,048 B apart: as fast as one run of 16 KB, same tool); the XLA
  twin gathers every table entry.
- the selection. ``topk_mask`` (prefill: a mask over rows for a block of
  queries, from the exact k-th largest score found by a radix search over
  the float's bits, 32 counting passes and no sort) and ``select_topk``
  (decode: row indices, ``jax.lax.top_k``). TIES go to the EARLIER
  position in both, so the two agree row for row.
- ``sparse_decode_attention``: the absorbed attention over the selected
  rows ALONE. The Mosaic kernel copies each selected row out of the pool
  by its number, ONE DMA a row (the keys' sub-row and ``c``'s two), into
  VMEM, waits ONCE for the whole buffer (a DMA semaphore counts bytes),
  then scores, softmax and values of all heads in one pass; the XLA twin
  gathers the same rows.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ray_tpu.ops import paged_attention
from ray_tpu.ops.attention import NEG_INF

# rows of a chunk of the indexer kernel's walk (``ops/mla_attention.py``'s
# chunking: bounds VMEM at 2 x CHUNK_ROWS x index_head_dim of keys and
# [index heads, CHUNK_ROWS] float32 of scores)
CHUNK_ROWS = 2048


# -- the row as words -------------------------------------------------------
WORD_LANES = 128        # words of a sub-row: one lane tile of 32-bit lanes
PE_WORDS = 64           # words of the keys' sub-row before k_I's (k_pe, zeros)


def word_row_subrows(latent: int) -> int:
    """Sub-rows of a token's row of words: the keys' one and ``latent /
    256`` of ``c``, rounded up to a power of two (module docstring)."""
    return 1 << (latent // (2 * WORD_LANES)).bit_length()


def pack_words(x):
    """[..., 2n] of a 16-bit dtype -> [..., n] uint32: element ``j`` in the
    low half of word ``j``, element ``j + n`` in the high half."""
    n = x.shape[-1] // 2
    bits = jax.lax.bitcast_convert_type(x, jnp.uint16).astype(jnp.uint32)
    return bits[..., :n] | (bits[..., n:] << 16)


def unpack_words(words, dtype=jnp.bfloat16):
    """``pack_words``' inverse: [..., n] uint32 -> [..., 2n] ``dtype``."""
    halves = jnp.concatenate([words & jnp.uint32(0xFFFF), words >> 16], -1)
    return jax.lax.bitcast_convert_type(halves.astype(jnp.uint16), dtype)


def _planes(words):
    """In a kernel: a tile of words as its two bf16 planes (low halves,
    high halves), each exact: a bf16 is the high half of a float32."""
    low = jax.lax.bitcast_convert_type(words << 16, jnp.float32)
    high = jax.lax.bitcast_convert_type(words & jnp.uint32(0xFFFF0000),
                                        jnp.float32)
    return low.astype(jnp.bfloat16), high.astype(jnp.bfloat16)


def _beside(x, before: int):
    """x [..., n] (n <= 64) as the lanes ``before ... before + n`` of a
    zero lane tile: what multiplies a plane of words that holds other
    parts in its other lanes."""
    pad = [(0, 0)] * (x.ndim - 1) + [(before, WORD_LANES - before
                                      - x.shape[-1])]
    return jnp.pad(x, pad)


# -- the indexer ------------------------------------------------------------
def index_scores(q_idx, w, k_idx):
    """``I`` of queries against DENSE keys: q_idx [..., T, Hi, Di], w
    [..., T, Hi] (float32), k_idx [..., S, Di] -> [..., T, S] float32. The
    products accumulate in float32; ReLU and the weighted sum over the
    index heads stay float32 (a dot would round ``w`` on the TPU)."""
    s = jnp.einsum("...thd,...sd->...hts", q_idx, k_idx,
                   preferred_element_type=jnp.float32)
    return jnp.sum(jax.nn.relu(s) * jnp.swapaxes(w, -1, -2)[..., None],
                   axis=-3)


def indexer_scores_reference(q_idx, w, v_pool, block_tables, lengths, *,
                             key_of):
    """The XLA twin: every table entry's rows gathered dense, their
    index keys taken out by ``key_of`` (the model's: rows [..., row] ->
    k_I [..., Di]); rows at or past a slot's length read ``NEG_INF``.
    q_idx [B, Hi, Di], w [B, Hi] -> [B, MAXB * bs] float32."""
    B, maxb = block_tables.shape
    bs = v_pool.shape[1]
    rows = v_pool[block_tables].reshape(B, maxb * bs, *v_pool.shape[2:])
    scores = index_scores(q_idx[:, None], w[:, None], key_of(rows))[:, 0]
    live = jnp.arange(maxb * bs)[None, :] < lengths[:, None]
    return jnp.where(live, scores, NEG_INF)


def _indexer_kernel(lens_ref, tables_ref, q_ref, w_ref, k_hbm, o_ref, k_buf,
                    sems, first_buf_ref, *, block_size: int, pages: int,
                    max_blocks: int):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)
    chunk_len = pages * block_size
    length = lens_ref[b]

    def live_pages(slot):
        return jnp.clip((lens_ref[slot] + block_size - 1) // block_size, 1,
                        max_blocks)

    def chunk_copies(slot, chunk, buf, act: str):
        """``act`` ("start" or "wait") on one chunk's page copies: of
        every token of a page its FIRST sub-row, the keys', as ONE
        strided copy (a page's 32 runs of 512 B, a row's bytes apart)."""
        n_live = live_pages(slot)
        for i in range(pages):
            j = chunk * pages + i

            @pl.when(j < n_live)
            def _():
                getattr(pltpu.make_async_copy(
                    k_hbm.at[tables_ref[slot, j], :, 0, 0],
                    k_buf.at[buf, pl.ds(i * block_size, block_size)],
                    sems.at[buf]), act)()

    @pl.when(b == 0)
    def _first():
        first_buf_ref[0] = 0
        chunk_copies(0, 0, 0, "start")

    first_buf = first_buf_ref[0]
    n_chunks = (live_pages(b) + pages - 1) // pages
    # chunks past the slot's live pages are never visited
    o_ref[...] = jnp.full_like(o_ref, NEG_INF)

    def chunk_body(c, carry):
        buf = (first_buf + c) % 2

        @pl.when(c + 1 < n_chunks)
        def _():
            chunk_copies(b, c + 1, 1 - buf, "start")

        @pl.when(jnp.logical_and(c + 1 == n_chunks,
                                 b + 1 < pl.num_programs(0)))
        def _():
            chunk_copies(b + 1, 0, 1 - buf, "start")

        chunk_copies(b, c, buf, "wait")
        # the query's two parts are zero where a word holds k_pe
        contract_lanes = (((1,), (1,)), ((), ()))
        low, high = _planes(k_buf[buf])                     # [T, 128] each
        s = (jax.lax.dot_general(q_ref[0, 0], low, contract_lanes,
                                 preferred_element_type=jnp.float32)
             + jax.lax.dot_general(q_ref[0, 1], high, contract_lanes,
                                   preferred_element_type=jnp.float32))
        score = jnp.sum(jax.nn.relu(s) * w_ref[0], axis=0, keepdims=True)
        at = jax.lax.broadcasted_iota(jnp.int32, score.shape, 1)
        # what a dead page's rows of the buffer hold is never read out
        o_ref[0, pl.ds(c, 1), :] = jnp.where(
            at < length - c * chunk_len, score, NEG_INF)
        return carry

    jax.lax.fori_loop(0, n_chunks, chunk_body, 0)
    first_buf_ref[0] = (first_buf + n_chunks) % 2


@functools.partial(jax.jit, static_argnames=("interpret",))
def indexer_scores_pallas(q_idx, w, k_pool, block_tables, lengths, *,
                          first_block=0, interpret: bool = False):
    """``k_pool`` [NB, bs, sub-rows, 128] uint32, the rows as words."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, Hi, Di = q_idx.shape
    bs = k_pool.shape[1]
    half = Di // 2
    if (k_pool.dtype != jnp.uint32 or k_pool.ndim != 4
            or k_pool.shape[3] != WORD_LANES
            or PE_WORDS + half != WORD_LANES):
        raise ValueError(
            f"the indexer kernel reads rows of sub-rows of {WORD_LANES} "
            f"words, the last {WORD_LANES - PE_WORDS} words of the first an "
            f"index key of {2 * (WORD_LANES - PE_WORDS)} numbers, got "
            f"{k_pool.dtype}{k_pool.shape[2:]} and keys of {Di}; use the "
            f"XLA twin")
    block_tables = block_tables.astype(jnp.int32) + first_block
    maxb = block_tables.shape[1]
    pages = max(1, min(maxb, CHUNK_ROWS // bs))
    chunk_rows = pages * bs
    n_chunks = -(-maxb // pages)
    q_parts = jnp.stack([_beside(q_idx[..., :half], PE_WORDS),
                         _beside(q_idx[..., half:], PE_WORDS)], axis=1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[pl.BlockSpec((1, 2, Hi, WORD_LANES),
                               lambda b, *_: (b, 0, 0, 0)),
                  # the weights as a column: they multiply [Hi, rows]
                  pl.BlockSpec((1, Hi, 1), lambda b, *_: (b, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, n_chunks, chunk_rows),
                               lambda b, *_: (b, 0, 0)),
        scratch_shapes=[pltpu.VMEM((2, chunk_rows, WORD_LANES), jnp.uint32),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SMEM((1,), jnp.int32)],
    )
    out = pl.pallas_call(
        functools.partial(_indexer_kernel, block_size=bs, pages=pages,
                          max_blocks=maxb),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, n_chunks, chunk_rows),
                                       jnp.float32),
        # slots run in order: each starts the next one's first copies
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(lengths.astype(jnp.int32), block_tables, q_parts.astype(jnp.bfloat16),
      w.astype(jnp.float32)[..., None],
      # a token's sub-rows, each a row of its own: [NB, bs, sub, 1, 128]
      k_pool.reshape(*k_pool.shape[:3], 1, WORD_LANES))
    return out.reshape(B, n_chunks * chunk_rows)[:, :maxb * bs]


def indexer_scores(q_idx, w, key_pool, block_tables, lengths, *, impl: str,
                   key_of, first_block=0):
    """``I`` [B, MAXB * bs] float32 of one query a slot against the slot's
    cached index keys; ``NEG_INF`` at and past its length. ``key_pool`` is
    the pool that holds the keys; ``impl`` is "pallas" (rows as words) or
    "xla" (any row, read by ``key_of``); ``first_block`` as in
    ``mla_decode_attention``."""
    if impl == "pallas":
        return indexer_scores_pallas(
            q_idx, w, key_pool, block_tables, lengths,
            first_block=first_block,
            interpret=paged_attention.pallas_interpret())
    if impl != "xla":
        raise ValueError(f"impl must be 'xla' or 'pallas', got {impl!r}")
    return indexer_scores_reference(
        q_idx, w, key_pool, first_block + block_tables, lengths,
        key_of=key_of)


# -- the selection ----------------------------------------------------------
def _sortable_bits(x):
    """float32 -> uint32 whose unsigned order is the floats' order."""
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))


def topk_mask(scores, valid, k: int):
    """The ``k`` largest of ``scores`` [..., S] among the rows ``valid``
    marks, as a MASK [..., S]; every valid row where there are no more
    than ``k``. EXACT, ties to the earlier position (what ``select_topk``
    gives): the k-th largest value comes from a radix search over the 32
    bits of the score (a count of rows at or above a candidate a bit), the
    rows above it are in, and of the rows AT it the first ``k - (rows
    above)`` by position."""
    if scores.shape[-1] <= k:
        return valid
    keys = jnp.where(valid, _sortable_bits(scores), jnp.uint32(0))

    def narrow(i, prefix):
        candidate = prefix | (jnp.uint32(1) << (jnp.uint32(31) - i))
        count = jnp.sum(keys >= candidate[..., None], axis=-1)
        return jnp.where(count >= k, candidate, prefix)

    kth = jax.lax.fori_loop(
        0, 32, lambda i, p: narrow(jnp.uint32(i), p),
        jnp.zeros(keys.shape[:-1], jnp.uint32))[..., None]
    above = keys > kth
    at = keys == kth
    room = k - jnp.sum(above, axis=-1, keepdims=True)
    return (above | (at & (jnp.cumsum(at, axis=-1) <= room))) & valid


def select_topk(scores, lengths, k: int):
    """Decode: ``(rows [B, K], count [B])``, ``K = min(k, S)``: the
    positions of the ``K`` largest of ``scores`` [B, S] under each slot's
    length, ties to the earlier position, of which the first ``count =
    min(length, K)`` are real (a shorter slot selects every row; what
    follows them is never read)."""
    K = min(k, scores.shape[-1])
    live = jnp.arange(scores.shape[-1])[None, :] < lengths[:, None]
    _, rows = jax.lax.top_k(jnp.where(live, scores, -jnp.inf), K)
    return rows, jnp.minimum(lengths, K).astype(jnp.int32)


# -- attention over the selected rows ---------------------------------------
def flat_rows(block_tables, rows, block_size: int, first_block=0):
    """Positions ``rows`` [B, K] of each slot as row numbers of the pool
    stack seen flat (a page's rows are contiguous)."""
    pages = jnp.take_along_axis(block_tables, rows // block_size, axis=1)
    return (first_block + pages) * block_size + rows % block_size


def selected_attention_reference(q_lat, q_pe, k_pool, v_pool, flat, count, *,
                                 scale: float, parts_of):
    """The XLA twin: the selected rows gathered by number and read by
    ``parts_of`` (the model's: rows of "k", rows of "v" -> c [B, K, R],
    k_pe [B, K, P]); the first ``count`` [B] of them are real. q_lat [B,
    H, R], q_pe [B, H, P] -> o_lat [B, H, R]."""
    def rows(pool):           # (the size spelled out: "v" may be empty)
        return pool.reshape(pool.shape[0] * pool.shape[1],
                            *pool.shape[2:])[flat]

    c, pe = parts_of(rows(k_pool), rows(v_pool))
    s = (jnp.einsum("bhr,bkr->bhk", q_lat, c,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bhp,bkp->bhk", q_pe, pe,
                      preferred_element_type=jnp.float32)) * scale
    real = jnp.arange(c.shape[1])[None, :] < count[:, None]
    p = jax.nn.softmax(jnp.where(real[:, None, :], s, NEG_INF), axis=-1)
    return jnp.einsum("bhk,bkr->bhr", p.astype(c.dtype), c,
                      preferred_element_type=jnp.float32).astype(q_lat.dtype)


def _selected_kernel(count_ref, rows_ref, q_ref, k_hbm, o_ref, buf, sem, *,
                     scale: float, n_sub: int):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)
    n = count_ref[b]
    K = buf.shape[1]

    # ``group`` rows an iteration (Mosaic unrolls a loop wholly or not
    # at all): fewer branches between the copies' descriptors
    group = next(g for g in (8, 4, 2, 1) if K % g == 0)

    def start(i, carry):
        for j in range(group):
            at = i * group + j
            # what the attention reads of a token, the keys' sub-row and
            # ``c``'s, is one run of the pool: ONE copy, each sub-row to
            # row ``at`` of its plane of the buffer
            pltpu.make_async_copy(
                k_hbm.at[rows_ref[b, at], pl.ds(0, n_sub + 1)],
                buf.at[:, pl.ds(at, 1), :], sem).start()
        return carry

    jax.lax.fori_loop(0, K // group, start, 0)
    # all K rows are in flight, whatever ``n`` (rows past it are copied and
    # masked), and a DMA semaphore counts BYTES: ONE wait, on a descriptor
    # of the buffer's own size, returns when every row is in
    pltpu.make_async_copy(buf, buf, sem).wait()

    contract_lanes = (((1,), (1,)), ((), ()))
    # planes of c in the order of its lanes: (half, sub-row)
    planes = [None] * (2 * n_sub)
    for sub in range(n_sub):
        planes[sub], planes[n_sub + sub] = _planes(buf[1 + sub])
    # k_pe is the low halves of the keys' sub-row's first words (their
    # high halves are its zero padding); the query is zero at the index
    # key's lanes
    s = jnp.zeros((q_ref.shape[2], K), jnp.float32)
    for p, plane in enumerate(planes + [_planes(buf[0])[0]]):
        s = s + jax.lax.dot_general(q_ref[0, p], plane, contract_lanes,
                                    preferred_element_type=jnp.float32)
    at = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(at < n, s * scale, NEG_INF)
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    total = jnp.sum(p, axis=-1, keepdims=True)
    p = p.astype(jnp.bfloat16)
    for i, plane in enumerate(planes):
        # the row is its own value: the latent part again
        out = jax.lax.dot_general(p, plane, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32) / total
        o_ref[0, i] = jnp.where(n > 0, out, 0.0).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def selected_attention_pallas(q_lat, q_pe, k_pool, flat, count, *,
                              scale: float, interpret: bool = False):
    """``k_pool`` [NB, bs, sub-rows, 128] uint32, the rows as words;
    ``flat`` [B, K] row numbers."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, R = q_lat.shape
    K = flat.shape[1]
    n_sub = R // (2 * WORD_LANES)
    if (k_pool.dtype != jnp.uint32
            or k_pool.shape[2:] != (word_row_subrows(R), WORD_LANES)
            or q_pe.shape[-1] != 2 * PE_WORDS):
        raise ValueError(
            f"the sparse attention kernel reads rows of words, "
            f"{word_row_subrows(R)} sub-rows of {WORD_LANES} for a latent "
            f"of {R}, got {k_pool.dtype}{k_pool.shape[2:]}; use the XLA "
            f"twin")
    # the query in the planes' order and lanes: c's 2 * n_sub, then
    # k_pe's (zeros where a word of "v" holds the index key)
    q_parts = jnp.concatenate([
        jnp.moveaxis(q_lat.reshape(B, H, 2 * n_sub, WORD_LANES), 2, 1),
        _beside(q_pe[..., :PE_WORDS], 0)[:, None]], axis=1)
    n_parts = 2 * n_sub + 1

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[pl.BlockSpec((1, n_parts, H, WORD_LANES),
                               lambda b, *_: (b, 0, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, 2 * n_sub, H, WORD_LANES),
                               lambda b, *_: (b, 0, 0, 0)),
        scratch_shapes=[pltpu.VMEM((n_sub + 1, K, WORD_LANES), jnp.uint32),
                        pltpu.SemaphoreType.DMA(())],
    )
    out = pl.pallas_call(
        functools.partial(_selected_kernel, scale=scale, n_sub=n_sub),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, 2 * n_sub, H, WORD_LANES),
                                       q_lat.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(count.astype(jnp.int32), flat.astype(jnp.int32),
      q_parts.astype(jnp.bfloat16),
      # a token's sub-rows, each a row of its own: [tokens, sub, 1, 128]
      k_pool.reshape(-1, k_pool.shape[2], 1, WORD_LANES))
    return jnp.moveaxis(out, 1, 2).reshape(B, H, R)


def sparse_decode_attention(q_lat, q_pe, k_pool, v_pool, block_tables, rows,
                            count, *, impl: str, scale: float, parts_of,
                            first_block=0):
    """The absorbed latent attention of one query a slot over the rows
    ``select_topk`` chose: q_lat [B, H, R], q_pe [B, H, P] -> o_lat [B,
    H, R]. No row that was not selected is read."""
    flat = flat_rows(block_tables, rows, k_pool.shape[1], first_block)
    if impl == "pallas":                # rows as words: "k" holds all
        return selected_attention_pallas(
            q_lat, q_pe, k_pool, flat, count, scale=scale,
            interpret=paged_attention.pallas_interpret())
    if impl != "xla":
        raise ValueError(f"impl must be 'xla' or 'pallas', got {impl!r}")
    return selected_attention_reference(
        q_lat, q_pe, k_pool, v_pool, flat, count, scale=scale,
        parts_of=parts_of)
