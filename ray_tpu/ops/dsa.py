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
  element ``j + n/2`` in the high half), in sub-rows of 128 words:
  ``"k"`` [..., 2, 128] holds ``c`` (1 KB a token, contiguous), ``"v"``
  [..., 128] holds ``k_pe`` with its padding (64 words: ``k_pe`` is
  their low halves) and ``k_I`` (64 words). Why: a kernel that reads ROWS BY NUMBER copies one row a DMA,
  and Mosaic slices an HBM array one row at a time only where its rows
  are contiguous, which on the chip's tiled layouts is 32-bit rows of
  exactly 128 lanes ("Slice shape along dimension 0 must be aligned to
  tiling (8)" for [N, 512] bf16 and for [N, 256] uint32 alike); XLA's own
  row gather took 59 ms a layer at the cell's shape, 1.8 us a row
  (PERF.md, PR 43). A kernel unpacks a word with a shift and a mask and
  never shuffles lanes: the QUERY is laid out to match (zeros where a
  word holds another part). Off the chip's dtype and widths the row is
  the plain ``c`` and ``k_pe | k_I`` and the XLA forms run.
- ``indexer_scores`` (decode): ``I[slot, 0..len)`` through the block
  table: the Mosaic kernel walks a slot's LIVE pages of ``"v"`` as
  ``ops/mla_attention.py``'s walks its pages (512 B a row of the 1,536);
  the XLA twin gathers every table entry.
- the selection. ``topk_mask`` (prefill: a mask over rows for a block of
  queries, from the exact k-th largest score found by a radix search over
  the float's bits, 32 counting passes and no sort) and ``select_topk``
  (decode: row indices, ``jax.lax.top_k``). TIES go to the EARLIER
  position in both, so the two agree row for row.
- ``sparse_decode_attention``: the absorbed attention over the selected
  rows ALONE. The Mosaic kernel copies each selected row out of the pools
  by its number, two DMAs a row (``c``'s two sub-rows as one, ``"v"``'s
  one), into VMEM, then scores, softmax and values of all heads in one
  pass; the XLA twin gathers the same rows.
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
PE_WORDS = 64           # words of "v" before the index key's (k_pe, zeros)


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


def _indexer_kernel(lens_ref, tables_ref, q_ref, w_ref, v_hbm, o_ref, k_buf,
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
        """``act`` ("start" or "wait") on one chunk's page copies."""
        n_live = live_pages(slot)
        for i in range(pages):
            j = chunk * pages + i

            @pl.when(j < n_live)
            def _():
                getattr(pltpu.make_async_copy(
                    v_hbm.at[tables_ref[slot, j]],
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
def indexer_scores_pallas(q_idx, w, v_pool, block_tables, lengths, *,
                          first_block=0, interpret: bool = False):
    """``v_pool`` [NB, bs, 128] uint32, the rows as words."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, Hi, Di = q_idx.shape
    bs = v_pool.shape[1]
    half = Di // 2
    if (v_pool.dtype != jnp.uint32 or v_pool.shape[2:] != (WORD_LANES,)
            or PE_WORDS + half != WORD_LANES):
        raise ValueError(
            f"the indexer kernel reads rows of {WORD_LANES} words whose "
            f"last {WORD_LANES - PE_WORDS} are an index key of "
            f"{2 * (WORD_LANES - PE_WORDS)} numbers, got {v_pool.dtype}"
            f"{v_pool.shape[2:]} and keys of {Di}; use the XLA twin")
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
      w.astype(jnp.float32)[..., None], v_pool)
    return out.reshape(B, n_chunks * chunk_rows)[:, :maxb * bs]


def indexer_scores(q_idx, w, v_pool, block_tables, lengths, *, impl: str,
                   key_of, first_block=0):
    """``I`` [B, MAXB * bs] float32 of one query a slot against the slot's
    cached index keys; ``NEG_INF`` at and past its length. ``impl`` is
    "pallas" (rows as words) or "xla" (any row, read by ``key_of``);
    ``first_block`` as in ``mla_decode_attention``."""
    if impl == "pallas":
        return indexer_scores_pallas(
            q_idx, w, v_pool, block_tables, lengths, first_block=first_block,
            interpret=paged_attention.pallas_interpret())
    if impl != "xla":
        raise ValueError(f"impl must be 'xla' or 'pallas', got {impl!r}")
    return indexer_scores_reference(
        q_idx, w, v_pool, first_block + block_tables, lengths, key_of=key_of)


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
    c, pe = parts_of(
        k_pool.reshape(-1, *k_pool.shape[2:])[flat],
        v_pool.reshape(-1, *v_pool.shape[2:])[flat])
    s = (jnp.einsum("bhr,bkr->bhk", q_lat, c,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bhp,bkp->bhk", q_pe, pe,
                      preferred_element_type=jnp.float32)) * scale
    real = jnp.arange(c.shape[1])[None, :] < count[:, None]
    p = jax.nn.softmax(jnp.where(real[:, None, :], s, NEG_INF), axis=-1)
    return jnp.einsum("bhk,bkr->bhr", p.astype(c.dtype), c,
                      preferred_element_type=jnp.float32).astype(q_lat.dtype)


def _selected_kernel(count_ref, rows_ref, q_ref, k_hbm, v_hbm, o_ref, c_buf,
                     v_buf, sems, *, scale: float, n_sub: int):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)
    n = count_ref[b]
    K = v_buf.shape[0]

    def copies(i, row):
        """Row ``row`` of the pools to place ``i`` of the buffers: the
        token's ``n_sub`` sub-rows of ``c`` as one copy (they are
        contiguous), its row of "v" as another."""
        return (pltpu.make_async_copy(k_hbm.at[row],
                                      c_buf.at[:, pl.ds(i, 1), :],
                                      sems.at[0]),
                pltpu.make_async_copy(v_hbm.at[pl.ds(row, 1), :],
                                      v_buf.at[pl.ds(i, 1), :], sems.at[1]))

    # ``group`` rows an iteration (Mosaic unrolls a loop wholly or not
    # at all): fewer branches between the copies' descriptors
    group = next(g for g in (8, 4, 2, 1) if K % g == 0)

    def start(i, carry):
        for j in range(group):
            for copy in copies(i * group + j, rows_ref[b, i * group + j]):
                copy.start()
        return carry

    def wait(i, carry):
        for _ in range(group):
            for copy in copies(0, 0):
                copy.wait()
        return carry

    jax.lax.fori_loop(0, K // group, start, 0)
    jax.lax.fori_loop(0, K // group, wait, 0)

    contract_lanes = (((1,), (1,)), ((), ()))
    # planes of c in the order of its lanes: (half, sub-row)
    planes = [None] * (2 * n_sub)
    for sub in range(n_sub):
        planes[sub], planes[n_sub + sub] = _planes(c_buf[sub])
    # k_pe is the low halves of "v"'s first words (their high halves are
    # its zero padding); the query is zero at the index key's lanes
    s = jnp.zeros((q_ref.shape[2], K), jnp.float32)
    for p, plane in enumerate(planes + [_planes(v_buf[...])[0]]):
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
def selected_attention_pallas(q_lat, q_pe, k_pool, v_pool, flat, count, *,
                              scale: float, interpret: bool = False):
    """``k_pool`` [NB, bs, n_sub, 128] and ``v_pool`` [NB, bs, 128]
    uint32, the rows as words; ``flat`` [B, K] row numbers."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, R = q_lat.shape
    K = flat.shape[1]
    n_sub = R // (2 * WORD_LANES)
    if (k_pool.dtype != jnp.uint32
            or k_pool.shape[2:] != (n_sub, WORD_LANES)
            or v_pool.shape[2:] != (WORD_LANES,)
            or q_pe.shape[-1] != 2 * PE_WORDS):
        raise ValueError(
            f"the sparse attention kernel reads rows of words, c in "
            f"sub-rows of {WORD_LANES}, got {k_pool.dtype}"
            f"{k_pool.shape[2:]} and {v_pool.shape[2:]} for a latent of "
            f"{R}; use the XLA twin")
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
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, 2 * n_sub, H, WORD_LANES),
                               lambda b, *_: (b, 0, 0, 0)),
        scratch_shapes=[pltpu.VMEM((n_sub, K, WORD_LANES), jnp.uint32),
                        pltpu.VMEM((K, WORD_LANES), jnp.uint32),
                        pltpu.SemaphoreType.DMA((2,))],
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
      # a token's sub-rows, each a row of its own: [tokens, n_sub, 1, 128]
      k_pool.reshape(-1, n_sub, 1, WORD_LANES),
      v_pool.reshape(-1, WORD_LANES))
    return jnp.moveaxis(out, 1, 2).reshape(B, H, R)


def sparse_decode_attention(q_lat, q_pe, k_pool, v_pool, block_tables, rows,
                            count, *, impl: str, scale: float, parts_of,
                            first_block=0):
    """The absorbed latent attention of one query a slot over the rows
    ``select_topk`` chose: q_lat [B, H, R], q_pe [B, H, P] -> o_lat [B,
    H, R]. No row that was not selected is read."""
    flat = flat_rows(block_tables, rows, k_pool.shape[1], first_block)
    if impl == "pallas":
        return selected_attention_pallas(
            q_lat, q_pe, k_pool, v_pool, flat, count, scale=scale,
            interpret=paged_attention.pallas_interpret())
    if impl != "xla":
        raise ValueError(f"impl must be 'xla' or 'pallas', got {impl!r}")
    return selected_attention_reference(
        q_lat, q_pe, k_pool, v_pool, flat, count, scale=scale,
        parts_of=parts_of)
