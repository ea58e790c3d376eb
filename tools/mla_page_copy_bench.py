"""What a page of the latent cache costs to FETCH, apart from what the
attention does with it.

    chiprun -- python3 tools/mla_page_copy_bench.py              # ~2 min
    chiprun -- python3 tools/mla_page_copy_bench.py --only a,c   # some variants
    python3 tools/mla_page_copy_bench.py --tiny-cpu              # rehearsal

``ops/mla_attention.py``'s kernel walks each slot's live pages chunk by chunk,
double-buffered, and at ``kanana-2-30b-a3b-d5.long_decode_mla``'s shape (32
slots x ~560 live pages of 32 rows, chunks of 64 pages) it reads 2.56 ns a row
where its bytes need 1.41 (PERF.md section 5, PR 41). ``tools/
dsa_row_copy_bench.py`` (PR 44) read what a DMA descriptor costs in a kernel's
instruction stream, ~17 ns to start and ~3 ns to wait for, whatever it moves.
This tool times that walk at that shape, ``reps`` calls in the device's queue
at once (``tools/moe_gmm_bench.py``'s ``timed``), with the copies and the
arithmetic apart:

- ``a``: PR 41's page: ``c`` [32, 512] out of one pool and ``k_pe`` [32, 128]
  out of another, each started and each waited for alone: 2 starts + 2 waits
  a page;
- ``b``: the same 2 starts a page, ONE wait a buffer (a DMA semaphore counts
  bytes: the chunk's live pages are waited for as the powers of two that
  their number is the sum of, a full chunk as one);
- ``c``: ONE pool whose row is ``c | k_pe``, 640 lanes: one start a page
  of [32, 640], one wait a buffer as in ``b``;
- ``c_all``: ``c`` with EVERY page of a chunk started (a dead page reads
  whatever its table entry names) and one wait of the buffer's own size;
- ``d``: no copies: the kernel's three dots and online softmax a chunk over
  a buffer that is already resident (lane slices of ONE [rows, 640] buffer),
  the compute floor;
- ``d2``: ``d`` over two buffers, [rows, 512] and [rows, 128]: what the lane
  slices cost;
- ``k``: the kernel itself, ``ops.mla_attention.mla_decode_attention_pallas``
  as the tree holds it (at its own ``CHUNK_ROWS``), over the same pool,
  tables and lengths.

What it read on the chip (PERF.md section 5, PR 45): ``a``, ``b`` and ``c``
the SAME 0.982 ms (748 GB/s: a walk that only copies is bound by its bytes
however its pages are described, the descriptors are issued while earlier
pages move), ``d`` 0.478, and the kernel 1.362 with PR 41's two copies a
page against 0.987 with one: the descriptors' cost shows only where the
dots stand on the same instruction stream. So read ``k`` beside ``c`` and
``d``: at ``c``'s pace the kernel is bound by its bytes, near ``c`` + ``d``
by its instruction stream.

Every copying kernel hands back each slot's last live page as its buffer
holds it after the walk, and it is compared with the pool's: a variant that
moves the wrong bytes is an ``error``, not a time. ``d`` / ``d2`` are
compared with the same arithmetic in XLA, ``k`` with its XLA twin on two
slots. Prints one JSON line
(``chiprun_out/mla_page_copy_bench.json`` too). A CPU run (``--tiny-cpu``:
debug widths, the Pallas interpreter) checks the kernels' results and reads
no time.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

NEG_INF = -1e30

# name -> (lanes of each pool a page is read from, wait a "page", the live
# pages of a "buffer", or the "whole" buffer with every page started)
COPIES = {
    "a": ("split", "page"),
    "b": ("split", "buffer"),
    "c": ("one", "buffer"),
    "c_all": ("one", "whole"),
}
COMPUTES = {"d": "one", "d2": "split"}
KERNEL = "k"


def _copy_kernel(lens_ref, tables_ref, *refs, n_pools, wait, bs, pages,
                 max_blocks):
    import jax
    import jax.experimental.pallas as pl
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu

    pools, o_ref = refs[:n_pools], refs[n_pools]
    bufs = refs[n_pools + 1:2 * n_pools + 1]
    sems, first_buf_ref = refs[-2:]
    b = pl.program_id(0)

    def live_pages(slot):
        return jnp.clip((lens_ref[slot] + bs - 1) // bs, 1, max_blocks)

    def copy(p, slot, j, i, buf):
        page = tables_ref[slot, jnp.minimum(j, max_blocks - 1)]
        return pltpu.make_async_copy(
            pools[p].at[page], bufs[p].at[buf, pl.ds(i * bs, bs)],
            sems.at[p, buf])

    def start(slot, chunk, buf):
        n_live = live_pages(slot)
        for i in range(pages):
            j = chunk * pages + i
            if wait == "whole":
                for p in range(n_pools):
                    copy(p, slot, j, i, buf).start()
                continue

            @pl.when(j < n_live)
            def _():
                for p in range(n_pools):
                    copy(p, slot, j, i, buf).start()

    def landed(slot, chunk, buf):
        n = jnp.clip(live_pages(slot) - chunk * pages, 0, pages)
        if wait == "page":
            for i in range(pages):
                @pl.when(i < n)
                def _():
                    for p in range(n_pools):
                        copy(p, slot, 0, i, buf).wait()
        elif wait == "whole":
            for p in range(n_pools):
                pltpu.make_async_copy(bufs[p].at[buf], bufs[p].at[buf],
                                      sems.at[p, buf]).wait()
        else:
            # the semaphore counts bytes: n pages' are the powers of two
            # that n is the sum of
            part = 1 << (pages.bit_length() - 1)
            while part:
                @pl.when((n & part) != 0)
                def _(part=part):
                    for p in range(n_pools):
                        rows = bufs[p].at[buf, pl.ds(0, part * bs)]
                        pltpu.make_async_copy(rows, rows,
                                              sems.at[p, buf]).wait()
                part //= 2

    @pl.when(b == 0)
    def _first():
        first_buf_ref[0] = 0
        start(0, 0, 0)

    first_buf = first_buf_ref[0]
    n_live = live_pages(b)
    n_chunks = (n_live + pages - 1) // pages

    def chunk_body(c, carry):
        buf = (first_buf + c) % 2

        @pl.when(c + 1 < n_chunks)
        def _():
            start(b, c + 1, 1 - buf)

        @pl.when(jnp.logical_and(c + 1 == n_chunks,
                                 b + 1 < pl.num_programs(0)))
        def _():
            start(b + 1, 0, 1 - buf)

        landed(b, c, buf)
        return carry

    jax.lax.fori_loop(0, n_chunks, chunk_body, 0)
    last_buf = (first_buf + n_chunks - 1) % 2
    first_buf_ref[0] = (first_buf + n_chunks) % 2
    # the slot's last live page, where the walk left it
    at = pl.multiple_of((n_live - 1 - (n_chunks - 1) * pages) * bs, bs)
    lane = 0
    for p in range(n_pools):
        w = bufs[p].shape[-1]
        o_ref[0, :, lane:lane + w] = bufs[p][last_buf, pl.ds(at, bs), :]
        lane += w


def copy_call(widths, wait, B, bs, pages, max_blocks, interpret):
    """jitted fn(lengths [B], tables [B, max_blocks], *pools [NB, bs, w]) ->
    [B, bs, sum(widths)]: each slot's last live page."""
    import jax
    import jax.experimental.pallas as pl
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu

    n = len(widths)
    total = sum(widths)
    call = pl.pallas_call(
        functools.partial(_copy_kernel, n_pools=n, wait=wait, bs=bs,
                          pages=pages, max_blocks=max_blocks),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * n,
            out_specs=pl.BlockSpec((1, bs, total), lambda b, *_: (b, 0, 0)),
            scratch_shapes=[pltpu.VMEM((2, pages * bs, w), jnp.bfloat16)
                            for w in widths]
            + [pltpu.SemaphoreType.DMA((n, 2)), pltpu.SMEM((1,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((B, bs, total), jnp.bfloat16),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret)
    return jax.jit(call)


def _compute_kernel(lens_ref, q_lat_ref, q_pe_ref, *refs, split, R, bs, pages,
                    max_blocks, scale):
    """``ops/mla_attention.py:_mla_kernel``'s arithmetic a chunk, over rows
    that are already in VMEM: every chunk reads the same resident buffer."""
    import jax
    import jax.experimental.pallas as pl
    import jax.numpy as jnp

    if split:
        c_ref, pe_ref, o_ref, m_ref, l_ref, acc_ref = refs
    else:
        rows_ref, o_ref, m_ref, l_ref, acc_ref = refs
    b = pl.program_id(0)
    chunk_len = pages * bs
    length = lens_ref[b]
    n_live = jnp.clip((length + bs - 1) // bs, 1, max_blocks)
    n_chunks = (n_live + pages - 1) // pages
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def chunk_body(c, carry):
        buf = c % 2
        if split:
            rows, pe = c_ref[buf], pe_ref[buf]
        else:
            rows, pe = rows_ref[buf, :, :R], rows_ref[buf, :, R:]
        contract_lanes = (((1,), (1,)), ((), ()))
        s = jax.lax.dot_general(q_lat_ref[0], rows, contract_lanes,
                                preferred_element_type=jnp.float32)
        s = (s + jax.lax.dot_general(
            q_pe_ref[0], pe, contract_lanes,
            preferred_element_type=jnp.float32)) * scale
        at = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(at < length - c * chunk_len, s, NEG_INF)
        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(rows.dtype), rows, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)
        return carry

    jax.lax.fori_loop(0, n_chunks, chunk_body, 0)
    o_ref[0] = (acc_ref[...] / l_ref[:, :1]).astype(o_ref.dtype)


def compute_call(split, B, H, R, P, bs, pages, max_blocks, scale, interpret):
    """jitted fn(lengths [B], q_lat [B, H, R], q_pe [B, H, P], resident
    rows [2, pages * bs, R + P] (or its two lane ranges)) -> [B, H, R]."""
    import jax
    import jax.experimental.pallas as pl
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu

    def per_slot(width):
        return pl.BlockSpec((1, H, width), lambda b, *_: (b, 0, 0))

    def resident(width):
        return pl.BlockSpec((2, pages * bs, width), lambda b, *_: (0, 0, 0))

    call = pl.pallas_call(
        functools.partial(_compute_kernel, split=split, R=R, bs=bs,
                          pages=pages, max_blocks=max_blocks, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B,),
            in_specs=[per_slot(R), per_slot(P)]
            + ([resident(R), resident(P)] if split else [resident(R + P)]),
            out_specs=per_slot(R),
            scratch_shapes=[pltpu.VMEM((H, 128), jnp.float32),
                            pltpu.VMEM((H, 128), jnp.float32),
                            pltpu.VMEM((H, R), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, H, R), jnp.bfloat16),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret)
    return jax.jit(call)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=2147480045)
    ap.add_argument("--only", default="",
                    help="comma-separated variants (default: all)")
    ap.add_argument("--reps", type=int, default=100)
    ap.add_argument("--chunk-rows", type=int, default=2048)
    ap.add_argument("--tiny-cpu", action="store_true")
    ap.add_argument("--out", default="chiprun_out/mla_page_copy_bench.json")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from tools.moe_gmm_bench import timed

    tiny = args.tiny_cpu
    device = jax.devices()[0]
    if not tiny and device.platform != "tpu":
        sys.exit("mla_page_copy_bench: no TPU; a CPU time is not a reading "
                 "(--tiny-cpu checks the kernels' results)")
    # the cell: 32 slots x 24,576 positions in pages of 32 rows, the five
    # layers' pool one stack of 5 x 24,577 blocks of which the tables name
    # one layer's window; mid-window a slot holds ~17.9k rows = 560 pages =
    # 8 chunks of 64 and 48 pages of a ninth
    if tiny:
        B, H, R, P, bs, blocks, first, maxb, pages = 3, 4, 32, 16, 8, 40, 13, 12, 4
        lengths = np.array([8 * 9 + 3, 8 * 4, 8 * 11 + 1], np.int32)
    else:
        B, H, R, P, bs, maxb = 32, 32, 512, 128, 32, 768
        blocks, first, pages = 5 * 24577, 3 * 24577, args.chunk_rows // 32
    W = R + P
    rng = np.random.default_rng(args.seed)
    if not tiny:
        lengths = (17920 + rng.integers(-31, 32, B)).astype(np.int32)
    window = 24 if tiny else 24576
    tables = first + np.stack([rng.choice(window, maxb, replace=False)
                               for _ in range(B)]).astype(np.int32)
    n_live = -(-lengths // bs)
    total_pages = int(n_live.sum())
    lengths, tables = jnp.asarray(lengths), jnp.asarray(tables)
    last_page = tables[jnp.arange(B), jnp.asarray(n_live - 1)]
    key = jax.random.key(args.seed % (2**31))

    only = [v for v in args.only.split(",") if v]
    unknown = set(only) - set(COPIES) - set(COMPUTES) - {KERNEL}
    if unknown:
        sys.exit(f"mla_page_copy_bench: no variant {sorted(unknown)}")
    line = {"tool": "mla_page_copy_bench", "device": device.device_kind,
            "platform": device.platform, "seed": args.seed,
            "shape": {"slots": B, "heads": H, "lanes": [R, P], "block": bs,
                      "pool_blocks": blocks, "pages_a_chunk": pages,
                      "pages": total_pages, "rows": int(lengths.sum())},
            "reps": None if tiny else args.reps, "copies": {}, "compute": {},
            "kernel": {}}

    def drawn(k, *shape):
        # bf16 of any bit pattern under 2 in size (no NaN, no infinity):
        # rows are compared as bits
        bits = jax.random.bits(jax.random.fold_in(key, k), shape, jnp.uint16)
        return jax.lax.bitcast_convert_type(bits & jnp.uint16(0xBF7F),
                                            jnp.bfloat16)

    pool, q_lat, q_pe, rows = jax.jit(lambda: (
        drawn(1, blocks, bs, W), drawn(2, B, H, R) * 0.25,
        drawn(3, B, H, P) * 0.25, drawn(4, 2, pages * bs, W)))()
    for name, (layout, wait) in COPIES.items():
        if only and name not in only:
            continue
        try:
            widths = (W,) if layout == "one" else (R, P)
            fn = copy_call(widths, wait, B, bs, pages, maxb, tiny)
            pools = ((pool,) if layout == "one"
                     else (pool[..., :R], pool[..., R:]))
            got = np.asarray(fn(lengths, tables, *pools).astype(jnp.float32))
            want = np.asarray(pool[last_page].astype(jnp.float32))
            if not np.array_equal(got, want):
                raise AssertionError("the pages copied are not the pool's")
            reading = {"starts_a_page": len(widths), "wait": wait,
                       "bytes_a_page": 2 * bs * W, "ms": None}
            if not tiny:
                ms = timed(fn, (lengths, tables, *pools), args.reps)
                reading.update(
                    ms=ms, ns_a_page=1e6 * ms / total_pages,
                    gb_per_s=total_pages * reading["bytes_a_page"] / ms / 1e6)
            del pools
        except Exception as e:          # a refusal is a reading too
            reading = {"error": f"{type(e).__name__}: {str(e)[-400:]}"}
        line["copies"][name] = reading
    scale = (R + P) ** -0.5
    if not only or KERNEL in only:
        from ray_tpu.ops import mla_attention
        try:
            kw = dict(scale=scale, first_block=0)
            fn = functools.partial(mla_attention.mla_decode_attention,
                                   impl="pallas", **kw)
            operands = (q_lat, q_pe, pool, tables, lengths)
            got = np.asarray(fn(*operands).astype(jnp.float32))
            want = np.asarray(mla_attention.mla_decode_attention(
                q_lat[:2], q_pe[:2], pool, tables[:2], lengths[:2],
                impl="xla", **kw).astype(jnp.float32))
            if not np.allclose(got[:2], want, atol=2e-2):
                raise AssertionError("the kernel is not its XLA twin")
            reading = {"chunk_rows": mla_attention.CHUNK_ROWS, "ms": None}
            if not tiny:
                ms = timed(fn, operands, args.reps)
                reading.update(
                    ms=ms, ns_a_page=1e6 * ms / total_pages,
                    gb_per_s=total_pages * 2 * bs * W / ms / 1e6)
        except Exception as e:
            reading = {"error": f"{type(e).__name__}: {str(e)[-400:]}"}
        line["kernel"][KERNEL] = reading
    del pool

    for name, layout in COMPUTES.items():
        if only and name not in only:
            continue
        try:
            split = layout == "split"
            fn = compute_call(split, B, H, R, P, bs, pages, maxb, scale, tiny)
            operands = (rows[..., :R], rows[..., R:]) if split else (rows,)
            got = np.asarray(fn(lengths, q_lat, q_pe, *operands)
                             .astype(jnp.float32))
            if tiny:
                # the same softmax over the resident chunks, repeated
                n_chunks = -(-int(n_live.max()) // pages)

                @jax.jit
                def xla(lengths, q_lat, q_pe, rows):
                    seen = jnp.concatenate([rows[c % 2]
                                            for c in range(n_chunks)])
                    s = (jnp.einsum("bhr,sr->bhs", q_lat, seen[:, :R],
                                    preferred_element_type=jnp.float32)
                         + jnp.einsum("bhp,sp->bhs", q_pe, seen[:, R:],
                                      preferred_element_type=jnp.float32)
                         ) * scale
                    live = jnp.arange(seen.shape[0])[None] < lengths[:, None]
                    p = jax.nn.softmax(jnp.where(live[:, None], s, NEG_INF),
                                       -1)
                    return jnp.einsum("bhs,sr->bhr", p,
                                      seen[:, :R].astype(jnp.float32))

                want = np.asarray(xla(lengths, q_lat, q_pe, rows))
                if not np.allclose(got, want, atol=2e-2):
                    raise AssertionError("the chunks' softmax is not XLA's")
            elif not np.isfinite(got).all():
                raise AssertionError("the chunks' softmax is not finite")
            reading = {"buffers": 2 if split else 1, "ms": None}
            if not tiny:
                ms = timed(fn, (lengths, q_lat, q_pe, *operands), args.reps)
                reading.update(ms=ms, ns_a_page=1e6 * ms / total_pages)
        except Exception as e:
            reading = {"error": f"{type(e).__name__}: {str(e)[-400:]}"}
        line["compute"][name] = reading

    text = json.dumps(line)
    if not tiny:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "a") as f:
            f.write(text + "\n")
    print(text)
    failed = [n for part in ("copies", "compute", "kernel")
              for n, r in line[part].items()
              if "error" in r]
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
