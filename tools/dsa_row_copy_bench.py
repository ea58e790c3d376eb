"""What a selected row costs to FETCH, apart from what is done with it.

    chiprun -- python3 tools/dsa_row_copy_bench.py              # ~2 min
    chiprun -- python3 tools/dsa_row_copy_bench.py --only a,b   # some variants
    python3 tools/dsa_row_copy_bench.py --tiny-cpu              # rehearsal

``ops/dsa.py``'s sparse attention copies each selected row out of the pool
by its number, and at ``deepseek-v3.2-d5.long_decode_dsa``'s shape (16 slots
x 2,048 rows drawn from a pool of 360,480) those copies, not the bytes and
not the arithmetic, are the kernel's time (PERF.md section 5). This tool
times COPY-ONLY Mosaic kernels at that shape, ``reps`` calls in the device's
queue at once (``tools/moe_gmm_bench.py``'s ``timed``), to tell apart what a
copy costs to START (a descriptor in the scalar core's instruction
stream), to WAIT for, and to MOVE (the DMA engine):

- ``a``: PR 43's gather: a row is two copies (``c`` [2, 128] words out of
  one array, the row of ``"v"`` [128] out of another), each started and each
  waited for alone: 2 starts + 2 waits a row;
- ``b``: the same 2 starts a row and ONE wait a buffer (a DMA semaphore
  counts bytes: a wait on a descriptor of the whole buffer's size returns
  when every row has landed);
- ``c1`` ... ``c4``: ONE array whose row is 1 ... 4 sub-rows of 128 words
  (512 ... 2,048 B), one start a row, one wait a buffer;
- ``d64``, ``d256``: ``c3`` with at most 64 / 256 copies in flight (a wait
  for a group's bytes before the next group starts); ``c3`` is "all".

and the INDEXER's side of a one-array row, a slot's 576 live pages of 32
rows in chunks of 64 pages, copy only:

- ``p_v``: PR 43's page: 32 rows x 128 words of ``"v"``, one 16 KB run;
- ``p_v_bulk``: the same with one wait a chunk;
- ``p_sub3`` / ``p_sub4``: the index keys' sub-row out of a row of 3 / 4
  sub-rows: 32 runs of 512 B at a stride of 1,536 / 2,048 B, ONE copy a
  page, one wait a chunk.

Every kernel hands back the last rows it copied and they are compared with
the pool's: a variant that moves the wrong bytes is an ``error``, not a
time. Prints one JSON line (``chiprun_out/dsa_row_copy_bench.json`` too).
A CPU run (``--tiny-cpu``: debug widths, the Pallas interpreter) checks the
kernels' results and reads no time.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

LANES = 128
TAIL = 8                # rows of each buffer a kernel hands back

# name -> (sub-rows of each array a row is read from (0: a plain [T, 128]
# array, read as PR 43 read "v"), wait a "row" or a "buffer", copies in
# flight (None: all))
GATHERS = {
    "a": ((2, 0), "row", None),
    "b": ((2, 0), "buffer", None),
    "c1": ((1,), "buffer", None),
    "c2": ((2,), "buffer", None),
    "c3": ((3,), "buffer", None),
    "c4": ((4,), "buffer", None),
    "d64": ((3,), "buffer", 64),
    "d256": ((3,), "buffer", 256),
}
# name -> (sub-rows of the pool's row, of which the LAST is copied (0: a
# plain [NB, bs, 128] array), wait a "page" or a "chunk")
PAGES = {
    "p_v": (0, "page"),
    "p_v_bulk": (0, "chunk"),
    "p_sub3": (3, "chunk"),
    "p_sub4": (4, "chunk"),
}


def _gather_kernel(rows_ref, *refs, subs, wait, in_flight):
    import jax
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = len(subs)
    pools, o_ref, bufs, sems = refs[:n], refs[n], refs[n + 1:-1], refs[-1]
    b = pl.program_id(0)
    K = rows_ref.shape[1]
    group = next(g for g in (8, 4, 2, 1) if K % g == 0)

    def copies(i, row):
        out = []
        for p, sub in enumerate(subs):
            if sub:
                src, dst = pools[p].at[row], bufs[p].at[:, pl.ds(i, 1), :]
            else:
                src = pools[p].at[pl.ds(row, 1), :]
                dst = bufs[p].at[pl.ds(i, 1), :]
            out.append(pltpu.make_async_copy(src, dst, sems.at[p]))
        return out

    def landed(count):
        """ONE wait an array for ``count`` rows' bytes."""
        for p, sub in enumerate(subs):
            part = (bufs[p].at[:, pl.ds(0, count), :] if sub
                    else bufs[p].at[pl.ds(0, count), :])
            pltpu.make_async_copy(part, part, sems.at[p]).wait()

    def start(g, carry):
        for j in range(group):
            row = rows_ref[b, g * group + j]
            for copy in copies(g * group + j, row):
                copy.start()
        return carry

    def wait_rows(g, carry):
        for _ in range(group):
            for copy in copies(0, 0):
                copy.wait()
        return carry

    first = K if in_flight is None else min(in_flight, K)
    jax.lax.fori_loop(0, first // group, start, 0)
    if wait == "row":
        jax.lax.fori_loop(0, K // group, wait_rows, 0)
    else:
        def steady(g, carry):
            landed(group)
            return start(g, carry)

        jax.lax.fori_loop(first // group, K // group, steady, 0)
        landed(first)
    at = 0
    for p, sub in enumerate(subs):
        for s in range(max(sub, 1)):
            tail = (bufs[p][s, pl.ds(K - TAIL, TAIL), :] if sub
                    else bufs[p][pl.ds(K - TAIL, TAIL), :])
            o_ref[0, at] = tail
            at += 1


def gather_call(subs, wait, in_flight, B, K, interpret):
    """(jitted fn(rows [B, K], *pools) -> [B, sub-rows, TAIL, 128], the
    pools' row shapes)."""
    import jax
    import jax.experimental.pallas as pl
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu

    total = sum(max(s, 1) for s in subs)
    call = pl.pallas_call(
        functools.partial(_gather_kernel, subs=subs, wait=wait,
                          in_flight=in_flight),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * len(subs),
            out_specs=pl.BlockSpec((1, total, TAIL, LANES),
                                   lambda b, *_: (b, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((s, K, LANES) if s else (K, LANES), jnp.uint32)
                for s in subs] + [pltpu.SemaphoreType.DMA((len(subs),))]),
        out_shape=jax.ShapeDtypeStruct((B, total, TAIL, LANES), jnp.uint32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret)
    return jax.jit(call), [(s, 1, LANES) if s else (LANES,) for s in subs]


def _page_kernel(tables_ref, hbm, o_ref, buf, sem, *, sub, wait, bs, pages,
                 chunks):
    import jax
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)

    def copy(chunk, i):
        page = tables_ref[b, chunk * pages + i]
        # of a row of sub-rows, the LAST one of every token of the page:
        # bs runs of 128 words, a row's bytes apart
        src = hbm.at[page, :, sub - 1, 0] if sub else hbm.at[page]
        return pltpu.make_async_copy(src, buf.at[pl.ds(i * bs, bs)], sem)

    def chunk_body(chunk, carry):
        for i in range(pages):
            copy(chunk, i).start()
        if wait == "page":
            for i in range(pages):
                copy(chunk, i).wait()
        else:
            pltpu.make_async_copy(buf, buf, sem).wait()
        return carry

    jax.lax.fori_loop(0, chunks, chunk_body, 0)
    o_ref[0] = buf[pl.ds(pages * bs - TAIL, TAIL)]


def page_call(sub, wait, B, bs, pages, chunks, interpret):
    """(jitted fn(tables [B, chunks * pages], pool) -> [B, TAIL, 128]: the
    last rows of the slot's last page, the pool's row shape)."""
    import jax
    import jax.experimental.pallas as pl
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu

    call = pl.pallas_call(
        functools.partial(_page_kernel, sub=sub, wait=wait, bs=bs,
                          pages=pages, chunks=chunks),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, TAIL, LANES), lambda b, *_: (b, 0, 0)),
            scratch_shapes=[pltpu.VMEM((pages * bs, LANES), jnp.uint32),
                            pltpu.SemaphoreType.DMA(())]),
        out_shape=jax.ShapeDtypeStruct((B, TAIL, LANES), jnp.uint32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret)
    return jax.jit(call), ((sub, 1, LANES) if sub else (LANES,))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=2147480044)
    ap.add_argument("--only", default="",
                    help="comma-separated variants (default: all)")
    ap.add_argument("--reps", type=int, default=100)
    ap.add_argument("--tiny-cpu", action="store_true")
    ap.add_argument("--out", default="chiprun_out/dsa_row_copy_bench.json")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from tools.moe_gmm_bench import timed

    tiny = args.tiny_cpu
    device = jax.devices()[0]
    if not tiny and device.platform != "tpu":
        sys.exit("dsa_row_copy_bench: no TPU; a CPU time is not a reading "
                 "(--tiny-cpu checks the kernels' results)")
    # the cell: 16 slots x 2,048 selected rows; a pool of 5 layers' 11,265
    # blocks of 32 rows is the stack the decode program holds, of which a
    # layer's window is 360,480 rows; a slot's ~17.9k live rows are 576
    # pages = 9 chunks of 64
    if tiny:
        B, K, bs, blocks, pages, chunks = 2, 24, 8, 12, 3, 2
    else:
        B, K, bs, blocks, pages, chunks = 16, 2048, 32, 11265, 64, 9
    T = blocks * bs
    rng = np.random.default_rng(args.seed)
    rows = jnp.asarray(np.stack([rng.choice(T, K, replace=False)
                                 for _ in range(B)]).astype(np.int32))
    tables = jnp.asarray(np.stack([rng.choice(blocks, pages * chunks,
                                              replace=False)
                                   for _ in range(B)]).astype(np.int32))

    def drawn(*shape):
        return jax.random.bits(jax.random.key(args.seed % (2**31)), shape,
                               jnp.uint32)

    only = [v for v in args.only.split(",") if v]
    unknown = set(only) - set(GATHERS) - set(PAGES)
    if unknown:
        sys.exit(f"dsa_row_copy_bench: no variant {sorted(unknown)}")
    line = {"tool": "dsa_row_copy_bench", "device": device.device_kind,
            "platform": device.platform, "seed": args.seed,
            "shape": {"slots": B, "rows_a_slot": K, "pool_rows": T,
                      "block": bs, "pages_a_slot": pages * chunks,
                      "pages_a_chunk": pages},
            "reps": None if tiny else args.reps, "gather": {}, "pages": {}}

    for name, (subs, wait, in_flight) in GATHERS.items():
        if only and name not in only:
            continue
        try:
            fn, shapes = gather_call(subs, wait, in_flight, B, K, tiny)
            pools = [drawn(T, *shape) for shape in shapes]
            got = np.asarray(fn(rows, *pools))
            want = np.concatenate(
                [np.moveaxis(np.asarray(pool[rows[:, -TAIL:]])
                             .reshape(B, TAIL, -1, LANES), 2, 1)
                 for pool in pools], axis=1)
            if not np.array_equal(got, want):
                raise AssertionError("the rows copied are not the pool's")
            reading = {"bytes_a_row": 512 * sum(max(s, 1) for s in subs),
                       "copies_a_row": len(subs), "wait": wait,
                       "in_flight": in_flight or K, "ms": None}
            if not tiny:
                ms = timed(fn, (rows, *pools), args.reps)
                reading.update(ms=ms, ns_a_row=1e6 * ms / (B * K))
            del pools
        except Exception as e:          # a refusal is a reading too
            reading = {"error": f"{type(e).__name__}: {str(e)[-400:]}"}
        line["gather"][name] = reading

    for name, (sub, wait) in PAGES.items():
        if only and name not in only:
            continue
        try:
            fn, shape = page_call(sub, wait, B, bs, pages, chunks, tiny)
            pool = drawn(blocks, bs, *shape)
            got = np.asarray(fn(tables, pool))
            last = np.asarray(pool[tables[:, -1], -TAIL:])
            want = last.reshape(B, TAIL, -1)[..., -LANES:]
            if not np.array_equal(got, want):
                raise AssertionError("the pages copied are not the pool's")
            reading = {"bytes_a_page": 512 * bs,
                       "runs_a_page": bs if sub else 1,
                       "row_bytes": 512 * max(sub, 1), "wait": wait,
                       "ms": None}
            if not tiny:
                ms = timed(fn, (tables, pool), args.reps)
                moved = B * pages * chunks * reading["bytes_a_page"]
                reading.update(ms=ms, gb_per_s=moved / ms / 1e6,
                               ns_a_page=1e6 * ms / (B * pages * chunks))
            del pool
        except Exception as e:
            reading = {"error": f"{type(e).__name__}: {str(e)[-400:]}"}
        line["pages"][name] = reading

    text = json.dumps(line)
    if not tiny:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "a") as f:
            f.write(text + "\n")
    print(text)
    failed = [n for part in ("gather", "pages") for n, r in line[part].items()
              if "error" in r]
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
