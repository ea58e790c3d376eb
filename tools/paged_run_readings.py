"""Chip readings behind ``ops.paged_attention.RUN_BYTES``: the paged
decode kernel ALONE over pools of 1, 2, 4 and 8 K/V heads of 128 in
blocks of 32 rows, each at its cell's slots and depth, the blocks copied
one a page (``run`` 1) and in runs of 2, 4, 8 (PERF.md, PR 58, quotes
them).

    chiprun -- python3 tools/paged_run_readings.py

Every slot's blocks lie one after another in the pool, so any run is a
valid view of the same bytes; each reading stands beside the K/V bytes'
least time. One JSON line, to ``chiprun_out/paged_run_readings.json``
too.
"""
from __future__ import annotations

import json
import os
import sys

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# (cell, K/V heads, query rows a slot, slots, positions a slot, max_seq)
SHAPES = (
    ("jamba2-3b.long_decode_mamba1", 1, 20, 32, 19_000, 24_576),
    ("nemotron-3-super-d11.long_decode_ssm", 2, 32, 64, 9_500, 14_336),
    ("mellum2-12b-a2.5b-d8.long_decode_hybrid", 4, 32, 32, 10_000, 16_384),
    ("sdar-30b-a3b-chat-d6.block_decode", 4, 128, 48, 3_500, 8_192),
    ("mistral-7b-v0.3-d6.batch_decode", 8, 32, 32, 2_000, 8_192),
)


def main():
    if jax.devices()[0].platform != "tpu":
        sys.exit("paged_run_readings: no TPU; a CPU time is not a reading")
    from benchmark.lib.peaks import peaks_for
    from ray_tpu.ops.paged_attention import (paged_decode_attention,
                                             run_blocks)
    from tools.jamba_kernel_readings import timed

    peaks = peaks_for(jax.devices()[0].device_kind)
    bs, D = 32, 128
    line = {"device": jax.devices()[0].device_kind, "block_size": bs}
    keys = jax.random.split(jax.random.key(0), 2)
    for cell, Hkv, H, B, deep, max_seq in SHAPES:
        maxb = max_seq // bs
        q = jax.random.normal(keys[0], (B, H, D), jnp.bfloat16)
        pool = jax.random.normal(keys[1], (B * maxb + 8, bs, Hkv, D),
                                 jnp.bfloat16)
        tables = jnp.arange(B * maxb, dtype=jnp.int32).reshape(B, maxb)
        lengths = jnp.full((B,), deep, jnp.int32)
        read = {"kv_heads": Hkv, "page_bytes": bs * Hkv * D * 2,
                "run_blocks": run_blocks(bs, Hkv, D, 2),
                "kv_least_ms": 1e3 * B * deep * 2 * Hkv * D * 2
                / peaks["hbm_bytes_per_s"]}
        for run in (1, 2, 4, 8):
            if run * bs * Hkv > 2048:
                continue
            f = jax.jit(lambda q, kp, vp, run=run: paged_decode_attention(
                q, kp, vp, tables, lengths, impl="pallas", run=run))
            read[f"run{run}_ms"] = 1e3 * timed(f, q, pool, pool, reps=30)
        del pool
        line[cell] = read

    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/paged_run_readings.json", "w") as out:
        out.write(json.dumps(line) + "\n")
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
