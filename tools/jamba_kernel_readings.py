"""Chip readings of the three kernels ``jamba2-3b.long_decode_mamba1``
leans on, each ALONE at the cell's shapes (PERF.md, PR 57, quotes them).

    chiprun -- python3 tools/jamba_kernel_readings.py

- the Mamba-1 STATE UPDATE of one decode step: 26 layers' calls in one
  ``lax.scan`` over the layer index (as the model's runs make them), 32
  slots x S [16, 5120] float32 in a donated stack, the Mosaic kernel and
  the XLA twin, against the bytes'
  least time (``costs.ssm_state_update_least_s``);
- the Mamba-1 PREFILL SCAN of one chunk-prefill call of one layer (1 row
  x 512 positions x 5120 channels, ``u`` in bf16, a carried state): the
  Mosaic kernel and the XLA
  twin (a ``lax.scan`` over positions), each scaled to the cell's set-up
  (524,288 prompt tokens x 26 layers), against its least time
  (``costs.ssm_scan_least_s``: the exponentials at one vector register a
  cycle, or the bytes); and what an exponential COSTS on this chip, read
  by a kernel that does nothing else (``exp_rate``);
- the PAGED DECODE ATTENTION of one MQA layer, 20 query heads over ONE
  K/V head of 128, 32 slots x 19,000 positions, in pages of 32 rows
  (8 KB), of 256 rows (64 KB), and in blocks of 32 rows copied in RUNS
  of 8 (``run=8``: what the engine does since PR 58, and the same 64 KB
  a copy), against the K/V bytes' least time.

One JSON line, to ``chiprun_out/jamba_kernel_readings.json`` too.
"""
from __future__ import annotations

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def timed(fn, *args, reps=50, carry=None):
    """Seconds a call, ``reps`` calls in the device's queue; ``carry``:
    the index of the argument that the call's first result replaces (a
    donated buffer)."""
    args = list(args)

    def call():
        out = fn(*args)
        if carry is not None:
            args[carry] = out[0]
        return out

    jax.block_until_ready(call())
    rounds = []
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(reps):
            out = call()
        jax.block_until_ready(out)
        rounds.append((time.perf_counter() - t0) / reps)
    return float(np.median(rounds))


def exp_rate(regs: int = 32, rounds: int = 4096):
    """Float32 exponentials a second of one TensorCore, and multiply-adds
    in their place: ``regs`` vector registers resident in VMEM, ``rounds``
    passes of ``x <- exp(x * c)`` (or ``x <- x * c + c``) over them in one
    kernel call: no HBM traffic to speak of, so the time is the unit's."""
    import functools

    import jax.experimental.pallas as pl

    def kernel(x_ref, o_ref, *, op):
        def body(_, x):
            return jnp.exp(x * -0.5) if op == "exp" else x * -0.5 + 0.25
        o_ref[...] = jax.lax.fori_loop(0, rounds, body, x_ref[...])

    x = jnp.full((regs * 8, 128), 0.3, jnp.float32)
    out = {"registers": regs, "rounds": rounds}
    for op in ("exp", "fma"):
        f = jax.jit(pl.pallas_call(
            functools.partial(kernel, op=op),
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype)))
        t = timed(f, x, reps=20)
        out[f"{op}_per_s"] = regs * 1024 * rounds / t
        out[f"{op}_cycles_a_register_at_940MHz"] = t * 940e6 / (regs * rounds)
    return out


def main():
    if jax.devices()[0].platform != "tpu":
        sys.exit("jamba_kernel_readings: no TPU; a CPU time is not a reading")
    from benchmark import run as harness
    from benchmark.costs import ssm1_hybrid_transformer as costs
    from benchmark.lib.peaks import peaks_for
    from ray_tpu.ops import ssm1
    from ray_tpu.ops.paged_attention import (paged_decode_attention,
                                             run_blocks)

    cfg = harness.load_json(harness.ROOT, "benchmark/configs/jamba2-3b.json")
    peaks = peaks_for(jax.devices()[0].device_kind)
    s = costs.dims(cfg)
    L, B, N, W = s["mamba_layers"], 32, s["state"], s["inner"]
    line = {"device": jax.devices()[0].device_kind}
    k = jax.random.split(jax.random.key(0), 8)

    # -- the state update, 26 layers a step ----------------------------------
    a = -jnp.exp(jax.random.uniform(k[0], (L, N, W), maxval=2.7))
    dt = jax.nn.softplus(jax.random.normal(k[1], (L, B, W)) - 4.0)
    dtu = dt * jax.random.normal(k[2], (L, B, W))
    Bm, Cm = (jax.random.normal(k[i], (L, B, N)) for i in (3, 4))

    def step_of(update):
        def step(stack):
            def body(stack, j):
                stack, y = update(stack, j, a[j], dt[j], dtu[j], Bm[j], Cm[j])
                return stack, y
            return jax.lax.scan(body, stack, jnp.arange(L, dtype=jnp.int32))
        return jax.jit(step, donate_argnums=0)

    upd = {"least_ms": 1e3 * costs.ssm_state_update_least_s(cfg, peaks, B)}
    stack = jax.random.normal(k[5], (L, B, N, W), jnp.float32)
    upd["pallas_ms"] = 1e3 * timed(step_of(ssm1.state_update_pallas), stack,
                                   carry=0)
    stack = jax.random.normal(k[5], (L, B, N, W), jnp.float32)
    upd["xla_ms"] = 1e3 * timed(step_of(ssm1.state_update_reference), stack,
                                carry=0)
    del stack
    line["state_update_26_layers"] = upd

    # -- the prefill scan, one chunk of one layer ----------------------------
    T, tokens = 512, 524_288
    u = jax.random.normal(k[0], (1, T, W), jnp.bfloat16)
    dt1 = jax.nn.softplus(jax.random.normal(k[1], (1, T, W)) - 4.0)
    Bs, Cs = (jax.random.normal(k[i], (1, T, N)) for i in (3, 4))
    S0 = jax.random.normal(k[5], (1, N, W), jnp.float32)
    scale = tokens / T * L          # calls in the cell's set-up
    scan = {"calls_in_setup": scale, "least_s_in_setup": L * costs.ssm_scan_least_s(
        cfg, peaks, tokens), "bytes_least_s_in_setup": L * costs.ssm_scan_bytes(
        cfg, tokens) / peaks["hbm_bytes_per_s"]}
    t = timed(jax.jit(ssm1.selective_scan_pallas), u, dt1, a[0], Bs, Cs, S0,
              reps=100)
    scan["pallas_us_a_call"], scan["pallas_s_in_setup"] = 1e6 * t, t * scale
    t = timed(jax.jit(ssm1.selective_scan_reference), u, dt1, a[0], Bs, Cs,
              S0, reps=10)
    scan["xla_us_a_call"], scan["xla_s_in_setup"] = 1e6 * t, t * scale
    line["prefill_scan"] = scan

    # -- what an exponential costs here: the scan's peak, measured ----------
    line["exp_rate"] = exp_rate()

    # -- paged attention over ONE K/V head -----------------------------------
    H, D, deep, max_seq = s["heads"], s["head_dim"], 19_000, 24_576
    q = jax.random.normal(k[6], (B, H, D), jnp.bfloat16)
    lengths = jnp.full((B,), deep, jnp.int32)
    att = {"kv_least_ms": 1e3 * B * deep * 2 * D * 2
           / peaks["hbm_bytes_per_s"]}
    for bs in (32, 256):
        maxb = max_seq // bs
        nb = B * maxb + 1
        pool = jax.random.normal(k[7], (nb, bs, 1, D), jnp.bfloat16)
        tables = jnp.arange(B * maxb, dtype=jnp.int32).reshape(B, maxb)
        for impl in ("pallas", "xla"):
            f = jax.jit(lambda q, kp, vp, impl=impl: paged_decode_attention(
                q, kp, vp, tables, lengths, impl=impl))
            att[f"{impl}_block{bs}_ms"] = 1e3 * timed(f, q, pool, pool,
                                                      reps=30)
        if bs == 32:
            # the same pool, its blocks read in runs of 8: every slot's
            # blocks lie one after another, so its table is made of runs
            run = run_blocks(bs, 1, D, 2)
            pool8 = pool[:B * maxb]
            f = jax.jit(lambda q, kp, vp: paged_decode_attention(
                q, kp, vp, tables, lengths, impl="pallas", run=run))
            att[f"pallas_block{bs}_run{run}_ms"] = 1e3 * timed(
                f, q, pool8, pool8, reps=30)
            del pool8
        del pool
    line["paged_attention_1_kv_head"] = att

    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/jamba_kernel_readings.json", "w") as out:
        out.write(json.dumps(line) + "\n")
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
