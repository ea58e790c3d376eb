"""The chunked scan's share of its roofline and of the chunk-prefill
program, from a TRACED SET-UP: the kernel runs only in
``nemotron-3-super-d11.long_decode_ssm``'s set-up, so no metric of the
window can read it (PERF.md, Where the time goes).

    chiprun -- python3 tools/ssm_scan_share.py
    python3 tools/ssm_scan_share.py --tiny-cpu          # rehearsal

An engine of the cell's own shape admits ONE prompt of the cell's 8,192
tokens (16 chunks of 512 through ``_prefill_chunk``, each 4 scan chunks
of 128 a Mamba layer, the state carried) under ``jax.profiler``. From
the trace: the time of the ``prefill_with_prefix`` program calls (``XLA
Modules``). The trace's operations (``XLA Ops``: fusions by number)
carry no scope in their statistics on this stack, so the scan's own time
is read ALONE at the same shapes (``ops.ssm.chunked_scan``, one row of
512 positions from a carried state, timed over 200 calls) and set
against ``costs.ssm_scan_flops`` / ``ssm_scan_bytes``, what the scan
NEEDS, and against the traced program's time. Prints one JSON line.
"""
import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=2900000047)
    ap.add_argument("--tiny-cpu", action="store_true")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from benchmark import run as harness
    from benchmark.builders import nemotron_h as builder
    from benchmark.costs import ssm_latent_moe_transformer as costs
    from benchmark.lib import serving, trace
    from benchmark.lib.peaks import peaks_for
    from ray_tpu.llm.engine import ContinuousBatchingEngine, SamplingParams
    from ray_tpu.ops import ssm

    traffic = harness.load_json(harness.HERE, "traffic",
                                "long_decode_ssm.json")
    cfg = harness.load_json(harness.ROOT,
                            "benchmark/configs/nemotron-3-super-d11.json")
    eng_kw = {k: traffic["engine"][k]
              for k in ("max_slots", "max_seq", "block_size")}
    prompt_len = traffic["prompt_len"]["value"]
    if args.tiny_cpu:
        cfg = {**cfg, **cfg["tiny_cpu"]}
        eng_kw, prompt_len = dict(max_slots=4, max_seq=2048, block_size=8), 1500
    model = builder.build_model(cfg, eng_kw["max_seq"])
    params = jax.jit(lambda key: model.serving_params(model.init(key)))(
        jax.random.key(args.seed % (2**31 - 1)))
    eng = ContinuousBatchingEngine(model, params, **eng_kw)
    chunk = eng.buckets[-1]

    def admit(index: int):
        req = eng.submit(serving.make_prompt(args.seed, index, prompt_len,
                                             cfg["vocab_size"]),
                         SamplingParams(max_tokens=2))
        while not req.output:
            eng.step()
        jax.block_until_ready(eng.kv)

    admit(0)                                   # every chunk shape compiled
    trace_dir = tempfile.mkdtemp(prefix="ssm_scan_")
    jax.profiler.start_trace(trace_dir)
    admit(1)
    jax.profiler.stop_trace()

    chunks = -(-prompt_len // chunk)
    layers = cfg["hybrid_override_pattern"].count("M")
    out = {"device": jax.devices()[0].device_kind, "prompt_len": prompt_len,
           "chunk": chunk, "chunks": chunks, "mamba_layers": layers}
    planes = trace.load_planes(trace_dir)
    for plane, lines in planes.items():
        if trace.DEVICE_PLANE.match(plane):
            calls = [d for name, _, d in lines.get(trace.MODULES_LINE, [])
                     if "prefill_with_prefix" in name]
            out["prefill_program_calls"] = len(calls)
            out["prefill_program_s"] = sum(calls) / 1e9

    # the scan alone, at one layer's shapes of one chunk
    c = model.cfg
    H, P, G, N = c.mamba_heads, c.mamba_head_dim, c.ssm_groups, c.ssm_state
    keys = jax.random.split(jax.random.key(1), 6)
    x = jax.random.normal(keys[0], (1, chunk, H, P), c.dtype)
    dt = jax.nn.softplus(jax.random.normal(keys[1], (1, chunk, H)) - 4.0)
    a = -jnp.exp(jax.random.uniform(keys[2], (H,), minval=0.0, maxval=2.7))
    Bm, Cm = (jax.random.normal(k, (1, chunk, G, N), c.dtype)
              for k in keys[3:5])
    S0 = jax.random.normal(keys[5], (1, G, N, (H // G) * P), jnp.float32)
    alone = jax.jit(lambda *v: ssm.chunked_scan(*v, chunk=c.scan_chunk,
                                                dtype=c.dtype))
    jax.block_until_ready(alone(x, dt, a, Bm, Cm, S0))
    reps = 3 if args.tiny_cpu else 200
    t0 = time.perf_counter()
    for _ in range(reps):
        y, S1 = alone(x, dt, a, Bm, Cm, S0)
    jax.block_until_ready((y, S1))
    out["scan_alone_s_per_call"] = (time.perf_counter() - t0) / reps

    flops = costs.ssm_scan_flops(cfg, chunk)
    nbytes = costs.ssm_scan_bytes(cfg, chunk)
    out.update(scan_flops_per_call=flops, scan_bytes_per_call=nbytes)
    if not args.tiny_cpu:           # a CPU run names no share of a peak
        peaks = peaks_for(out["device"])
        least = max(flops / peaks["bf16_flops_per_s"],
                    nbytes / peaks["hbm_bytes_per_s"])
        out["scan_least_s_per_call"] = least
        out["scan_roofline_alone_pct"] = 100 * least / out[
            "scan_alone_s_per_call"]
        # were the scan in the program what it is alone
        out["scan_share_of_prefill_program_pct"] = (
            100 * out["scan_alone_s_per_call"] * chunks * layers
            / out["prefill_program_s"])
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    from ray_tpu._private import platform
    platform.enable_compile_cache()
    sys.exit(main())
