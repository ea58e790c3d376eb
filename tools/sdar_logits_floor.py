"""The floor and the controls of ``sdar-30b-a3b-chat-d6.block_decode``'s
two checks, on the chip at the published widths (depth 6):

    chiprun -- python3 tools/sdar_logits_floor.py --seeds 8 \\
        --faults int8_weights,causal_in_block,skip_commit,...
    python3 tools/sdar_logits_floor.py --tiny-cpu --seeds 1   # rehearsal

Per seed: weights from the seed, an engine as the cell's (fewer slots:
the checks bring their own pool), then ``drivers/serve_closed_blocks.py``'s
``check_logits_blocks`` against the honest reference and against each
planted fault (``benchmark/reference/sdar.py`` ``FAULTS``), and the
greedy check's measure (``stream_gaps``) on what the ENGINE generated
for the cell's two greedy prompts, honest and planted. One JSON line a
reading on stdout, all of them in ``--out``."""

import argparse
import gc
import json
import os
import sys
import time
import types

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, default=4)
    ap.add_argument("--first-seed", type=int, default=2147484001)
    ap.add_argument("--faults", default="")
    ap.add_argument("--greedy", type=int, default=1)
    ap.add_argument("--tiny-cpu", action="store_true")
    ap.add_argument("--out", default="chiprun_out/sdar_logits_floor.jsonl")
    args = ap.parse_args()
    if args.tiny_cpu:
        from ray_tpu._private.platform import force_cpu_platform
        force_cpu_platform(1)
    import jax

    from ray_tpu._private.platform import enable_compile_cache
    from ray_tpu.llm.engine import ContinuousBatchingEngine, SamplingParams

    from benchmark import run as harness
    from benchmark.builders import sdar
    from benchmark.drivers import serve_closed_blocks as driver
    from benchmark.lib import serving

    enable_compile_cache()
    cfg = harness.load_json(harness.ROOT,
                            "benchmark/configs/sdar-30b-a3b-chat-d6.json")
    traffic = harness.load_json(harness.HERE, "traffic", "block_decode.json")
    if args.tiny_cpu:
        cfg = {**cfg, **cfg["tiny_cpu"]}
    cc, eng = traffic["correctness"], traffic["engine"]
    model = sdar.build_model(cfg, eng["max_seq"])
    faults = [None] + [f for f in args.faults.split(",") if f]
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    out = open(args.out, "a")

    def say(**reading):
        line = json.dumps(reading)
        print(line, flush=True)
        out.write(line + "\n")
        out.flush()

    for k in range(args.seeds):
        seed = args.first_seed + 7919 * k
        t0 = time.perf_counter()
        params = jax.jit(lambda key: model.serving_params(model.init(key)))(
            jax.random.key(seed % (2**31 - 1)))
        server = types.SimpleNamespace(
            model=model, engine=ContinuousBatchingEngine(
                model, params, max_slots=4, max_seq=eng["max_seq"],
                block_size=eng["block_size"]))
        streams = []
        if args.greedy:
            for j, (plen, n) in enumerate(zip(cc["greedy"]["prompt_lens"],
                                              cc["greedy"]["tokens"])):
                prompt = serving.make_prompt(seed, 600_000 + j, plen,
                                             model.cfg.mask_token_id)
                req, = server.engine.generate(
                    [prompt], SamplingParams(max_tokens=n))
                streams.append((prompt, req.output, req.unmasked_at))
        for fault in faults:
            reference = sdar.reference_teacher_forced(cfg, fault)
            checks = driver.check_logits_blocks(
                server, *reference, seed=seed, prompt_len=cc["prompt_len"],
                blocks=cc["blocks"], tol_rel_rms=cc["tolerance_rel_rms"])
            gaps = [max(driver.stream_gaps(model.cfg, params, *reference,
                                           *stream)) for stream in streams]
            say(seed=seed, fault=fault,
                greedy_worst_gap_rel_rms=max(gaps, default=None),
                seconds=time.perf_counter() - t0,
                **{key: checks[key] for key in checks
                   if key.startswith("logits_") or key == "argmax_agreement"})
        # the next seed's weights need this seed's gone from the chip
        del server, params, reference, checks, streams
        gc.collect()


if __name__ == "__main__":
    main()
