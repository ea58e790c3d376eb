"""The floor and the controls of ``xing4.0-29b-a4b-d5.long_decode_mhc``'s
logits check, read by the check ITSELF at the published widths.

    chiprun -- python3 tools/mhc_logits_floor.py [--seeds 6] [--first 0]
            [--decode-steps N] [--controls int8_weights,...] [--control-seeds 2]
    chiprun -- python3 tools/mhc_logits_floor.py --greedy SEED
    python3 tools/mhc_logits_floor.py --tiny-cpu          # rehearsal

Every reading is ``benchmark.lib.serving.check_logits`` called on a stub
of the server (the model, seeded params as an engine holds them, the
block size) at the traffic file's ``correctness`` shape: the relative RMS
of the paged bf16 logits against a float32 reference. HONEST readings
over seeds (each seeds its own weights and sequences) give the floor,
which is WHICH EXPERTS bf16 CHOSE (top-4 of 64 sigmoid scores, near-ties
swapped, each swap cascading through the later layers); ``forced`` gives
what is left when the reference is FORCED to the experts the program
chose, every position made by the counted decode step: the program's own
arithmetic. CONTROLS, each a deliberate departure of the REFERENCE
(``benchmark/reference/xing.py`` FAULTS) against the honest system, are
read on two seeds each: ``int8_weights`` (the nearest precision below the
stated bf16), and ISSUE 50's six: ``h_res_identity``,
``one_sinkhorn_round``, ``maps_in_bf16``, ``h_post_without_2``,
``no_mscale``, ``no_q_norm``.

``--greedy SEED``: what stands BEHIND THE GREEDY CHECK'S MARGIN. The
cell's own deployment (``benchmark.lib.serving.start`` at the traffic
file's engine, weights from SEED), the check's two greedy requests
streamed through the handle ONCE, and ``check_greedy``'s number, the
worst gap of a streamed token under the reference's first in RMS of its
logits (``token_gaps``), read under the honest reference and under each
control: a control whose worst gap stays inside the margin is one the
greedy check cannot refuse.

Prints one JSON line a reading and a summary line last; the lines go to
``chiprun_out/mhc_logits_floor.jsonl`` too.
"""
import argparse
import json
import os
import sys
import types

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CONTROLS = ("int8_weights", "h_res_identity", "one_sinkhorn_round",
            "maps_in_bf16", "h_post_without_2", "no_mscale", "no_q_norm")


def forced_reading(model, params, builder, cfg, seed: int, total: int,
                   last: int, bs: int) -> float:
    """Two sequences of ``total`` tokens, EVERY position by the counted
    decode step (the kernels at every length), the reference forced to
    the experts each step chose: relative RMS over the last ``last``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng([seed % (2**63), 778])
    seqs = jnp.asarray(rng.integers(1, model.cfg.vocab_size, (2, total)),
                       jnp.int32)
    nb = -(-total // bs)
    pool = model.init_kv_pool(2 * nb + 1, bs)
    tables = jnp.arange(2 * nb, dtype=jnp.int32).reshape(2, nb)
    step = jax.jit(model.decode_step_paged_counted, donate_argnums=(2,))
    logits, experts = [], []
    for pos in range(total):
        out, pool, extras = step(params, seqs[:, pos], pool, tables,
                                 jnp.full((2,), pos, jnp.int32))
        experts.append(extras["experts"])
        if pos >= total - last:
            logits.append(out)
    got = jnp.stack(logits, axis=1).astype(jnp.float32)
    forced = jnp.concatenate(experts, axis=2)            # [L, 2, total, K]
    want = jax.jit(lambda p, t, e: builder.reference_forward(cfg)(
        p, t, forced_experts=e)[:, total - last:])(params, seqs, forced)
    return float(jnp.sqrt(jnp.mean((got - want) ** 2) / jnp.mean(want ** 2)))


def greedy_readings(seed: int, controls, tiny: bool, say) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import ray_tpu

    from benchmark import run as harness
    from benchmark.builders import xing as builder
    from benchmark.lib import serving
    from benchmark.lib.bench_server import SERVERS
    from benchmark.lib.records import RequestRecord

    tr = harness.load_json(harness.HERE, "traffic", "long_decode_mhc.json")
    cfg = harness.load_json(harness.ROOT,
                            "benchmark/configs/xing4.0-29b-a4b-d5.json")
    eng, cc = tr["engine"], tr["correctness"]["greedy"]
    if tiny:
        cfg = {**cfg, **cfg["tiny_cpu"]}
        eng, cc = {**eng, "max_slots": 4, "max_seq": 1024}, {
            **cc, "prompt_lens": [96, 600]}
    ray_tpu.init()
    handle = serving.start(builder.program_config(cfg, eng["max_seq"]),
                           model_id="mhc-greedy-controls", engine=eng,
                           seed=seed % (2**31 - 1))
    params = SERVERS[-1].engine.params
    worst = {}
    for k, plen in enumerate(cc["prompt_lens"]):
        prompt = serving.make_prompt(seed, 600_000 + k, int(plen),
                                     cfg["vocab_size"])
        rec = RequestRecord(index=-4, due_at=0.0)
        toks = serving.stream_request(handle, prompt, cc["tokens"], rec)
        assert not rec.error and len(toks) == cc["tokens"], rec.error
        for fault in (None,) + tuple(controls):
            want = jax.jit(lambda p, t, a=int(plen) - 1, f=fault: (
                builder.reference_forward(cfg, f)(p, t)[
                    0, a:a + cc["tokens"]]))(
                        params, jnp.asarray([prompt + toks], jnp.int32))
            gaps = serving.token_gaps(np.asarray(want), toks)
            kind = fault or "honest"
            worst[kind] = max(worst.get(kind, 0.0), max(gaps))
            say({"kind": "greedy_" + kind, "seed": seed, "prompt_len": plen,
                 "gaps_rel_rms": gaps})
    say({"greedy_worst_gap_rel_rms": worst, "seed": seed,
         "margin_rel_rms": cc["margin_rel_rms"],
         "device": jax.devices()[0].device_kind})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, default=6)
    ap.add_argument("--first", type=int, default=0,
                    help="the first seed's number (other seeds than an "
                         "earlier call's)")
    ap.add_argument("--decode-steps", type=int, default=None,
                    help="in place of the traffic file's")
    ap.add_argument("--controls", default=",".join(CONTROLS))
    ap.add_argument("--control-seeds", type=int, default=2)
    ap.add_argument("--tiny-cpu", action="store_true")
    ap.add_argument("--greedy", type=int, default=None, metavar="SEED")
    args = ap.parse_args(argv)
    controls = tuple(c for c in args.controls.split(",") if c)
    if args.greedy is not None:
        os.makedirs("chiprun_out", exist_ok=True)
        with open("chiprun_out/mhc_greedy_controls.jsonl", "a") as out:
            def tell(line):
                print(json.dumps(line), flush=True)
                out.write(json.dumps(line) + "\n")
            greedy_readings(args.greedy, controls, args.tiny_cpu, tell)
        return 0

    import jax

    from benchmark import run as harness
    from benchmark.builders import xing as builder
    from benchmark.lib.serving import check_logits

    tr = harness.load_json(harness.HERE, "traffic", "long_decode_mhc.json")
    cc = tr["correctness"]
    cfg = harness.load_json(harness.ROOT,
                            "benchmark/configs/xing4.0-29b-a4b-d5.json")
    shape = dict(prompt_len=cc["prompt_len"],
                 decode_steps=args.decode_steps or cc["decode_steps"])
    bs, max_seq, forced_total = tr["engine"]["block_size"], 8192, 1088
    if args.tiny_cpu:
        cfg = {**cfg, **cfg["tiny_cpu"]}
        shape, bs, max_seq, forced_total = dict(
            prompt_len=96, decode_steps=8), 8, 256, 40
    model = builder.build_model(cfg, max_seq)
    init = jax.jit(lambda key: model.serving_params(model.init(key)))
    readings = {}
    os.makedirs("chiprun_out", exist_ok=True)
    out = open("chiprun_out/mhc_logits_floor.jsonl", "w")

    def say(line):
        print(json.dumps(line), flush=True)
        out.write(json.dumps(line) + "\n")
        out.flush()

    for s in range(args.first, args.first + args.seeds):
        seed = 50_000_000 + 7919 * s
        params = init(jax.random.key(seed % (2**31 - 1)))
        srv = types.SimpleNamespace(model=model, engine=types.SimpleNamespace(
            params=params, block_size=bs))
        kinds = (None,) + (
            controls if s - args.first < args.control_seeds else ())
        for fault in kinds:
            r = check_logits(srv, builder.reference_forward(cfg, fault),
                             seed=seed, tol_rel_rms=cc["tolerance_rel_rms"],
                             **shape)
            kind = fault or "honest"
            readings.setdefault(kind, []).append(r["logits_rel_rms"])
            say({"kind": kind, "seed": seed, **r})
        if s - args.first < 3 and controls == CONTROLS:
            f = forced_reading(model, params, builder, cfg, seed,
                               forced_total, shape["decode_steps"], bs)
            readings.setdefault("forced", []).append(f)
            say({"kind": "forced", "seed": seed, "logits_rel_rms": f,
                 "positions_by_decode_steps": forced_total})
        del params, srv
    say({"summary": {k: [min(v), max(v), len(v)] for k, v in readings.items()},
         "device": jax.devices()[0].device_kind,
         "tolerance_rel_rms": cc["tolerance_rel_rms"], **shape})
    return 0


if __name__ == "__main__":
    from ray_tpu._private import platform
    platform.enable_compile_cache()
    sys.exit(main())
