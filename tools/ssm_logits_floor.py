"""The floor and the controls of ``nemotron-3-super-d11.long_decode_ssm``'s
check of logits AND state, read by the check ITSELF at the published
widths.

    chiprun -- python3 tools/ssm_logits_floor.py [--weights 6] [--seqs 4]
    python3 tools/ssm_logits_floor.py --tiny-cpu          # rehearsal

Every reading is ``benchmark/drivers/serve_closed_state.check_logits_state``
called on a stub of the server (the model, seeded params as an engine
holds them, an engine of 8 slots for its placement functions) at the
traffic file's ``correctness`` shape, and gives two numbers: the logits'
relative RMS (``logits_rel_rms``) and the first layer's state, its worst
head's (``state_worst_head_rel_rms``). HONEST readings over weight seeds
x sequence seeds give the floors (logits: five routers of top-22 of 512,
bf16 swaps near-tied experts; state: bf16 inputs against float32);
CONTROLS are read on two weight seeds each:

- ``int8_weights``: the REFERENCE with every matmul weight (the
  embedding and the head among them, the router not) through int8 with
  one scale per output channel: the nearest precision below the stated
  bf16 (the system's 9.3 GB of weights cannot stand twice on a chip);
- ``bf16_state``: the SYSTEM hands ``S`` on rounded to bf16 wherever a
  program hands it on, as a cache that held it in bf16 would (the rows
  stay float32 in memory, so the kernel runs; ``lax.reduce_precision``,
  not a pair of casts, which the TPU compiler may drop);
- ``state_not_written``: the SYSTEM's placement leaves the state rows as
  they were (an activation that forgot the row: zeros);
- ``through_padding``: the SYSTEM's prefill gets no lengths (the
  recurrence runs through the padding behind each prompt);
- the reference's other ``FAULTS`` (``no_gate``, ``no_D``,
  ``no_conv_bias``, ``no_scaling``): the honest system against a reference with the
  mechanism changed.

Prints one JSON line a reading and a summary line last.
"""
import argparse
import gc
import json
import os
import sys
import types

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--weights", type=int, default=6)
    ap.add_argument("--seqs", type=int, default=4)
    ap.add_argument("--tiny-cpu", action="store_true")
    args = ap.parse_args(argv)

    import jax

    from benchmark import run as harness
    from benchmark.builders import nemotron_h as builder
    from benchmark.drivers.serve_closed_state import check_logits_state
    from benchmark.reference.nemotron_h import FAULTS
    from ray_tpu.llm.engine import ContinuousBatchingEngine

    cc = harness.load_json(harness.HERE, "traffic",
                           "long_decode_ssm.json")["correctness"]
    cfg = harness.load_json(harness.ROOT,
                            "benchmark/configs/nemotron-3-super-d11.json")
    shape = dict(prompt_len=cc["prompt_len"], decode_steps=cc["decode_steps"],
                 state_steps=cc["state"]["decode_steps"])
    bs, max_seq = 32, 2048
    if args.tiny_cpu:
        cfg = {**cfg, **cfg["tiny_cpu"]}
        shape, bs, max_seq = dict(prompt_len=100, decode_steps=12,
                                  state_steps=40), 8, 256
    tols = dict(tol_rel_rms=cc["tolerance_rel_rms"],
                tol_state=cc["state"]["tolerance_worst_head_rel_rms"])
    model = builder.build_model(cfg, max_seq)

    def in_bf16(a):
        return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)

    class HandsOnBf16(type(model)):
        @staticmethod
        def _stack_state(mamba_outs, like):
            out = type(model)._stack_state(mamba_outs, like)
            return dict(out, ssm=in_bf16(out["ssm"])) if out else out

        def decode_step_paged_counted(self, *a, **kw):
            logits, pool, extras = super().decode_step_paged_counted(*a, **kw)
            return logits, dict(pool, ssm=in_bf16(pool["ssm"])), extras

    def server(model, params, **patch):
        eng = ContinuousBatchingEngine(model, params, max_slots=8,
                                       max_seq=max_seq, block_size=bs)
        for k, v in patch.items():
            setattr(eng, k, v)
        return types.SimpleNamespace(model=model, engine=eng)

    init = jax.jit(lambda key: model.serving_params(model.init(key)))
    honest_ref = builder.reference_forward(cfg)
    first_state = builder.reference_first_state(cfg)
    bf16_model = HandsOnBf16(model.cfg)
    readings = {}

    def one_set_of_weights(w: int) -> None:
        """Everything that holds the weights dies with this scope: 9.3 GB
        stand once on a chip."""
        params = init(jax.random.key(1000 + w))
        srv = server(model, params)
        runs = [("honest", srv, honest_ref, 7_000_000 + 13 * s + 101 * w)
                for s in range(args.seqs)]
        if w < 2:
            runs += [("bf16_state", server(bf16_model, params), honest_ref,
                      7_000_000 + 101 * w),
                     ("through_padding",
                      server(model, params, recurrent=False), honest_ref,
                      7_000_000 + 101 * w),
                     ("state_not_written",
                      server(model, params, _write_state_impl=(
                          lambda pool, state, slots: pool)), honest_ref,
                      7_000_000 + 101 * w)]
            runs += [(fault, srv, builder.reference_forward(cfg, fault),
                      7_000_000 + 101 * w)
                     for fault in FAULTS if fault != "bf16_state"]
        for kind, system, ref, seed in runs:
            r = check_logits_state(system, ref, first_state, seed=seed,
                                   **tols, **shape)
            for number in ("logits_rel_rms", "state_worst_head_rel_rms",
                           "state_conv_window_rel_rms"):
                readings.setdefault(number, {}).setdefault(kind, []).append(
                    r[number])
            print(json.dumps({"kind": kind, "weights": w, "seed": seed, **r}),
                  flush=True)

    for w in range(args.weights):
        one_set_of_weights(w)
        gc.collect()            # the engines' jitted methods are cycles
        jax.clear_caches()
    print(json.dumps({"summary": {
        number: {k: [min(v), max(v), len(v)] for k, v in kinds.items()}
        for number, kinds in readings.items()},
        "device": jax.devices()[0].device_kind, "tolerances": tols}))
    return 0


if __name__ == "__main__":
    from ray_tpu._private import platform
    platform.enable_compile_cache()
    sys.exit(main())
