"""The floor and the controls of ``jamba2-3b.long_decode_mamba1``'s check of
logits AND state, read by the check ITSELF at the published widths and
FULL depth (beside ``tools/ssm_logits_floor.py``, Nemotron's).

    chiprun -- python3 tools/ssm1_logits_floor.py [--weights 3] [--seqs 2]
    python3 tools/ssm1_logits_floor.py --tiny-cpu          # rehearsal

Every reading is ``benchmark/drivers/serve_closed_state.check_logits_state``
called on a stub of the server (the model, seeded params as an engine
holds them, an engine of 8 slots for its placement functions) at the
traffic file's ``correctness`` shape, and gives three numbers: the
logits' relative RMS, the first layer's state, its worst STATE INDEX's
(``state_worst_head_rel_rms``: ``JambaModel.state_heads`` groups by
``n``), and the convolution's window. This model has no router: the
honest readings over weight seeds x sequence seeds are bf16's own floor.
CONTROLS, on the first weight seed (``--controls`` of them):

- ``bf16_state``: the SYSTEM hands ``S`` on rounded to bf16 wherever a
  program hands it on, as a cache that held it in bf16 would (the rows
  stay float32 in memory, so the kernel runs; ``lax.reduce_precision``,
  not a pair of casts, which the TPU compiler may drop);
- ``through_padding``: the SYSTEM's prefill gets no lengths;
- ``state_not_written``: the SYSTEM's placement leaves the state rows as
  they were;
- the reference's ``FAULTS`` (``no_inner_norms``, ``no_dt_bias``,
  ``no_conv_bias``, ``scalar_A``, ``int8_weights``): the honest system
  against a reference with the mechanism changed (the state's own number
  then reads the reference's honest first layer: only the logits move).

Prints one JSON line a reading and a summary line last (also to
``chiprun_out/ssm1_logits_floor.json``).
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
    ap.add_argument("--weights", type=int, default=3)
    ap.add_argument("--seqs", type=int, default=2)
    ap.add_argument("--controls", type=int, default=1)
    ap.add_argument("--tiny-cpu", action="store_true")
    args = ap.parse_args(argv)

    import jax

    from benchmark import run as harness
    from benchmark.builders import jamba as builder
    from benchmark.drivers.serve_closed_state import check_logits_state
    from benchmark.reference.jamba import FAULTS
    from ray_tpu.llm.engine import ContinuousBatchingEngine

    cc = harness.load_json(harness.HERE, "traffic",
                           "long_decode_mamba1.json")["correctness"]
    cfg = harness.load_json(harness.ROOT, "benchmark/configs/jamba2-3b.json")
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
        def forward_step(self, *a, **kw):
            logits, cache = super().forward_step(*a, **kw)
            return logits, dict(cache, ssm=in_bf16(cache["ssm"]))

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
        """Everything that holds the weights dies with this scope."""
        params = init(jax.random.key(1000 + w))
        srv = server(model, params)
        runs = [("honest", srv, honest_ref, 7_000_000 + 13 * s + 101 * w)
                for s in range(args.seqs)]
        if w < args.controls:
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
    summary = json.dumps({"summary": {
        number: {k: [min(v), max(v), len(v)] for k, v in kinds.items()}
        for number, kinds in readings.items()},
        "device": jax.devices()[0].device_kind, "tolerances": tols})
    if not args.tiny_cpu:
        os.makedirs("chiprun_out", exist_ok=True)
        with open("chiprun_out/ssm1_logits_floor.json", "w") as out:
            out.write(summary + "\n")
    print(summary)
    return 0


if __name__ == "__main__":
    from ray_tpu._private import platform
    platform.enable_compile_cache()
    sys.exit(main())
