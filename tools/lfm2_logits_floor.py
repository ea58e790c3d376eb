"""The floor and the controls of ``lfm2-24b-a2b-d10.long_decode_shortconv``'s
check of logits, routing AND state, read by the check ITSELF at the
published widths and depth 10 (beside ``tools/ssm1_logits_floor.py``,
Jamba's).

    chiprun -- python3 tools/lfm2_logits_floor.py [--weights 3] [--seqs 2]
        [--controls 1] [--only int8_weights,no_qk_norm] [--first 0]
    python3 tools/lfm2_logits_floor.py --tiny-cpu          # rehearsal

Every reading is ``benchmark/drivers/serve_closed_conv.check_logits_state``
called on a stub of the server (the model, seeded params as an engine
holds them, an engine of 8 slots for its placement functions) at the
traffic file's ``correctness`` shape, and gives the three numbers the
cell limits: ``logits_rel_rms`` (against the reference FORCED to the
system's experts: the arithmetic), ``routing_agreement`` (the share of
choices at which the reference's own router takes the system's set) and
the first conv layer's state, its worst ROW's
(``state_conv_worst_row_rel_rms``); and beside them what the reference
reads when it ROUTES BY ITSELF (``logits_rel_rms_own_routing``,
``routing_agreement_own_routing``): under bf16 compute the router's 4th
and 5th choice swap where nearly tied and every layer behind a swap goes
another way, the routing floor that the forced comparison takes out.
CONTROLS, on the first weight seed (``--controls`` of them):

- ``through_padding``: the SYSTEM's prefill gets no lengths (the filter's
  rows are then the padding's);
- ``state_not_written``: the SYSTEM's placement leaves the state rows as
  they were (activation leaves zeros);
- ``state_stale``: the SYSTEM's decode step hands the first conv layer's
  rows back as it got them (never shifted), and ``state_fp8``: it holds
  every conv layer's rows at an 8-bit float's three bits of mantissa
  (``float8_e4m3``'s, at bf16's exponents), the nearest precision below
  the stated bf16: the two readings ABOVE the state's
  limit (its memory is two positions: nothing the prefill or activation
  did wrong is still in it after the check's steps);
- the reference's ``FAULTS`` (``no_B_gate``, ``no_C_gate``,
  ``last_tap_only``, ``taps_reversed``, ``no_expert_bias``,
  ``softmax_router``, ``no_renorm``, ``no_qk_norm``,
  ``dense_layers_as_experts``, ``int8_weights``): the honest system
  against a reference with the mechanism changed (the state's own number
  then reads the reference's honest first layer: the logits or the
  agreement move).

Prints one JSON line a reading and a summary line last (also to
``chiprun_out/lfm2_logits_floor.json``).
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
    ap.add_argument("--only", default="",
                    help="comma-separated controls to run (default: all)")
    ap.add_argument("--first", type=int, default=0,
                    help="the first weight seed (a later call's readings "
                         "are then new ones)")
    ap.add_argument("--tiny-cpu", action="store_true")
    args = ap.parse_args(argv)

    import jax

    from benchmark import run as harness
    from benchmark.builders import lfm2 as builder
    from benchmark.drivers.serve_closed_conv import check_logits_state
    from benchmark.reference.lfm2 import FAULTS
    from ray_tpu.llm.engine import ContinuousBatchingEngine

    cc = harness.load_json(harness.HERE, "traffic",
                           "long_decode_shortconv.json")["correctness"]
    cfg = harness.load_json(harness.ROOT,
                            "benchmark/configs/lfm2-24b-a2b-d10.json")
    shape = dict(prompt_len=cc["prompt_len"], decode_steps=cc["decode_steps"],
                 state_steps=cc["state"]["decode_steps"])
    bs, max_seq = 32, 2048
    if args.tiny_cpu:
        cfg = {**cfg, **cfg["tiny_cpu"]}
        shape, bs, max_seq = dict(prompt_len=100, decode_steps=12,
                                  state_steps=20), 8, 256
    tols = dict(tol_rel_rms=cc["tolerance_rel_rms"],
                min_routing_agreement=cc["routing"]["min_agreement"],
                tol_state=cc["state"]["tolerance_worst_head_rel_rms"])
    model = builder.build_model(cfg, max_seq)

    def server(params, step=None, **patch):
        eng = ContinuousBatchingEngine(model, params, max_slots=8,
                                       max_seq=max_seq, block_size=bs)
        for k, v in patch.items():
            setattr(eng, k, v)
        return types.SimpleNamespace(
            model=model if step is None else SteppedBy(model, step),
            engine=eng)

    class SteppedBy:
        """``model`` with ``step(pool before, pool after) -> pool`` behind
        its decode step."""

        def __init__(self, model, step):
            self._model, self._step = model, step

        def __getattr__(self, name):
            return getattr(self._model, name)

        def decode_step_paged_counted(self, params, tokens, pool, *a, **kw):
            logits, after, extras = self._model.decode_step_paged_counted(
                params, tokens, pool, *a, **kw)
            return logits, self._step(pool, after), extras

    def stale(before, after):
        return dict(after, conv=after["conv"].at[0].set(before["conv"][0]))

    def fp8(before, after):
        # (``reduce_precision`` and not a cast there and back, which XLA
        # drops on the chip as excess precision)
        return dict(after, conv=jax.lax.reduce_precision(
            after["conv"], exponent_bits=8, mantissa_bits=3))

    init = jax.jit(lambda key: model.serving_params(model.init(key)))
    honest_ref = builder.reference_forward(cfg)
    first_state = builder.reference_first_state(cfg)
    readings = {}

    def one_set_of_weights(w: int) -> None:
        """Everything that holds the weights dies with this scope."""
        params = init(jax.random.key(1000 + w))
        srv = server(params)
        runs = [("honest", srv, honest_ref, 7_000_000 + 13 * s + 101 * w)
                for s in range(args.seqs)]
        if w - args.first < args.controls:
            controls = [("through_padding", server(params, recurrent=False),
                         honest_ref),
                        ("state_not_written",
                         server(params, _write_state_impl=(
                             lambda pool, state, slots: pool)), honest_ref),
                        ("state_stale", server(params, stale), honest_ref),
                        ("state_fp8", server(params, fp8), honest_ref)]
            controls += [(fault, srv, builder.reference_forward(cfg, fault))
                         for fault in FAULTS]
            only = set(filter(None, args.only.split(",")))
            runs += [(*c, 7_000_000 + 101 * w) for c in controls
                     if not only or c[0] in only]
        for kind, system, ref, seed in runs:
            r = check_logits_state(system, ref, first_state, seed=seed,
                                   **tols, **shape)
            for number in ("logits_rel_rms", "routing_agreement",
                           "state_conv_worst_row_rel_rms",
                           "logits_rel_rms_own_routing",
                           "routing_agreement_own_routing"):
                readings.setdefault(number, {}).setdefault(kind, []).append(
                    r[number])
            print(json.dumps({"kind": kind, "weights": w, "seed": seed, **r}),
                  flush=True)

    for w in range(args.first, args.first + args.weights):
        one_set_of_weights(w)
        gc.collect()            # the engines' jitted methods are cycles
        jax.clear_caches()
    summary = json.dumps({"summary": {
        number: {k: [min(v), max(v), len(v)] for k, v in kinds.items()}
        for number, kinds in readings.items()},
        "device": jax.devices()[0].device_kind, "tolerances": tols})
    if not args.tiny_cpu:
        os.makedirs("chiprun_out", exist_ok=True)
        with open("chiprun_out/lfm2_logits_floor.json", "w") as out:
            out.write(summary + "\n")
    print(summary)
    return 0


if __name__ == "__main__":
    from ray_tpu._private import platform
    platform.enable_compile_cache()
    sys.exit(main())
