"""The timed path of ``nemotron-3-super-d11.long_decode_ssm`` compared
TIGHTLY, at the timed sizes, past any router.

    chiprun -- python3 tools/ssm_timed_path_check.py            # ~6 min
    chiprun -- python3 tools/ssm_timed_path_check.py --fault    # the control
    python3 tools/ssm_timed_path_check.py --tiny-cpu [--fault]  # rehearsal

Why it exists. The cell's own ``correct`` cannot hold its timed path
tightly: five routers of top-22 of 512 stand before the logits and with
seeded weights bf16 swaps near-tied experts (the tolerance's reason in
``benchmark/traffic/long_decode_ssm.json``). But the FIRST LAYER of the
cell's pattern (``MEMEMEMEM*E``) is a Mamba-2 layer, and what a slot
holds of it, ``S`` and the convolution's window, is a function of the
embedding and that one mixer: no router stands before it, nothing
cascades, and bf16 against float32 reads a few 1e-3.

What it does. An engine of the cell's own shape (64 slots x 14,336,
block 32) takes 64 prompts of 8,192 tokens through its own chunked
prefills (16 chunks of 512 a prompt = 4 scan chunks each, every chunk
starting from the state the chunk before left, activation writing the
slot's row) and then decodes some tens of tokens in all 64 slots (the
Mosaic state-update kernel rewriting every slot's row in place). Layer
0's state rows of three slots are compared with
``benchmark/reference/nemotron_h.py``'s float32 recurrence, a position
at a time, over the tokens the slot's state has consumed (the prompt and
the generated tokens fed back): ``ssm`` (through
``model.state_heads``: [H, P, N]) and ``conv`` (the last 3 inputs of the
convolution). The attention layer's K/V rows (layer 9, behind four
routers) are compared too and only REPORTED: the routers' floor is in
them.

``--fault`` plants one fault in the model (a chunk of a chunked prefill
hands on a ZERO ``S`` instead of the one it reached, so activation
writes a row that holds the decode steps' inputs alone): the rows must
then read far off. (Dropping only the CARRY between chunks is a weaker
control than it sounds: most heads forget within a chunk of 512.) Prints one JSON line; exit 1
where the honest rows pass ``LIMIT`` or the faulty ones do not.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

LIMIT = 0.02            # relative RMS of a slot's layer-0 state
N_DECODE = 40


def rel(got, want) -> float:
    import numpy as np
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2) / np.mean(want ** 2)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=3300000101)
    ap.add_argument("--fault", action="store_true")
    ap.add_argument("--tiny-cpu", action="store_true")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import run as harness
    from benchmark.builders import nemotron_h as builder
    from benchmark.lib import serving
    from ray_tpu.llm.engine import ContinuousBatchingEngine, SamplingParams

    traffic = harness.load_json(harness.HERE, "traffic",
                                "long_decode_ssm.json")
    cfg = harness.load_json(harness.ROOT,
                            "benchmark/configs/nemotron-3-super-d11.json")
    eng_kw = {k: traffic["engine"][k]
              for k in ("max_slots", "max_seq", "block_size")}
    prompt_len, n_decode, slots = (traffic["prompt_len"]["value"], N_DECODE,
                                   (0, 29, 63))
    if args.tiny_cpu:
        cfg = {**cfg, **cfg["tiny_cpu"]}
        eng_kw = dict(max_slots=4, max_seq=1024, block_size=8)
        prompt_len, n_decode, slots = 700, 6, (0, 3)

    model = builder.build_model(cfg, eng_kw["max_seq"])
    params = jax.jit(lambda key: model.serving_params(model.init(key)))(
        jax.random.key(args.seed % (2**31 - 1)))

    class Faulty(type(model)):
        def prefill_with_prefix(self, *a, **kw):
            logits, small = super().prefill_with_prefix(*a, **kw)
            return logits, dict(small, ssm=jnp.zeros_like(small["ssm"]))

    layer0 = jax.jit(builder.reference_first_state(cfg))

    reference = jax.jit(lambda p, t: builder.reference_forward(cfg)(
        p, t, with_kept=True)[1]["attn"])

    eng = ContinuousBatchingEngine(
        Faulty(model.cfg) if args.fault else model, params, **eng_kw)
    reqs = [eng.submit(serving.make_prompt(args.seed, 700_000 + i,
                                           prompt_len, cfg["vocab_size"]),
                       SamplingParams(max_tokens=10**6))
            for i in range(eng_kw["max_slots"])]
    while min(len(r.output) for r in reqs) < n_decode:
        eng.step()

    stats = eng.stats
    out = {"fault": args.fault, "device": jax.devices()[0].device_kind,
           "decode_attention_impl": eng.decode_attention_impl,
           "decode_steps": stats["decode_steps"],
           "state_chunks_carried": stats["state_chunks_carried"],
           "state_rows_written": stats["state_rows_written"], "slots": {}}
    # a step dispatched ahead has consumed the newest token too
    ahead = eng._in_flight is not None
    bs, worst = eng.block_size, 0.0
    for n, slot in enumerate(slots):
        req = eng.slots[slot]
        toks = req.prompt + req.output[:len(req.output) - (not ahead)]
        S = len(toks)
        got_s = np.asarray(model.state_heads(eng.kv["ssm"][0, slot]))
        got_w = np.asarray(eng.kv["conv"][0, slot].astype(jnp.float32))
        want_s, want_w = jax.device_get(
            layer0(params, jnp.asarray([toks], jnp.int32)))
        row = {"tokens": S, "ssm": rel(got_s, want_s),
               "conv": rel(got_w, want_w)}
        worst = max(worst, row["ssm"], row["conv"])
        if n == 0 and "*" in cfg["hybrid_override_pattern"]:
            # reported, not held: four routers stand before these rows
            written = S - ahead          # the step ahead's row is in flight
            ids = jnp.asarray(eng._tables[slot, :-(-written // bs)])
            kv = jax.device_get(reference(
                params, jnp.asarray([toks[:written]], jnp.int32)))[0]
            for name in ("k", "v"):
                rows = eng.kv[name][0][ids]
                rows = np.asarray(rows.reshape(
                    -1, *rows.shape[2:])[:written].astype(jnp.float32))
                row[f"{name}_rows_behind_routers"] = rel(rows, kv[name][0])
        out["slots"][str(slot)] = row
    out["worst"], out["limit"] = worst, LIMIT
    out["ok"] = (worst > 10 * LIMIT) if args.fault else (worst <= LIMIT)
    print(json.dumps(out))
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    from ray_tpu._private import platform
    platform.enable_compile_cache()
    sys.exit(main())
