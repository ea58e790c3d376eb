"""The timed path of ``kanana-2-30b-a3b-d5.long_decode_mla`` compared TIGHTLY,
at the timed sizes, past the router.

    chiprun -- python3 tools/mla_timed_path_check.py            # ~2 min
    chiprun -- python3 tools/mla_timed_path_check.py --fault    # the control
    python3 tools/mla_timed_path_check.py --tiny-cpu [--fault]  # rehearsal

Why it exists. The cell's own ``correct`` cannot hold its timed path tightly
(PERF.md section 7 (z), ROADMAP Y2): with seeded weights this block's routing
cascades, the logits check's floor is 0.15-0.27 and the greedy margin stands
where a fault of the whole attention passes two to five runs of nine. But the FIRST
EXPERT LAYER'S LATENT CACHE ROWS (layer 1: ``c | k_pe``, one row of ``"k"``
since PR 45) are a function
of layer 0 alone: the embedding, layer 0's latent attention over every earlier
row, and the DENSE FFN. No router stands before them, nothing cascades, and
bf16 against float32 reads under 0.01.

What it does. An engine of the cell's own shape (32 slots x 24,576, block 32:
``benchmark/traffic/long_decode_mla.json``) takes 32 prompts of 16,384 tokens
through its own chunked prefills (32 chunks of 512 a prompt, each gathering
the slot's latent blocks and up-projecting them: ``prefill_with_prefix``) and
then decodes 48 tokens in all 32 slots (the Mosaic kernel over 513+ blocks a
slot, nine chunks of its loop). The pool's layer-1 rows of three slots, read
through the engine's block tables, are compared with
``benchmark/reference/kanana.py``'s float32 arithmetic over the slot's prompt
+ generated tokens:

- prompt rows 512..16,383: the chunked prefill with its prefix gather;
- decode rows 16,384..: the row a decode step writes in layer 1 is made from
  layer 0's ABSORBED attention over the slot's pages in that very step.

``--fault`` plants two faults in the model (the decode attention reads the
kernel's first chunk of 2,048 rows only; a chunk prefill's gathered prefix
past 2,048 rows reads as zeros): the rows must then read FAR off. Prints one
JSON line; exit 1 where the honest rows pass ``LIMIT`` or the faulty ones
do not. It sees layer 0's attention only (the expert layers run the same
body at another ``first_block``), and neither the router nor the experts.

Readings (chip, PR 41): honest layer-1 ``c`` 0.0073 (prefix chunks), 0.0074-
0.0075 (decode rows), worst row 0.0115; faulty 0.74 and 0.81-0.84. PR 45 (the
one row, one copy a page): PERF.md section 6.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

LIMIT = 0.03            # relative RMS of a group of layer-1 rows; 4 x honest
N_DECODE = 48


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
    from benchmark.builders import kanana as builder
    from benchmark.lib import serving
    from benchmark.reference import kanana as R
    from ray_tpu.llm.engine import ContinuousBatchingEngine, SamplingParams

    traffic = harness.load_json(harness.HERE, "traffic",
                                "long_decode_mla.json")
    cfg = harness.load_json(harness.ROOT,
                            "benchmark/configs/kanana-2-30b-a3b-d5.json")
    eng_kw = {k: traffic["engine"][k]
              for k in ("max_slots", "max_seq", "block_size")}
    prompt_len, n_decode = traffic["prompt_len"]["value"], N_DECODE
    first, cut, slots = 512, 2048, (0, 13, 31)
    if args.tiny_cpu:
        cfg = {**cfg, **cfg["tiny_cpu"]}
        eng_kw = dict(max_slots=4, max_seq=1024, block_size=8)
        prompt_len, n_decode, first, cut, slots = 700, 6, 64, 64, (0, 3)
    rank, rope = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    theta, eps = float(cfg["rope_theta"]), float(cfg["rms_norm_eps"])

    model = builder.build_model(cfg, eng_kw["max_seq"])
    params = jax.jit(lambda key: model.serving_params(model.init(key)))(
        jax.random.key(args.seed % (2**31 - 1)))

    class Faulty(type(model)):
        def _attend_pages(self, q, k_pool, v_pool, layer, block_tables,
                          lengths, **kw):
            return super()._attend_pages(
                q, k_pool, v_pool, layer, block_tables,
                jnp.minimum(lengths, cut), **kw)

        def prefill_with_prefix(self, params, tokens, prefix_k, prefix_v,
                                *a, **kw):
            return super().prefill_with_prefix(
                params, tokens, prefix_k.at[:, :, cut:].set(0),
                prefix_v.at[:, :, cut:].set(0), *a, **kw)

    @jax.jit
    def layer1_rows(params, tokens):
        """Layer 1's latent rows (c, k_pe) of tokens [1, S] in float32, by
        the reference's own pieces: layer 0 whole, then layer 1's norm,
        down-projection, ``kv_a_layernorm`` and RoPE."""
        rp = builder.reference_params(cfg, params)
        with jax.default_matmul_precision("highest"):
            x = R._f32(rp["embed"][tokens])
            lp = {n: a[0] for n, a in rp["dense_layers"].items()}
            h = R._rms_norm(x, R._f32(lp["attn_norm"]), eps)
            x = x + R._latent_attention(
                h, lp, nope=cfg["qk_nope_head_dim"], rope=rope, rank=rank,
                theta=theta, eps=eps, fault=None)
            x = x + R._swiglu(R._rms_norm(x, R._f32(lp["mlp_norm"]), eps),
                              lp["gate"], lp["up"], lp["down"])
            lp = {n: rp["moe_layers"][n][0]
                  for n in ("attn_norm", "kv_a_proj", "kv_a_layernorm")}
            down = (R._rms_norm(x, R._f32(lp["attn_norm"]), eps)
                    @ R._f32(lp["kv_a_proj"]))
            return (R._rms_norm(down[..., :rank],
                                R._f32(lp["kv_a_layernorm"]), eps),
                    R._rope(down[..., None, rank:], theta)[:, :, 0])

    eng = ContinuousBatchingEngine(
        Faulty(model.cfg) if args.fault else model, params, **eng_kw)
    reqs = [eng.submit(serving.make_prompt(args.seed, 700_000 + i,
                                           prompt_len, cfg["vocab_size"]),
                       SamplingParams(max_tokens=10**6))
            for i in range(eng_kw["max_slots"])]
    while min(len(r.output) for r in reqs) < n_decode:
        eng.step()

    out = {"fault": args.fault, "device": jax.devices()[0].device_kind,
           "decode_attention_impl": eng.decode_attention_impl,
           "decode_steps": eng.stats["decode_steps"], "slots": {}}
    bs, worst = eng.block_size, 0.0
    for slot in slots:
        req = eng.slots[slot]
        toks = req.prompt + req.output[:len(req.output) - 1]   # rows written
        S = len(toks)
        ids = jnp.asarray(eng._tables[slot, :-(-S // bs)])

        # layer 1's rows, c | k_pe (padded) in ONE row of "k" ("v" holds
        # nothing: every size spelled out, a width of 0 infers no -1)
        rows = eng.kv["k"][1][ids]
        rows = np.asarray(rows.reshape(ids.shape[0] * bs, rows.shape[-1])[:S]
                          .astype(jnp.float32))
        c, pe = rows[:, :rank], rows[:, rank:rank + rope]
        wc, wpe = jax.device_get(
            layer1_rows(params, jnp.asarray([toks], jnp.int32)))
        wc, wpe = wc[0], wpe[0]
        # the system's rotary lanes are de-interleaved (evens, then odds)
        wpe = np.concatenate([wpe[:, 0::2], wpe[:, 1::2]], -1)
        row = {"tokens": S,
               "c_first_chunk": rel(c[:first], wc[:first]),
               "c_prefix_chunks": rel(c[first:prompt_len],
                                      wc[first:prompt_len]),
               "c_decode_rows": rel(c[prompt_len:], wc[prompt_len:]),
               "k_pe_prefix_chunks": rel(pe[first:prompt_len],
                                         wpe[first:prompt_len]),
               "k_pe_decode_rows": rel(pe[prompt_len:], wpe[prompt_len:])}
        out["slots"][str(slot)] = row
        worst = max(worst, *(v for k, v in row.items() if k != "tokens"))
    out["worst"], out["limit"] = worst, LIMIT
    out["ok"] = (worst > 10 * LIMIT) if args.fault else (worst <= LIMIT)
    print(json.dumps(out))
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    from ray_tpu._private import platform
    platform.enable_compile_cache()
    sys.exit(main())
