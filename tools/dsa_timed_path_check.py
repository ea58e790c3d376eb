"""The timed path of ``deepseek-v3.2-d5.long_decode_dsa`` compared TIGHTLY,
at the timed sizes, past the router.

    chiprun -- python3 tools/dsa_timed_path_check.py            # ~4 min
    chiprun -- python3 tools/dsa_timed_path_check.py --fault    # the control
    python3 tools/dsa_timed_path_check.py --tiny-cpu [--fault]  # rehearsal

``tools/mla_timed_path_check.py`` for the sparse-attention cell, for the
same reason: the cell's own ``correct`` stands on a floor of router and
selection near-ties (PERF.md section 7), but the FIRST EXPERT LAYER'S CACHE
ROWS (layer 1: ``c``, ``k_pe`` and the index key ``k_I``) are a function of
layer 0 alone: the embedding, layer 0's SPARSE latent attention (its
indexer, its selection, the selected rows) over every earlier row, and the
dense FFN. No router stands before them. THE SELECTION IS FORCED: with
seeded weights the indexer's scores say nothing about a row's attention
weight, so the rows bf16 swaps at the 2,048th place carry as much
attention as any others and move these rows by 0.09-0.13 (``--free-
selection`` reads that floor: chip, PR 43; a trained indexer's near-ties
are rows of little attention). So the reference's layer 0 reads
the rows the SYSTEM's arithmetic selects: the model's own projections of the
same embeddings (``_qkv``), ``ops.dsa.index_scores`` and ``topk_mask`` over
them, a block of queries at a time. What is then compared is everything
else: the rows' values, the masked prefill, the row reads, the absorbed
attention, the cache's words.

What it does. An engine of the cell's own shape (16 slots x 22,528, block
32) takes 16 prompts of 16,384 tokens through its own chunked prefills (the
selection as a mask over the gathered prefix) and then decodes 48 tokens in
all 16 slots (index scores over 513+ blocks a slot, the top-2,048's row
numbers, the selected rows copied by number). The pool's layer-1 rows of
three slots, read through the engine's block tables and unpacked from the
words they are held as, are compared with ``benchmark/reference/
deepseek_v32.py``'s float32 arithmetic over the slot's prompt + generated
tokens, prompt rows and decode rows apart.

``--fault`` plants two faults in the model (the decode step's indexer and
attention see the slot's first 2,048 rows only; a chunk prefill's gathered
prefix past 2,048 rows reads as zeros): the rows must then read FAR off.
Prints one JSON line; exit 1 where the honest rows pass ``LIMIT`` or the
faulty ones do not.

Readings (chip, PR 43, 16 slots live, 16,431 rows a slot, 47 decode steps):
honest, selection forced: first chunk (no selection yet) 0.0121, prefix
chunks 0.0130-0.0138, decode rows 0.0132-0.0158, worst 0.0158; selection
FREE: prefix chunks 0.087-0.088, decode rows 0.098-0.128; faulty 1.08
(prefix chunks) and 1.28-1.29 (decode rows). PR 44 (the row one run under
"k", one copy a selected row): honest the same to the digits shown, worst
0.0158; faulty 1.08 and 1.28-1.30.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

LIMIT = 0.03            # relative RMS of a group of layer-1 rows; 2 x honest
N_DECODE = 48


def rel(got, want) -> float:
    import numpy as np
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2) / np.mean(want ** 2)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=3300000101)
    ap.add_argument("--fault", action="store_true")
    ap.add_argument("--free-selection", action="store_true")
    ap.add_argument("--tiny-cpu", action="store_true")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import run as harness
    from benchmark.builders import deepseek_v32 as builder
    from benchmark.lib import serving
    from benchmark.reference import deepseek_v32 as R
    from ray_tpu.llm.engine import ContinuousBatchingEngine, SamplingParams

    traffic = harness.load_json(harness.HERE, "traffic",
                                "long_decode_dsa.json")
    cfg = harness.load_json(harness.ROOT,
                            "benchmark/configs/deepseek-v3.2-d5.json")
    eng_kw = {k: traffic["engine"][k]
              for k in ("max_slots", "max_seq", "block_size")}
    prompt_len, n_decode = traffic["prompt_len"]["value"], N_DECODE
    first, cut, slots = 512, 2048, (0, 7, 15)
    if args.tiny_cpu:
        cfg = {**cfg, **cfg["tiny_cpu"], "index_topk": 48}
        eng_kw = dict(max_slots=2, max_seq=512, block_size=8)
        prompt_len, n_decode, first, cut, slots = 300, 6, 64, 64, (0, 1)
    rank, rope = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    theta, eps = float(cfg["rope_theta"]), float(cfg["rms_norm_eps"])

    model = builder.build_model(cfg, eng_kw["max_seq"])
    params = jax.jit(lambda key: model.serving_params(model.init(key)))(
        jax.random.key(args.seed % (2**31 - 1)))

    class Faulty(type(model)):
        def _attend_pages(self, q, c_pool, pe_pool, layer, block_tables,
                          lengths, **kw):
            return super()._attend_pages(
                q, c_pool, pe_pool, layer, block_tables,
                jnp.minimum(lengths, cut), **kw)

        def prefill_with_prefix(self, params, tokens, prefix_k, prefix_v,
                                *a, **kw):
            return super().prefill_with_prefix(
                params, tokens, prefix_k.at[:, :, cut:].set(0),
                prefix_v.at[:, :, cut:].set(0), *a, **kw)

    kw = builder.reference_kwargs(cfg)

    @jax.jit
    def system_selection(params, tokens):
        """[1, S, S]: the rows layer 0 selects for every query by the
        SYSTEM's own arithmetic (its projections in its compute dtype,
        its index scores, its exact top-k), a block of queries at a
        time."""
        from ray_tpu.ops import dsa
        S = tokens.shape[1]
        layer = {n: a[0] for n, a in params["leading_layers"].items()}
        h = model._norm(model._embed(params, tokens), layer["attn_norm"])
        (_, q_idx, w), k_rows, v_rows = model._qkv(
            h, layer, jnp.arange(S)[None], None, lambda a, *names: a)
        k_idx = model._row_parts(k_rows, v_rows)[2]
        block = 128
        pad = -S % block
        rows = jnp.arange(S + pad).reshape(-1, block)
        cols = jnp.arange(S)

        def one(args):
            at, q, wt = args
            return dsa.topk_mask(dsa.index_scores(q, wt, k_idx),
                                 (at[:, None] >= cols[None, :])[None],
                                 model.cfg.index_topk)

        masks = jax.lax.map(one, (
            rows,
            jnp.pad(q_idx, ((0, 0), (0, pad), (0, 0), (0, 0))).reshape(
                -1, 1, block, *q_idx.shape[2:]),
            jnp.pad(w, ((0, 0), (0, pad), (0, 0))).reshape(
                -1, 1, block, w.shape[-1])))
        return masks.transpose(1, 0, 2, 3).reshape(1, S + pad, S)[:, :S]

    @jax.jit
    def layer1_rows(params, tokens, forced):
        """Layer 1's cache rows (c, k_pe, k_I) of tokens [1, S] in float32,
        by the reference's own pieces: layer 0 whole (its selection its
        own), then layer 1's norm, down-projection, ``kv_a_layernorm``,
        the index key's projection and LayerNorm, and both RoPE forms."""
        rp = builder.reference_params(cfg, params)
        inv = R.yarn_inv_freq(rope, theta, kw["yarn"])
        with jax.default_matmul_precision("highest"):
            x = R._f32(rp["embed"][tokens])
            lp = {n: a[0] for n, a in rp["dense_layers"].items()}
            h = R._rms_norm(x, R._f32(lp["attn_norm"]), eps)
            x = x + R._sparse_latent_attention(
                h, lp, nope=cfg["qk_nope_head_dim"], rope=rope, rank=rank,
                theta=theta, yarn=kw["yarn"],
                mscale_all_dim=kw["mscale_all_dim"], eps=eps,
                index_topk=cfg["index_topk"], forced=forced, fault=None)[0]
            x = x + R._swiglu_wide(
                R._rms_norm(x, R._f32(lp["mlp_norm"]), eps),
                lp["gate"], lp["up"], lp["down"])
            lp = {n: rp["moe_layers"][n][0]
                  for n in ("attn_norm", "kv_a_proj", "kv_a_layernorm",
                            "indexer_wk", "indexer_k_norm")}
            h = R._rms_norm(x, R._f32(lp["attn_norm"]), eps)
            down = h @ R._f32(lp["kv_a_proj"])
            norm = R._f32(lp["indexer_k_norm"])
            k_i = R._layer_norm(h @ R._f32(lp["indexer_wk"]), norm[0],
                                norm[1], 1e-6)
            k_i = jnp.concatenate(
                [R._rope(k_i[:, :, None, :rope], inv, True)[:, :, 0],
                 k_i[..., rope:]], -1)
            return (R._rms_norm(down[..., :rank],
                                R._f32(lp["kv_a_layernorm"]), eps),
                    R._rope(down[..., None, rank:], inv, False)[:, :, 0],
                    k_i)

    eng = ContinuousBatchingEngine(
        Faulty(model.cfg) if args.fault else model, params, **eng_kw)
    reqs = [eng.submit(serving.make_prompt(args.seed, 700_000 + i,
                                           prompt_len, cfg["vocab_size"]),
                       SamplingParams(max_tokens=10**6))
            for i in range(eng_kw["max_slots"])]
    while min(len(r.output) for r in reqs) < n_decode:
        eng.step()

    out = {"fault": args.fault, "selection_forced": not args.free_selection,
           "device": jax.devices()[0].device_kind,
           "decode_attention_impl": eng.decode_attention_impl,
           "decode_indexer_impl": eng.stats["decode_indexer_impl"],
           "decode_steps": eng.stats["decode_steps"], "slots": {}}
    bs, worst = eng.block_size, 0.0
    for slot in slots:
        req = eng.slots[slot]
        toks = req.prompt + req.output[:len(req.output) - 1]   # rows written
        S = len(toks)
        ids = jnp.asarray(eng._tables[slot, :-(-S // bs)])
        # layer 1's rows, as what they hold (unpacked where they are words)
        # (the size spelled out: a row of words leaves "v" empty)
        parts = model._row_parts(*(
            eng.kv[name][1][ids].reshape(len(ids) * bs,
                                         *eng.kv[name].shape[3:])[:S]
            for name in ("k", "v")))
        c, pe, ki = (np.asarray(a.astype(jnp.float32)) for a in parts)
        pe = pe[:, :rope]
        toks_in = jnp.asarray([toks], jnp.int32)
        wc, wpe, wki = (a[0] for a in jax.device_get(layer1_rows(
            params, toks_in, None if args.free_selection
            else system_selection(params, toks_in))))
        # the system's rotary lanes are de-interleaved (evens, then odds)
        wpe = np.concatenate([wpe[:, 0::2], wpe[:, 1::2]], -1)
        row = {"tokens": S,
               "c_first_chunk": rel(c[:first], wc[:first]),
               "c_prefix_chunks": rel(c[first:prompt_len],
                                      wc[first:prompt_len]),
               "c_decode_rows": rel(c[prompt_len:], wc[prompt_len:]),
               "k_pe_prefix_chunks": rel(pe[first:prompt_len],
                                         wpe[first:prompt_len]),
               "k_pe_decode_rows": rel(pe[prompt_len:], wpe[prompt_len:]),
               "k_idx_prefix_chunks": rel(ki[first:prompt_len],
                                          wki[first:prompt_len]),
               "k_idx_decode_rows": rel(ki[prompt_len:], wki[prompt_len:])}
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
