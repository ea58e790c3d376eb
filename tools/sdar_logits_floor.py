"""The floor and the controls of ``sdar-30b-a3b-chat-d6.block_decode``'s
two checks, on the chip at the published widths (depth 6):

    chiprun -- python3 tools/sdar_logits_floor.py --seeds 8 \\
        --faults int8_weights,causal_in_block,skip_commit,...
    chiprun -- python3 tools/sdar_logits_floor.py --seeds 3 --fused 1 \\
        --greedy 0
    python3 tools/sdar_logits_floor.py --tiny-cpu --seeds 1   # rehearsal

Per seed: weights from the seed, an engine as the cell's (fewer slots:
the checks bring their own pool), then ``drivers/serve_closed_blocks.py``'s
``check_logits_blocks`` against the honest reference and against each
planted fault (``benchmark/reference/sdar.py`` ``FAULTS``), and the
greedy check's measure (``stream_gaps``) on what the ENGINE generated
for the cell's two greedy prompts, honest and planted. One JSON line a
reading on stdout, all of them in ``--out``.

``--fused 1`` adds THE FUSED READING (``check_logits_fused``): the same
teacher-forced check over the same positions, with every block after the
first committed as the engine commits it, clean behind the next block's
all-masked pass in ONE call. The cell's own logits check runs the n-row
call; this says, at the published widths, that the call which carries
two blocks computes the same rows."""

import argparse
import gc
import json
import os
import sys
import time
import types

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def check_logits_fused(server, rows_of, logits_of, *, seed, prompt_len,
                       blocks, slots=8):
    """``drivers/serve_closed_blocks.check_logits_blocks`` through the
    engine's FUSED call: the same two seeded sequences, prefill, masked
    states and reference, the same ``2 x blocks x n x 3`` positions and
    measure. A block's three passes are ``block_step_paged_counted``
    with ``behind`` each: all masked, WITH the clean block before it
    behind it (its commit: the rows every later block reads are written
    here, by the half of the call that the head never sees); clean; and
    LAST a seeded subset masked (nothing behind either), so that what a
    block leaves in the pool are rows computed from masked inputs, as
    its last denoising pass leaves them in the engine: without the
    commit behind the next block this reads as the planted
    ``skip_commit`` does. ``slots``: the call's slots, two sequences and idle ones, with
    room for half as many blocks behind, as the engine's call has ((8 +
    4) x n x top-8 = 384 rows: the grouped matmul's row tile divides)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    engine = server.engine
    model, params, bs = server.model, engine.params, engine.block_size
    n, mask_id = model.cfg.block_length, model.cfg.mask_token_id
    total = prompt_len + n * blocks
    # the driver's draws, in its order
    rng = np.random.default_rng([seed % (2**63), 777])
    clean = rng.integers(1, mask_id, (2, total)).astype(np.int32)
    tail = clean[:, prompt_len:]
    hide = rng.random((2, blocks, n)) < 0.5
    hide[..., 0] |= ~hide.any(-1)
    hide[..., 1] &= ~hide.all(-1)
    states = [np.full_like(tail, mask_id),
              np.where(hide.reshape(2, -1), mask_id, tail), tail]
    commit, masked, subset = jax.jit(
        lambda p, c, a, b: rows_of(p, c, [a, b], prompt_len))(
            params, jnp.asarray(clean), *map(jnp.asarray, states[:2]))
    want_rows = jnp.stack([masked, subset, commit])

    nb_slot = -(-total // bs)
    own = np.arange(2 * nb_slot).reshape(2, nb_slot)

    @jax.jit
    def prefill_and_place(params, tokens, lengths):
        _, small = engine._prefill_impl(params, tokens, lengths)
        pool = model.init_kv_pool(2 * nb_slot + 1, bs)
        return engine._insert_impl(
            pool, small, jnp.asarray(own[:, :prompt_len // bs].reshape(-1)))

    step = jax.jit(lambda p, t, pool, tables, at, live, behind:
                   model.block_step_paged_counted(
                       p, t, pool, tables, at, live, behind)[:2],
                   donate_argnums=2)

    @jax.jit
    def compare(params, got, rows):
        want = logits_of(params, rows)
        diff = got - want
        return (jnp.sum(diff ** 2, axis=(1, 2, 3)),
                jnp.sum(want ** 2, axis=(1, 2, 3)))

    pool = prefill_and_place(params, jnp.asarray(clean[:, :prompt_len]),
                             jnp.full((2,), prompt_len, jnp.int32))
    tables = np.full((slots, nb_slot), 2 * nb_slot, np.int32)
    tables[:2] = own
    tables = jnp.asarray(tables)
    live = jnp.asarray(np.arange(slots) < 2)
    room = jnp.arange(slots // 2)
    err, ref = np.zeros(3), np.zeros(3)
    for b in range(blocks):
        offsets = np.zeros(slots, np.int32)
        offsets[:2] = prompt_len + n * b
        behind = np.full((len(room), n), mask_id, np.int32)
        if b:
            behind[:2] = tail[:, n * (b - 1):n * b]
        got = [None] * 3
        for k in (0, 2, 1):     # all masked and the commit; clean; a subset
            block = np.full((slots, n), mask_id, np.int32)
            block[:2] = states[k][:, n * b:n * (b + 1)]
            logits, pool = step(
                params, jnp.asarray(block), pool, tables,
                jnp.asarray(offsets), live,
                (jnp.asarray(behind), room,
                 live[room] & (k == 0 and b > 0)))
            got[k] = logits[:2]
        e, r = compare(params, jnp.stack(got).astype(jnp.float32),
                       want_rows[:, :, n * b:n * (b + 1)])
        err, ref = err + np.asarray(e), ref + np.asarray(r)
    rel = np.sqrt(err / ref)
    return {"fused_logits_rel_rms": float(np.sqrt(err.sum() / ref.sum())),
            "fused_logits_rel_rms_all_masked": float(rel[0]),
            "fused_logits_rel_rms_subset_masked": float(rel[1]),
            "fused_logits_rel_rms_commit": float(rel[2])}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, default=4)
    ap.add_argument("--first-seed", type=int, default=2147484001)
    ap.add_argument("--faults", default="")
    ap.add_argument("--greedy", type=int, default=1)
    ap.add_argument("--fused", type=int, default=0)
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
            if args.fused and fault is None:
                fused = check_logits_fused(
                    server, *reference, seed=seed,
                    prompt_len=cc["prompt_len"], blocks=cc["blocks"])
                checks = {**checks, **fused}
            say(seed=seed, fault=fault,
                greedy_worst_gap_rel_rms=max(gaps, default=None),
                seconds=time.perf_counter() - t0,
                **{key: checks[key] for key in checks
                   if "logits_" in key or key == "argmax_agreement"})
        # the next seed's weights need this seed's gone from the chip
        del server, params, reference, checks, streams
        gc.collect()


if __name__ == "__main__":
    main()
