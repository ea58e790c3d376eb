"""``serve_closed`` for a model that generates BY DIFFUSION OVER BLOCKS:
a decode step is a PASS over every slot's block and yields 0 tokens or
a whole block a slot, so nothing here may assume one token a slot a step.
The same admission, stall watch, close and record as ``drivers/
serve_closed.py`` (``watch_window``, ``watch_for_stalls``, ``silent_at``,
``resumed_by``, ``mis_sized``, ``requests_ended`` are its own, and the
record has its keys), with TWO differences.

1. THE WINDOW RULE COUNTS TOKENS, NOT STEPS. ``watch_window`` is
   unit-free: it is handed the tokens the engine has delivered A SLOT
   (``tokens_generated`` / slots, rounded up, + one block of slack: the
   slots run in step on seeded weights, a trained model's may stand a
   block apart) as ``engine_steps``, and as the cap the tokens the
   longest-lived request had left at the open: ``max_tokens`` less its
   head start, which is its client's count or the engine's, whichever is
   further on (the engine's: at most a block a pass since the first
   request's first token, and at most every token the engine made since).
   The margin of the close (``MIN_MARGIN_STEPS``, two seconds of the
   highest rate seen) is then in tokens a slot too. ``counts.cap_steps``,
   ``margin_steps`` and ``cap_steps_per_s`` are in TOKENS A SLOT;
   ``decode_steps_per_s`` stays the engine's passes a second.

2. THE CHECKS THAT DECIDE ``correct`` ARE THE DIFFUSION'S (the builder's
   ``reference_*`` functions; tolerances, their reasons and the readings
   they were set from in the traffic file's ``correctness`` block):

   - ``check_logits_blocks``: at the published widths, through the
     engine's own ``_prefill_impl`` and ``_insert_impl`` and the model's
     block-step program on a small pool: two seeded sequences, a prefill
     of ``prompt_len`` tokens, then ``blocks`` blocks each run
     TEACHER-FORCED through three passes, as the engine would: all
     masked, a seeded subset masked, and clean (the commit pass, whose
     K/V rows stay for the blocks after it); the logits of the blocks'
     own positions against the reference's (``reference_teacher_forced``:
     every block in every state at one forward's cost), relative RMS
     over all three passes, as ``lib.serving.check_logits`` reports it.
   - ``check_greedy_blocks``: greedy requests THROUGH THE HANDLE (the
     engine's chunked prefill, first block, block-step program with its
     sampler, unmask rule and state machine, the commit, the stream).
     Each streamed chunk carries, beside ``index``, the ``pass`` of its
     block that placed the token; the reference replays every block IN
     THE ENGINE'S OWN UNMASK ORDER and holds every token to its logits
     for exactly the masked state the token was chosen in: the
     reference's first there, or within ``margin_rel_rms`` x the RMS of
     those logits of it (``lib.serving.token_gaps``' measure). A request
     asks for as many tokens as end on a block's end: of a block that
     ``max_tokens`` cut the client sees too little to rebuild its states
     (the cut itself: ``tests/test_sdar_engine.py``).

THE RECORD LEAVES ``moe_expert_load`` OUT of the engine's four snapshots,
as ``serve_closed_state.py`` does and for its reason: 6 layers x 128
experts are ~4 KB a copy, and a traced line with four of them passes the
31,303 bytes of the longest accepted one. So
``moe.expert_load_max_over_mean.decode`` is not reported in this cell.

Traffic ``kind``: ``"serve_closed_blocks"``. Parameters (``benchmark/
README.md`` may not be edited by the PR that brought this file):
``serve_closed``'s (``engine``, ``clients``, ``prompt_len``, ``max_tokens``,
``min_streamed_before_window``, ``trace_seconds``) and, under
``correctness``: ``prompt_len``, ``blocks``, ``tolerance_rel_rms``,
``greedy.prompt_lens``, ``greedy.tokens`` (one count a prompt),
``greedy.margin_rel_rms``.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List

import numpy as np

from benchmark import drivers
from benchmark.drivers.serve_closed import (  # noqa: F401
    MIN_MARGIN_STEPS, STALL_FACTOR, STALL_GRACE_S, mis_sized, requests_ended,
    resumed_by, silent_at, watch_for_stalls, watch_window)
from benchmark.lib import serving
from benchmark.lib.records import RequestRecord, percentile

DECODE_ROWS = 8         # the check's pass: its two sequences and six idle
                        # slots (8 x 4 x top-8 = 256 rows: the grouped
                        # matmul's row tile divides them)


def check_logits_blocks(server, rows_of, logits_of, *, seed: int,
                        prompt_len: int, blocks: int,
                        tol_rel_rms: float) -> Dict:
    """Module docstring, first check. ``rows_of(params, clean, noised,
    start)`` and ``logits_of(params, rows)`` are the builder's
    ``reference_teacher_forced`` pair."""
    import jax
    import jax.numpy as jnp

    engine = server.engine
    model, params, bs = server.model, engine.params, engine.block_size
    cfg = model.cfg
    n, mask_id = cfg.block_length, cfg.mask_token_id
    total = prompt_len + n * blocks
    rng = np.random.default_rng([seed % (2**63), 777])
    clean = rng.integers(1, mask_id, (2, total)).astype(np.int32)
    tail = clean[:, prompt_len:]
    # a seeded subset of every block masked: at least one position, and
    # at least one left standing
    hide = rng.random((2, blocks, n)) < 0.5
    hide[..., 0] |= ~hide.any(-1)
    hide[..., 1] &= ~hide.all(-1)
    states = [np.full_like(tail, mask_id),
              np.where(hide.reshape(2, -1), mask_id, tail), tail]

    # the reference first: its temporaries go before the pool comes
    want_rows = jax.jit(
        lambda p, c, a, b: rows_of(p, c, [a, b], prompt_len))(
            params, jnp.asarray(clean), *map(jnp.asarray, states[:2]))
    commit, masked, subset = want_rows
    want_rows = jnp.stack([masked, subset, commit])      # the passes' order

    nb_slot = -(-total // bs)
    n_blocks = 2 * nb_slot
    own = np.arange(n_blocks).reshape(2, nb_slot)
    nb_prefill = prompt_len // bs

    @jax.jit
    def prefill_and_place(params, tokens, lengths):
        _, small = engine._prefill_impl(params, tokens, lengths)
        pool = model.init_kv_pool(n_blocks + 1, bs)
        return engine._insert_impl(
            pool, small, jnp.asarray(own[:, :nb_prefill].reshape(-1)))

    step = jax.jit(lambda p, t, pool, tables, at:
                   model.block_step_paged_counted(p, t, pool, tables, at)[:2],
                   donate_argnums=2)

    @jax.jit
    def compare(params, got, rows):
        want = logits_of(params, rows)
        diff = got - want
        return (jnp.sum(diff ** 2, axis=(1, 2, 3)),
                jnp.sum(want ** 2, axis=(1, 2, 3)), jnp.max(jnp.abs(diff)),
                jnp.sum(jnp.argmax(got, -1) == jnp.argmax(want, -1)),
                jnp.all(jnp.isfinite(got)))

    if prompt_len % bs:
        raise ValueError(f"the check's prompt of {prompt_len} fills no whole "
                         f"pages of {bs}")
    pool = prefill_and_place(params, jnp.asarray(clean[:, :prompt_len]),
                             jnp.full((2,), prompt_len, jnp.int32))
    tables = np.full((DECODE_ROWS, nb_slot), n_blocks, np.int32)
    tables[:2] = own
    tables = jnp.asarray(tables)
    err = np.zeros(3)
    ref = np.zeros(3)
    max_abs, same, finite = 0.0, 0, True
    for b in range(blocks):
        offsets = np.zeros(DECODE_ROWS, np.int32)
        offsets[:2] = prompt_len + n * b
        got = []
        for state in states:        # all masked, a subset, clean: the commit
            block = np.full((DECODE_ROWS, n), mask_id, np.int32)
            block[:2] = state[:, n * b:n * (b + 1)]
            logits, pool = step(params, jnp.asarray(block), pool, tables,
                                jnp.asarray(offsets))
            got.append(logits[:2])
        e, r, m, s, f = compare(params, jnp.stack(got).astype(jnp.float32),
                                want_rows[:, :, n * b:n * (b + 1)])
        err, ref = err + np.asarray(e), ref + np.asarray(r)
        max_abs, same = max(max_abs, float(m)), same + int(s)
        finite = finite and bool(f)
    rel = np.sqrt(err / ref)
    rel_rms = float(np.sqrt(err.sum() / ref.sum()))
    return {"ok": finite and rel_rms <= tol_rel_rms,
            "logits_rel_rms": rel_rms,
            "logits_rel_rms_all_masked": float(rel[0]),
            "logits_rel_rms_subset_masked": float(rel[1]),
            "logits_rel_rms_commit": float(rel[2]),
            "logits_max_abs_diff": max_abs,
            "argmax_agreement": same / (3 * 2 * n * blocks),
            "tolerance_rel_rms": tol_rel_rms,
            "positions": 3 * 2 * n * blocks}


def stream_with_passes(handle, prompt: List[int], max_tokens: int):
    """One streamed request read to its end: ``(tokens, passes, error)``,
    each token with the ``pass`` its chunk carried."""
    toks, passes = [], []
    try:
        gen = handle.options(stream=True).remote(
            {"prompt": prompt, "max_tokens": max_tokens, "stream": True})
        done = False
        while True:
            try:
                chunk = gen.next(timeout=serving.CHUNK_TIMEOUT_S)
            except StopIteration:
                break
            if "token_id" in chunk:
                toks.append(chunk["token_id"])
                passes.append(chunk["pass"])
            done = done or bool(chunk.get("done"))
        if not done:
            return toks, passes, "stream ended without its final chunk"
    except Exception as e:                      # noqa: BLE001 — recorded
        return toks, passes, f"{type(e).__name__}: {e}"
    return toks, passes, None


def replay_states(prompt: List[int], toks: List[int], passes: List[int],
                  n: int, steps: int, mask_id: int):
    """The blocks a stream filled, in the states its passes saw:
    ``(clean, start, states, chosen)``. ``clean`` is prompt + stream,
    ``start`` where its first block begins; ``states[k]`` [len - start]
    is every block as pass ``k + 1`` FOUND it (what the prompt gave and
    what passes up to ``k`` placed; masks elsewhere); ``chosen`` lists
    ``(k, position - start, token)`` for every streamed token."""
    start = len(prompt) - len(prompt) % n
    clean = np.asarray(prompt + toks, np.int64)
    if len(clean) % n:
        raise ValueError("the stream ends inside a block")
    placed_at = np.zeros(len(clean) - start, np.int64)   # 0: the prompt's
    placed_at[len(prompt) - start:] = passes
    states = [np.where(placed_at <= k, clean[start:], mask_id)
              for k in range(steps)]
    chosen = [(int(k) - 1, i, int(clean[start + i]))
              for i, k in enumerate(placed_at) if k > 0]
    return clean, start, states, chosen


def stream_gaps(model_cfg, params, rows_of, logits_of, prompt: List[int],
                toks: List[int], passes: List[int]) -> List[float]:
    """How far each streamed token lies under the reference's first FOR
    THE MASKED STATE IT WAS CHOSEN IN, in units of the RMS of those
    logits (``lib.serving.token_gaps``): one reference forward over the
    stream's blocks in every state its passes saw."""
    import jax
    import jax.numpy as jnp

    n, steps = model_cfg.block_length, model_cfg.denoising_steps
    clean, start, states, chosen = replay_states(
        prompt, toks, passes, n, steps, model_cfg.mask_token_id)
    rows = jax.jit(lambda p, c, s, at=start: jnp.stack(rows_of(
        p, c, list(s), at)[1:]))(
            params, jnp.asarray(clean[None], jnp.int32),
            jnp.asarray(np.stack(states)[:, None], jnp.int32))
    at = np.asarray([(state, i) for state, i, _ in chosen])
    want = np.asarray(jax.jit(logits_of)(
        params, rows[at[:, 0], 0, at[:, 1]]))
    return serving.token_gaps(want, [tok for _, _, tok in chosen])


def check_greedy_blocks(handle, server, rows_of, logits_of, *, seed: int,
                        prompt_lens: List[int], tokens: List[int],
                        margin_rel_rms: float) -> Dict:
    """Module docstring, second check."""
    cfg = server.model.cfg
    worst, top1, streamed, errors = 0.0, 0, 0, []
    for k, (plen, want_n) in enumerate(zip(prompt_lens, tokens)):
        prompt = serving.make_prompt(seed, 600_000 + k, int(plen),
                                     cfg.mask_token_id)
        toks, passes, error = stream_with_passes(handle, prompt, want_n)
        if (error or len(toks) != want_n
                or max(passes) > cfg.denoising_steps):
            errors.append(f"prompt {plen}: {error or (len(toks), passes)}")
            continue
        gaps = stream_gaps(cfg, server.engine.params, rows_of, logits_of,
                           prompt, toks, passes)
        worst = max(worst, max(gaps))
        top1 += sum(g == 0.0 for g in gaps)
        streamed += len(toks)
    return {"ok": not errors and streamed > 0 and worst <= margin_rel_rms,
            "greedy_errors": errors, "greedy_tokens": streamed,
            "greedy_top1": top1, "greedy_worst_gap_rel_rms": worst,
            "greedy_margin_rel_rms": margin_rel_rms}


def deploy_and_check(run):
    """``lib.serving.deploy_and_check`` with the two checks above. The
    program's configuration is built FIRST: a program without the model
    fails here, before any runtime is started."""
    import ray_tpu

    from benchmark.lib.bench_server import SERVERS

    tr, cfg = run.traffic, run.config
    eng = tr["engine"]
    model_config = run.builder.program_config(cfg, eng["max_seq"])
    ray_tpu.init()
    run.phase("runtime_init")
    handle = serving.start(model_config,
                           model_id=run.workload.replace(".", "-"),
                           engine=eng, seed=run.jax_seed)
    run.phase("deploy_and_init_weights")
    cc = tr["correctness"]
    reference = run.builder.reference_teacher_forced(cfg)
    checks = check_logits_blocks(
        SERVERS[-1], *reference, seed=run.seed, prompt_len=cc["prompt_len"],
        blocks=cc["blocks"], tol_rel_rms=cc["tolerance_rel_rms"])
    greedy = check_greedy_blocks(
        handle, SERVERS[-1], *reference, seed=run.seed,
        prompt_lens=cc["greedy"]["prompt_lens"],
        tokens=cc["greedy"]["tokens"],
        margin_rel_rms=cc["greedy"]["margin_rel_rms"])
    checks = {**checks, **greedy, "ok": checks["ok"] and greedy["ok"]}
    run.phase("correctness_check")
    return handle, checks


def tokens_a_slot(stats: Dict, slots: int, block: int) -> int:
    """What ``watch_window`` is handed for the engine's steps (module
    docstring, first difference)."""
    return -(-stats["tokens_generated"] // slots) + block


def run(run) -> dict:
    import ray_tpu
    from ray_tpu import serve

    tr, cfg = run.traffic, run.config
    eng = tr["engine"]
    n_clients = int(tr["clients"])
    plen = int(tr["prompt_len"]["value"])
    max_tokens = int(tr["max_tokens"])
    block = int(cfg["generation"]["block_length"])
    slots = int(eng["max_slots"])
    # prompts hold no mask id: ids below it
    vocab = int(cfg["generation"]["mask_token_id"])

    handle, checks = deploy_and_check(run)

    stop = threading.Event()
    stamps = [[] for _ in range(n_clients)]
    records = [[] for _ in range(n_clients)]

    def client(i: int):
        k = 0
        while not stop.is_set():
            rec = RequestRecord(index=i * 1000 + k,
                                due_at=time.perf_counter())
            records[i].append(rec)
            serving.stream_request(
                handle, serving.make_prompt(run.seed, i * 1000 + k, plen,
                                            vocab),
                max_tokens, rec, stamps[i])
            if rec.error:
                return
            k += 1

    threads = [threading.Thread(target=client, args=(i,), daemon=True,
                                name=f"bench-client-{i}")
               for i in range(n_clients)]

    def wait_tokens(i: int, n: int):
        deadline = time.perf_counter() + serving.CHUNK_TIMEOUT_S
        while len(stamps[i]) < n:
            if records[i] and records[i][-1].error:
                raise RuntimeError(f"client {i}: {records[i][-1].error}")
            if time.perf_counter() > deadline:
                raise TimeoutError(f"client {i} got no token in time")
            time.sleep(0.002)

    def engine_tokens() -> int:
        return tokens_a_slot(serving.engine_stats(handle), slots, block)

    for i, t in enumerate(threads):       # one admission at a time
        t.start()
        wait_tokens(i, 1)
        if i == 0:
            first = serving.engine_stats(handle)
    for i in range(n_clients):
        wait_tokens(i, int(tr["min_streamed_before_window"]))
    run.phase("admit_clients")

    before = serving.engine_stats(handle)
    counts0 = [len(s) for s in stamps]
    compiles0 = run.compiles.snapshot()["requests"]
    t_open = run.open_window()
    # the first request's tokens by the engine's count: a block when
    # ``first`` was read, then at most a block a pass, and at most every
    # token the engine has made since
    engine_first = block + min(
        block * (before["decode_steps"] - first["decode_steps"]),
        before["tokens_generated"] - first["tokens_generated"])
    head_start = max(max(counts0), engine_first)
    tokens_cap = max_tokens - head_start
    watch_for_stalls(run, stamps, stop)
    run.trace_during(t_open, tr.get("trace_seconds", 4),
                     snapshot=lambda: serving.engine_stats(handle))
    close = watch_window(engine_tokens, t_open=t_open, seconds=run.seconds,
                         steps_open=tokens_a_slot(before, slots, block),
                         steps_cap=tokens_cap)
    t_closed = close.t_closed
    counts1 = [len(s) for s in stamps]
    stop.set()          # a request that ends from here on is not sent again
    last_stamp = [s[-1] if s else None for s in stamps]
    errored = [any(r.error for r in records[i]) for i in range(n_clients)]
    compiles1 = run.compiles.snapshot()["requests"]
    after = serving.engine_stats(handle)
    run.finish_trace()

    gaps = sorted(b - a for s, n in zip(stamps, counts1)
                  for a, b in zip(s[:n], s[1:n]) if t_open <= a)
    # tokens come a block at a commit: a client's silence is judged by the
    # gap between BLOCKS (the largest of each run of ``block`` gaps)
    median_gap = gaps[len(gaps) * (2 * block - 1) // (2 * block)] if gaps \
        else 0.0
    silent = silent_at(last_stamp, t_closed,
                       max(1.0, STALL_FACTOR * median_gap))
    resumed = resumed_by(stamps, last_stamp, silent,
                         t_closed + STALL_GRACE_S)
    if silent:
        run.log(f"{len(silent)} client(s) silent at the close, "
                f"{len(resumed)} resumed within {STALL_GRACE_S:.0f} s "
                "(a stall across the close); the others are dead")

    # the streams cannot be cancelled through the API and have minutes
    # to go: the replica goes down under them
    serve.shutdown()
    ray_tpu.shutdown()
    if not run.tiny:            # a CPU run names no rate: nothing to refuse
        message = mis_sized(run.workload, close, t_open, tokens_cap,
                            max_tokens)
        if message:
            raise drivers.MisSized(message)

    gap_ms = None if run.tiny or not gaps else {
        f"p{q}": 1e3 * percentile(gaps, q) for q in (50, 90, 99, 100)}
    dead = set(silent) - set(resumed)
    failed = sum(bool(errored[i] or i in dead) for i in range(n_clients))
    requests = [r for rs in records for r in rs]
    ended = requests_ended(requests, t_open, t_closed)
    passes = after["decode_steps"] - before["decode_steps"]
    made = after["tokens_generated"] - before["tokens_generated"]
    window_s = t_closed - t_open
    passes_per_s = cap = None           # a CPU run (--tiny-cpu) names no rate
    rates = ""
    if not run.tiny:
        passes_per_s = passes / window_s
        # the tokens a slot a second past which the window begins to shrink
        cap = (tokens_cap - close.margin_steps) / run.seconds
        rates = (f" ({passes_per_s:.1f} passes/s, "
                 f"{made / slots / window_s:.1f} tokens/s a slot; the window "
                 f"shrinks past {cap:.1f} tokens/s a slot)")
    run.log(f"window closed "
            + (f"EARLY after {window_s:.1f} of {run.seconds:.0f} s, "
               f"{close.margin_steps} tokens a slot before the engine's "
               f"first request ends" if close.closed_early
               else f"at its {run.seconds:.0f} s")
            + f": {passes} passes, {made} tokens ({made / max(passes, 1):.2f} "
            f"a pass) of the {tokens_cap} a slot that a request of "
            f"{max_tokens} tokens had left{rates}; {close.looks} look(s) at "
            f"the engine; {ended} request(s) ended inside it"
            + (": the close came too late, the window held refill and "
               "prefill, not this cell's work" if ended else ""))
    record = {
        "kind": "serve_closed", "correct": bool(checks["ok"]) and failed == 0,
        "attempted": n_clients, "failed": failed, "checks": checks,
        "t_open": t_open, "t_close": t_closed,
        "stamps": stamps,
        "requests": requests,
        "engine_before": before, "engine_after": after,
        "slots": slots,
        # (a block-diffusion prefill samples no token: none to take off
        # ``engine.batch_occupancy``'s count)
        "first_tokens_in_window": 0,
        "tokens_received_in_window": sum(counts1) - sum(counts0),
        "requests_ended_in_window": ended,
        "window_s": window_s, "closed_early": close.closed_early,
        "cap_steps": tokens_cap, "margin_steps": close.margin_steps,
        "stalled_at_close": len(resumed),
        "decode_steps_per_s": passes_per_s, "cap_steps_per_s": cap,
        "token_gap_ms": gap_ms,
        "engine_trace_edges": run.trace_edges,
        "compiles_in_window": compiles1 - compiles0,
    }
    for stats in (before, after, *run.trace_edges):
        stats.pop("moe_expert_load", None)
    return record
