"""``serve_closed`` for a model whose cache holds MORE than rows of
tokens (a recurrent state a slot beside the paged K/V): the same
admission, window, close, stall watch and record, TO THE LETTER
(``closed_loop`` below is ``drivers/serve_closed.py``'s ``run`` from the
line after its deploy call on; ``benchmark/tests/test_nemotron_h.py``
holds the two equal line for line), with ONE difference: the check before the window
takes the cache as the MODEL lays it out, and holds the STATE beside the
logits.

``lib/serving.py:check_logits`` pads its two prompts to whole blocks and
prefills them with NO lengths (a recurrence would run on through the
padding and hand on the wrong state) and rebuilds the pool from
``small["k"]`` / ``small["v"]`` alone (a state that is no row of a token
has no way from the prefill to the decode steps). ``check_logits_state``
prefills two rows OF DIFFERENT LENGTHS in one padded batch with their
true lengths through the engine's own ``_prefill_impl``, places what the
prefill left through the engine's own ``_insert_impl`` (pages) and
``_write_state_impl`` (the state rows: what activation runs) into a
small cache that the model's ``init_kv_pool`` laid out, runs the decode
steps with that WHOLE tree, and compares

- the LOGITS of the first ``decode_steps`` positions with the reference
  as ``check_logits`` does (relative RMS, 2 sequences, the traffic
  file's ``correctness`` block): every layer is in them, and so is every
  router's floor;
- the STATE ROWS of the first layer after ``state.decode_steps`` steps
  (the traffic file's ``correctness.state`` block) with the builder's
  ``reference_first_state``, the recurrence a position at a time over
  the tokens the row has consumed: ``S`` HEAD BY HEAD, the worst head's
  relative RMS (``worst_head``), and the convolution's window. No router
  stands before the first layer, so the arithmetic is held tightly here;
  and a head that remembers long is where a state held in too few bits
  shows (its decay of 0.998 a step is under bf16's half-ulp: the rounded
  state stands still), which no sum over all heads' numbers can see.

The greedy check through the handle is ``lib/serving.py``'s own.

THE RECORD LEAVES ``moe_expert_load`` OUT of the engine's four snapshots
(``run`` below). ``benchmark/run.py`` prints the snapshots whole; this
configuration's router has 512 outputs over 5 expert layers, 11-16 KB a
copy, and the traced run's line came to 67,259 bytes (33 KB untraced)
where no accepted cell's passes 31,303: the driver's check could not read
it (PR 47's first check). So ``moe.expert_load_max_over_mean.decode`` is
not reported in this cell (its reader is the one thing that reads the
list; PERF.md section 7 has the edit to ``run.py`` that would bring it
back); every other counter stands as the engine gave it.

Traffic ``kind``: ``"serve_closed_state"``; parameters: ``serve_closed``'s
(``benchmark/README.md``) and ``correctness.state``. A later
``benchmark`` PR should let ``deploy_and_check`` take the check from the
builder; THIS FILE AND THE TEST THAT HOLDS ITS ``closed_loop`` EQUAL TO
``serve_closed``'s THEN GO, and the cell's traffic names kind
``serve_closed`` (PERF.md, Open questions).
"""

from __future__ import annotations

import threading
import time

import numpy as np

from benchmark import drivers
from benchmark.drivers.serve_closed import (  # noqa: F401
    LOOK_FAR_S, LOOK_S, MIN_MARGIN_STEPS, MIN_WINDOW_S, STALL_DUMP_S,
    STALL_FACTOR, STALL_GRACE_S, mis_sized, requests_ended, resumed_by,
    silent_at, watch_for_stalls, watch_window)
from benchmark.lib import serving
from benchmark.lib.records import RequestRecord, percentile
from benchmark.lib.serving import (  # noqa: F401
    check_greedy, engine_stats, make_prompt, start, stream_request,
    warm_shapes)

SHORTER_BY = 77         # the second row's prompt: no multiple of anything
DECODE_ROWS = 8         # the check's decode batch: its two sequences and
                        # six idle slots (8 x top-22 rows: a row tile of the
                        # grouped matmul divides them, as it does an
                        # engine's 64 x 22; 2 x 22 = 44 rows it does not)


def worst_head(got, want) -> float:
    """The largest relative RMS error of ONE head's state; got, want
    [..., H, P, N] (module docstring)."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    err = np.mean((got - want) ** 2, axis=(-2, -1))
    return float(np.sqrt(np.max(err / np.mean(want ** 2, axis=(-2, -1)))))


def check_logits_state(server, reference_forward, reference_first_state, *,
                       seed: int, prompt_len: int, decode_steps: int,
                       tol_rel_rms: float, state_steps: int,
                       tol_state: float) -> dict:
    """Two seeded sequences of ``prompt_len`` and ``prompt_len -
    SHORTER_BY`` prompt tokens (module docstring): bucket prefill with
    the rows' true lengths, the engine's own placement of pages and
    state, ``state_steps`` (>= ``decode_steps``) decode steps over the
    whole cache tree fed each sequence's own next tokens; the logits of
    the first ``decode_steps`` of those positions against the
    reference's full forward, the first layer's state rows after the
    last against ``reference_first_state``."""
    import jax
    import jax.numpy as jnp

    engine = server.engine
    model, params, bs = server.model, engine.params, engine.block_size
    lens = np.asarray([prompt_len, prompt_len - SHORTER_BY], np.int32)
    total, steps = prompt_len + decode_steps, state_steps
    rng = np.random.default_rng([seed % (2**63), 777])
    # two draws, so that the logits' tokens do not depend on how many
    # steps the state's comparison runs on behind them
    seqs = np.concatenate(
        [rng.integers(1, model.cfg.vocab_size, (2, n)).astype(np.int32)
         for n in (total, steps - decode_steps)], axis=1)
    # the prefill's bucket holds the logits' positions; the tables reach
    # as far as the state's steps go
    nb_prefill, nb_slot = -(-total // bs), -(-(prompt_len + steps) // bs)
    n_blocks = 2 * nb_slot
    padded = np.zeros((2, nb_prefill * bs), np.int32)
    for r in range(2):
        padded[r, :lens[r]] = seqs[r, :lens[r]]
    own = np.arange(n_blocks).reshape(2, nb_slot)

    @jax.jit
    def prefill_and_place(params, tokens, lengths):
        _, small = engine._prefill_impl(params, tokens, lengths)
        cache = model.init_kv_pool(n_blocks + 1, bs, DECODE_ROWS)
        cache = engine._insert_impl(
            cache, small, jnp.asarray(own[:, :nb_prefill].reshape(-1)))
        return engine._write_state_impl(cache, small, jnp.arange(2))

    # (the cache donated, as the engine's step donates it: the steps
    # dispatched ahead then share one buffer and do not pile up beside
    # the reference's temporaries)
    decode = jax.jit(model.decode_step_paged, donate_argnums=2)
    cache = prefill_and_place(params, jnp.asarray(padded), jnp.asarray(lens))
    # rows 2.. are IDLE slots, as an engine's are: their tables point at
    # the scratch block, their state rows hold zeros
    tables = np.full((DECODE_ROWS, nb_slot), n_blocks, np.int32)
    tables[:2] = own
    tables = jnp.asarray(tables)
    rows = np.arange(2)
    got = []
    for i in range(steps):
        # fresh arrays a step: a dispatched step may still read its inputs
        tokens, offsets = (np.zeros(DECODE_ROWS, np.int32) for _ in range(2))
        tokens[:2], offsets[:2] = seqs[rows, lens + i], lens + i
        logits, cache = decode(params, jnp.asarray(tokens), cache, tables,
                               jnp.asarray(offsets))
        if i < decode_steps:
            got.append(logits[:2])
    got = jnp.stack(got, axis=1).astype(jnp.float32)   # [2, decode_steps, V]
    at = lens[:, None] + np.arange(decode_steps)[None, :]
    want = jax.jit(lambda p, t: reference_forward(p, t)[
        rows[:, None], at])(params, jnp.asarray(seqs[:, :total]))
    diff = got - want
    rel_rms = float(jnp.sqrt(jnp.mean(diff ** 2) / jnp.mean(want ** 2)))
    max_abs = float(jnp.max(jnp.abs(diff)))
    argmax_same = float(jnp.mean(jnp.argmax(got, -1) == jnp.argmax(want, -1)))
    finite = bool(jnp.all(jnp.isfinite(got)))

    # the first layer's rows after ``steps`` steps: each row has consumed
    # its prompt and ``steps`` tokens more
    first_state = jax.jit(reference_first_state)
    heads, window = [], []
    for r in range(2):
        want_s, want_w = jax.device_get(first_state(
            params, jnp.asarray(seqs[r:r + 1, :lens[r] + steps])))
        heads.append(worst_head(model.state_heads(cache["ssm"][0, r]),
                                want_s))
        # (a window [K-1, C] as one "head": the same relative RMS)
        window.append(worst_head(cache["conv"][0, r].astype(jnp.float32),
                                 want_w))
    # (numpy's max: a NaN in either row is the reading, and fails)
    heads, window = float(np.max(heads)), float(np.max(window))
    state_ok = heads <= tol_state and window <= tol_state
    return {"ok": finite and rel_rms <= tol_rel_rms and state_ok,
            "logits_rel_rms": rel_rms, "logits_max_abs_diff": max_abs,
            "argmax_agreement": argmax_same, "tolerance_rel_rms": tol_rel_rms,
            "positions": 2 * decode_steps,
            "state_worst_head_rel_rms": heads,
            "state_conv_window_rel_rms": window,
            "state_steps": steps, "tolerance_state_rel_rms": tol_state}


def deploy_and_check(run):
    """``lib.serving.deploy_and_check`` with ``check_logits_state``. The
    program's configuration is built FIRST: a program without the model
    fails here, before any runtime is started."""
    import ray_tpu

    from benchmark.lib.bench_server import SERVERS

    tr, cfg = run.traffic, run.config
    eng = tr["engine"]
    model_config = run.builder.program_config(cfg, eng["max_seq"])
    ray_tpu.init()
    run.phase("runtime_init")
    handle = start(model_config, model_id=run.workload.replace(".", "-"),
                   engine=eng, seed=run.jax_seed)
    run.phase("deploy_and_init_weights")
    cc = tr["correctness"]
    reference = run.builder.reference_forward(cfg)
    checks = check_logits_state(
        SERVERS[-1], reference, run.builder.reference_first_state(cfg),
        seed=run.seed, prompt_len=cc["prompt_len"],
        decode_steps=cc["decode_steps"],
        tol_rel_rms=cc["tolerance_rel_rms"],
        state_steps=cc["state"]["decode_steps"],
        tol_state=cc["state"]["tolerance_worst_head_rel_rms"])
    greedy = check_greedy(
        handle, SERVERS[-1], reference, seed=run.seed,
        vocab=cfg["vocab_size"], prompt_lens=cc["greedy"]["prompt_lens"],
        tokens=cc["greedy"]["tokens"],
        margin_rel_rms=cc["greedy"]["margin_rel_rms"])
    checks = {**checks, **greedy, "ok": checks["ok"] and greedy["ok"]}
    run.phase("correctness_check")
    return handle, checks


def closed_loop(run) -> dict:
    import ray_tpu
    from ray_tpu import serve

    tr, cfg = run.traffic, run.config
    eng = tr["engine"]
    n_clients = int(tr["clients"])
    plen = int(tr["prompt_len"]["value"])
    max_tokens = int(tr["max_tokens"])
    vocab = cfg["vocab_size"]

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

    def engine_steps() -> int:
        return serving.engine_stats(handle)["decode_steps"]

    for i, t in enumerate(threads):       # one admission at a time
        t.start()
        wait_tokens(i, 1)
        if i == 0:
            # the first request decodes in every step from here on: the
            # engine's count of its tokens is one more than its steps
            # since (read a step or two late, so a step or two short)
            steps_first = engine_steps()
    for i in range(n_clients):
        wait_tokens(i, int(tr["min_streamed_before_window"]))
    run.phase("admit_clients")

    before = serving.engine_stats(handle)
    counts0 = [len(s) for s in stamps]
    compiles0 = run.compiles.snapshot()["requests"]
    t_open = run.open_window()
    # one token a slot a step: the window's steps may not outrun the
    # tokens the longest-lived request had left when it opened, by the
    # engine's count (its first request's) or its client's, whichever
    # is further on
    head_start = max(max(counts0), 1 + before["decode_steps"] - steps_first)
    steps_cap = max_tokens - head_start
    watch_for_stalls(run, stamps, stop)
    run.trace_during(t_open, tr.get("trace_seconds", 4),
                     snapshot=lambda: serving.engine_stats(handle))
    close = watch_window(engine_steps, t_open=t_open, seconds=run.seconds,
                         steps_open=before["decode_steps"],
                         steps_cap=steps_cap)
    t_closed = close.t_closed
    counts1 = [len(s) for s in stamps]
    stop.set()          # a request that ends from here on is not sent again
    # the clients are judged as the window closes: once the replica goes
    # down under them (below) every stream ends in an error that is the
    # shutdown's, not the system's
    last_stamp = [s[-1] if s else None for s in stamps]
    errored = [any(r.error for r in records[i]) for i in range(n_clients)]
    compiles1 = run.compiles.snapshot()["requests"]
    after = serving.engine_stats(handle)
    run.finish_trace()

    gaps = sorted(b - a for s, n in zip(stamps, counts1)
                  for a, b in zip(s[:n], s[1:n]) if t_open <= a)
    median_gap = gaps[len(gaps) // 2] if gaps else 0.0
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
        message = mis_sized(run.workload, close, t_open, steps_cap, max_tokens)
        if message:
            raise drivers.MisSized(message)

    # how the window's inter-token gaps spread: a uniformly slow run and
    # one that stalled once read the same rate and differ here
    gap_ms = None if run.tiny or not gaps else {
        f"p{q}": 1e3 * percentile(gaps, q) for q in (50, 90, 99, 100)}
    dead = set(silent) - set(resumed)
    failed = sum(bool(errored[i] or i in dead) for i in range(n_clients))
    requests = [r for rs in records for r in rs]
    ended = requests_ended(requests, t_open, t_closed)
    steps = after["decode_steps"] - before["decode_steps"]
    window_s = t_closed - t_open
    steps_per_s = cap = None            # a CPU run (--tiny-cpu) names no rate
    rates = ""
    if not run.tiny:
        steps_per_s = steps / window_s
        # the rate past which the window begins to shrink
        cap = (steps_cap - close.margin_steps) / run.seconds
        rates = f" ({steps_per_s:.1f} steps/s; the window shrinks past {cap:.1f})"
    run.log(f"window closed "
            + (f"EARLY after {window_s:.1f} of {run.seconds:.0f} s, "
               f"{close.margin_steps} steps before the engine's first "
               f"request ends" if close.closed_early
               else f"at its {run.seconds:.0f} s")
            + f": {steps} decode steps of the {steps_cap} a request of "
            f"{max_tokens} tokens had left{rates}; {close.looks} look(s) at "
            f"the engine; {ended} request(s) ended inside it"
            + (": the close came too late, the window held refill and "
               "grouped prefill, not this cell's work" if ended else ""))
    return {
        "kind": "serve_closed", "correct": bool(checks["ok"]) and failed == 0,
        "attempted": n_clients, "failed": failed, "checks": checks,
        "t_open": t_open, "t_close": t_closed,
        "stamps": stamps,
        "requests": requests,
        "engine_before": before, "engine_after": after,
        "slots": eng["max_slots"],
        "first_tokens_in_window": sum(
            1 for r in requests
            if r.first_token_at and t_open <= r.first_token_at <= t_closed),
        "tokens_received_in_window": sum(counts1) - sum(counts0),
        "requests_ended_in_window": ended,
        "window_s": window_s, "closed_early": close.closed_early,
        "cap_steps": steps_cap, "margin_steps": close.margin_steps,
        "stalled_at_close": len(resumed),
        "decode_steps_per_s": steps_per_s, "cap_steps_per_s": cap,
        "token_gap_ms": gap_ms,
        "engine_trace_edges": run.trace_edges,
        "compiles_in_window": compiles1 - compiles0,
    }


def run(run) -> dict:
    """``closed_loop``'s record without ``moe_expert_load`` (module
    docstring: the line has to stay short enough to be read)."""
    record = closed_loop(run)
    for stats in (record["engine_before"], record["engine_after"],
                  *record["engine_trace_edges"]):
        stats.pop("moe_expert_load", None)
    return record
