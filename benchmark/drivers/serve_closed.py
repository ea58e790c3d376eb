"""Closed loop: N streaming clients, one per decode slot, each sending
its next request only when the last one ended.

The window holds the same work in every run: all clients are admitted
one at a time during set-up (one prefill shape: 1 x bucket) and have
streamed a few tokens before the window opens, and ``max_tokens`` is
sized so that no request ends inside it — no prefill, admission or
refill inside the window — up to the step rate the run prints as its
cap. Past the cap requests END inside the window: the clients resubmit,
the engine prefills the resubmissions in groups no set-up ran (they
compile there), and the window holds another cell's work. Such a run is
reported as that (``requests_ended_in_window``, a line on stderr);
``correct`` keeps its meaning. The rate is taken per client over WHOLE
inter-token intervals (``lib.records.whole_interval_rate``) and summed.

A traced run also reads the engine's ``stats`` at both edges of the
traced span (``engine_trace_edges``): ``decode_program_roofline``
counts the K/V the timed program read from them.
"""

from __future__ import annotations

import threading
import time

from benchmark.lib import serving
from benchmark.lib.records import RequestRecord, percentile

STALL_FACTOR = 20        # a client silent for 20 median gaps has stalled
STALL_DUMP_S = 0.5       # no token anywhere for this long: say where
STALL_GRACE_S = 20.0     # a stream silent at the close may still resume


def watch_for_stalls(run, stamps, stop, limit: int = 3):
    """One run in about ten reads 3-16 % low because every stream stood
    still ONCE for 1.5-8 s (PR 30: the median gap of such a run is the
    others'). Where were the threads then? Ten looks a second at one
    sum; on a stall, the stacks that are not parked go to stderr. The
    one caught so far (PR 30, fix round, 3.5 s): every thread parked
    but the engine's, inside the runtime's device-to-host copy of the
    sampled tokens (``np.asarray`` in ``_decode_step``). Stalls of
    0.9 and 6.2 s passed with no dump at all: this thread stood still
    too, so the whole process did. The chip's machine is a sandbox
    whose ``/proc`` says nothing (no pressure, ``stat`` all zeros)."""
    import sys
    import traceback

    def work():
        seen, since, dumped = -1, time.perf_counter(), 0
        while not stop.is_set() and dumped < limit:
            time.sleep(0.1)
            n, now = sum(len(s) for s in stamps), time.perf_counter()
            if n != seen:
                seen, since = n, now
            elif now - since > STALL_DUMP_S:
                dumped += 1
                run.log(f"STALL: no token for {now - since:.2f} s, "
                        f"{now - run.t_open:.1f} s into the window")
                names = {t.ident: t.name for t in threading.enumerate()}
                for tid, frame in sys._current_frames().items():
                    name = names.get(tid, "?")
                    text = "".join(traceback.format_stack(frame)[-5:])
                    if (name.startswith("bench-") or "_sync_main" in text
                            or "_read_loop" in text):
                        continue        # the benchmark's own, or parked
                    print(f"-- thread {name}\n{text}", file=sys.stderr)
                sys.stderr.flush()
                since = now + 5.0       # say it once a stall

    threading.Thread(target=work, daemon=True,
                     name="bench-stall-watch").start()


def silent_at(last_stamp, t: float, limit: float):
    """The clients whose last token is older than ``limit`` at ``t``."""
    return [i for i, s in enumerate(last_stamp)
            if s is None or t - s > limit]


def resumed_by(stamps, last_stamp, silent, deadline: float,
               clock=time.perf_counter, sleep=time.sleep):
    """Of the clients ``silent`` at the close, those whose stream gave
    another token by ``deadline``: they stood still, they are not dead.

    The runtime stands still for 1.5-8 s in one run of ten (above). A
    stall that straddles the close left every client silent there, and
    the run read ``failed`` 31-32 for outputs that were sound (PR 29's
    ``bd1/5.P``). A dead stream never resumes and still fails; the
    window's rate is untouched, the stall is inside it either way."""
    waiting = list(silent)
    while waiting:
        waiting = [i for i in waiting
                   if not stamps[i] or stamps[i][-1] == last_stamp[i]]
        if not waiting or clock() >= deadline:
            break
        sleep(0.01)
    return [i for i in silent if i not in waiting]


def requests_ended(records, lo: float, hi: float) -> int:
    """Requests whose finish chunk reached its client inside ``[lo, hi]``."""
    return sum(1 for r in records
               if r.done_at is not None and lo <= r.done_at <= hi)


def run(run) -> dict:
    import ray_tpu
    from ray_tpu import serve

    tr, cfg = run.traffic, run.config
    eng = tr["engine"]
    n_clients = int(tr["clients"])
    plen = int(tr["prompt_len"]["value"])
    max_tokens = int(tr["max_tokens"])
    vocab = cfg["vocab_size"]

    handle, checks = serving.deploy_and_check(run)

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

    for i, t in enumerate(threads):       # one admission at a time
        t.start()
        wait_tokens(i, 1)
    for i in range(n_clients):
        wait_tokens(i, int(tr["min_streamed_before_window"]))
    run.phase("admit_clients")

    before = serving.engine_stats(handle)
    counts0 = [len(s) for s in stamps]
    compiles0 = run.compiles.snapshot()["requests"]
    t_open = run.open_window()
    t_close = t_open + run.seconds
    watch_for_stalls(run, stamps, stop)
    run.trace_during(t_open, tr.get("trace_seconds", 4),
                     snapshot=lambda: serving.engine_stats(handle))
    time.sleep(max(0.0, t_close - time.perf_counter()))
    t_closed = time.perf_counter()
    counts1 = [len(s) for s in stamps]
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
    stop.set()
    serve.shutdown()
    ray_tpu.shutdown()

    # how the window's inter-token gaps spread: a uniformly slow run and
    # one that stalled once read the same rate and differ here
    gap_ms = None if run.tiny or not gaps else {
        f"p{q}": 1e3 * percentile(gaps, q) for q in (50, 90, 99, 100)}
    dead = set(silent) - set(resumed)
    failed = sum(bool(errored[i] or i in dead) for i in range(n_clients))
    requests = [r for rs in records for r in rs]
    ended = requests_ended(requests, t_open, t_closed)
    # one token a slot a step: the window's steps may not outrun the
    # tokens the shortest-lived request had left when it opened
    steps = after["decode_steps"] - before["decode_steps"]
    steps_cap = max_tokens - max(counts0)
    steps_per_s = cap = None            # a CPU run (--tiny-cpu) names no rate
    rates = ""
    if not run.tiny:
        steps_per_s = steps / (t_closed - t_open)
        cap = steps_cap / (t_closed - t_open)
        rates = f" ({steps_per_s:.1f} against {cap:.1f} steps/s)"
    run.log(f"{steps} decode steps in the window; a request of {max_tokens} "
            f"tokens outlasts it up to {steps_cap}{rates}; {ended} "
            f"request(s) ended inside it"
            + (": PAST THE CAP, the window held refill and grouped prefill, "
               "not this cell's work" if ended else ""))
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
        "stalled_at_close": len(resumed),
        "decode_steps_per_s": steps_per_s, "cap_steps_per_s": cap,
        "token_gap_ms": gap_ms,
        "engine_trace_edges": run.trace_edges,
        "compiles_in_window": compiles1 - compiles0,
    }
