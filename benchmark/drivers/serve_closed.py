"""Closed loop: N streaming clients, one per decode slot, each sending
its next request only when the last one ended.

The window holds the same work in every run: all clients are admitted
one at a time during set-up (one prefill shape: 1 x bucket) and have
streamed a few tokens before the window opens, and NO REQUEST ENDS
INSIDE IT — no prefill, admission or refill inside the window — at any
step rate: the window closes at ``run_seconds`` OR just before the
engine finishes its first request, whichever comes first
(``watch_window``). ``max_tokens`` is sized so that at today's step
rates the first never comes late: up to the rate the run prints as its
cap every window lasts ``run_seconds``; past it the window SHRINKS (the
tokens measured stay what the cell was sized for, the seconds fall) and
the run says so (``counts.closed_early``, ``counts.window_s``, a line
on stderr). A window that would come out under ``MIN_WINDOW_S`` is no
measurement: the run fails and names the cell as mis-sized. What counts
is the ENGINE's step count, read through its public ``stats``: a slot
empties when the engine, not its client, reaches ``max_tokens``, and
the clients lag the engine by up to thousands of tokens. The rate is
taken per client over WHOLE inter-token intervals
(``lib.records.whole_interval_rate``) between the open and the close as
it really came, and summed.

A traced run also reads the engine's ``stats`` at both edges of the
traced span (``engine_trace_edges``): ``decode_program_roofline``
counts the K/V the timed program read from them.
"""

from __future__ import annotations

import dataclasses
import math
import threading
import time

from benchmark import drivers
from benchmark.lib import serving
from benchmark.lib.records import RequestRecord, percentile

STALL_FACTOR = 20        # a client silent for 20 median gaps has stalled
STALL_DUMP_S = 0.5       # no token anywhere for this long: say where
STALL_GRACE_S = 20.0     # a stream silent at the close may still resume
MIN_WINDOW_S = 12.0      # a shorter window is no measurement (the trace
                         # alone takes seconds 2-6): the cell is mis-sized
LOOK_S = 1.0             # between two looks at the engine's steps near the cap
LOOK_FAR_S = 6.0         # and while the cap is over two such gaps away: a look
                         # reads an expert model's load off the device from
                         # the replica's thread, so no more of them than the
                         # close needs (PERF.md, PR 36); the second look
                         # comes at 7 s, past the traced span (seconds 2-6)
MIN_MARGIN_STEPS = 64    # the close keeps at least this far from the cap


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


@dataclasses.dataclass
class WindowClose:
    t_closed: float
    closed_early: bool      # before ``run_seconds``: the cap came first
    steps_seen: int         # the engine's steps since the open, last look
    margin_steps: int       # kept between the close and the cap
    looks: int


def watch_window(engine_steps, *, t_open: float, seconds: float,
                 steps_open: int, steps_cap: int,
                 clock=time.perf_counter, sleep=time.sleep) -> WindowClose:
    """Wait for the window's close and say which it was: ``t_open +
    seconds``, or the look at which the engine stood within a margin of
    ``steps_cap``, the decode steps since the open after which its first
    request ends (one token a slot a step).

    ``engine_steps()`` reads the engine's ``decode_steps``: every
    ``LOOK_S`` while no rate is known or the engine, at the highest rate
    seen between two looks, could come within the margin of the cap in
    two ``LOOK_FAR_S``; every ``LOOK_FAR_S`` while it is further off.
    The margin is the steps of two ``LOOK_S`` at that rate (a stall in
    one interval must not shrink it), and never under
    ``MIN_MARGIN_STEPS``: a look that finds the engine just short of the
    margin is followed by one within ``LOOK_S``, still a whole
    ``LOOK_S`` of steps before any request ends. A look is the call the
    traced span makes at both its edges; far from the cap none falls
    inside that span, near it (every ``LOOK_S``) about four do."""
    t_limit = t_open + seconds
    t_prev, s_prev = t_open, steps_open
    margin, looks, rate = MIN_MARGIN_STEPS, 0, 0.0
    while True:
        now = clock()
        left = steps_cap - margin - (s_prev - steps_open)
        far = rate > 0 and left > 2 * LOOK_FAR_S * rate
        gap = LOOK_FAR_S if far else LOOK_S
        if now + gap >= t_limit:
            sleep(max(0.0, t_limit - now))
            return WindowClose(clock(), False, s_prev - steps_open, margin,
                               looks)
        sleep(gap)
        steps, now, looks = engine_steps(), clock(), looks + 1
        rate = max(rate, (steps - s_prev) / max(now - t_prev, 1e-9))
        margin = max(MIN_MARGIN_STEPS, math.ceil(2 * LOOK_S * rate))
        if steps - steps_open >= steps_cap - margin:
            return WindowClose(now, True, steps - steps_open, margin, looks)
        t_prev, s_prev = now, steps


def mis_sized(workload: str, close: WindowClose, t_open: float,
              steps_cap: int, max_tokens: int):
    """The message of a window that closed under ``MIN_WINDOW_S``, or
    ``None``: a rate from so few seconds is not reported."""
    window_s = close.t_closed - t_open
    if not close.closed_early or window_s >= MIN_WINDOW_S:
        return None
    return (f"{workload} is MIS-SIZED for this engine: its first request "
            f"of {max_tokens} tokens was {close.margin_steps} steps from "
            f"its end {window_s:.1f} s after the window opened "
            f"({close.steps_seen} of {steps_cap} decode steps), under the "
            f"{MIN_WINDOW_S:.0f} s a window must last; no rate is "
            f"reported. The cell needs longer requests, more slots or a "
            f"deeper model: a benchmark PR's.")


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
