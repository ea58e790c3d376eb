"""Closed loop: N streaming clients, one per decode slot, each sending
its next request only when the last one ended.

The window holds the same work in every run: all clients are admitted
one at a time during set-up (one prefill shape: 1 x bucket) and have
streamed a few tokens before the window opens, and ``max_tokens`` is
sized so that no request ends inside it — no prefill, admission or
refill inside the window. The rate is taken per client over WHOLE
inter-token intervals (``lib.records.whole_interval_rate``) and summed.
"""

from __future__ import annotations

import threading
import time

from benchmark.lib import serving
from benchmark.lib.records import RequestRecord

STALL_FACTOR = 20        # a client silent for 20 median gaps has stalled


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
    run.trace_during(t_open, tr.get("trace_seconds", 4))
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

    # the streams cannot be cancelled through the API and have minutes
    # to go: the replica goes down under them
    stop.set()
    serve.shutdown()
    ray_tpu.shutdown()

    gaps = sorted(b - a for s in stamps for a, b in zip(s, s[1:])
                  if t_open <= a and b <= t_closed)
    median_gap = gaps[len(gaps) // 2] if gaps else 0.0
    failed = 0
    for i in range(n_clients):
        silent = (last_stamp[i] is None or t_closed - last_stamp[i]
                  > max(1.0, STALL_FACTOR * median_gap))
        failed += bool(errored[i] or silent)
    mid = [(a + b) / 2 for a, b in zip(counts0, counts1)]
    return {
        "kind": "serve_closed", "correct": bool(checks["ok"]) and failed == 0,
        "attempted": n_clients, "failed": failed, "checks": checks,
        "t_open": t_open, "t_close": t_closed,
        "stamps": stamps,
        "requests": [r for rs in records for r in rs],
        "engine_before": before, "engine_after": after,
        "slots": eng["max_slots"],
        "first_tokens_in_window": sum(
            1 for rs in records for r in rs
            if r.first_token_at and t_open <= r.first_token_at <= t_closed),
        "tokens_received_in_window": sum(counts1) - sum(counts0),
        # tokens cached over all slots mid-window, while every request is
        # its client's first: prompt + what it has streamed
        "live_tokens_total": sum(plen + m for m in mid),
        "compiles_in_window": compiles1 - compiles0,
    }
