"""Open loop: requests are sent on a schedule fixed by the traffic file
and the seed, whether or not earlier ones have finished, so a slow
system builds a queue instead of receiving less load. TTFT is timed
from the moment a request was DUE.

The schedule is ``lib.schedule.cyclic_schedule``: the same multiset of
gaps and lengths for every seed, rotated by the seed. Warm-up traffic
(the part of the cycle before the window) brings the batch to its steady
occupancy; requests due inside the window are all read to their end
after it closes, and every one is counted.
"""

from __future__ import annotations

import threading
import time

from benchmark.lib import serving
from benchmark.lib.records import RequestRecord
from benchmark.lib.schedule import cyclic_schedule


def chunked_lengths(traffic: dict) -> list:
    """One prompt length per chunked-prefill shape the traffic can
    reach: prompts longer than the largest bucket prefill in chunks of
    it, each chunk attending over a power-of-two padded prefix."""
    buckets = sorted(traffic["prefill_buckets"])
    big, longest = buckets[-1], int(traffic["prompt_len"]["max"])
    out = []
    full = 1
    while full * big < longest:
        out.extend(full * big + b for b in buckets
                   if full * big + b <= longest)
        full += 1
    return out


def setup(run):
    """Runtime, deployment, correctness check and warm-up by shape;
    returns ``(handle, checks, warm)``."""
    tr, cfg = run.traffic, run.config
    handle, checks = serving.deploy_and_check(run)

    plen = tr["prompt_len"]
    shortest = int(plen.get("min", plen.get("value", 1)))
    longest = int(plen.get("max", plen.get("value", 1)))
    buckets = sorted(tr["prefill_buckets"])
    reach = [b for i, b in enumerate(buckets)
             if b >= shortest and (i == 0 or buckets[i - 1] < longest)]
    warm = serving.warm_shapes(
        handle, seed=run.seed, vocab=cfg["vocab_size"], buckets=reach,
        group_sizes=tr["warm_group_sizes"],
        chunked_lens=chunked_lengths(tr), log=run.log)
    run.phase("warm_prefill_shapes")
    return handle, checks, warm


def play(run, handle, rate: float, seconds: float, warmup_s: float,
         trace: bool = False, salt: int = 0) -> dict:
    """Warm-up traffic, one window at ``rate``, and the drain. ``salt``
    keeps the prompts of several windows in one process (the sweep)
    apart: a prompt seen before would hit the prefix cache."""
    tr, cfg = run.traffic, run.config
    vocab = cfg["vocab_size"]
    tr = {**tr, "arrival": {**tr["arrival"], "rate_per_s": rate}}
    schedule = cyclic_schedule(tr, run.traffic_name, run.seed, seconds,
                               warmup_s)
    prompts = [serving.make_prompt(run.seed, salt * 1_000_000 + n,
                                   item["prompt_len"], vocab)
               for n, item in enumerate(schedule)]
    t_zero = time.perf_counter() + warmup_s + 0.25   # the window opens here
    records = [RequestRecord(index=n, due_at=t_zero + item["due"],
                             in_window=item["in_window"])
               for n, item in enumerate(schedule)]

    def send(n: int):
        rec = records[n]
        delay = rec.due_at - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        serving.stream_request(handle, prompts[n],
                               schedule[n]["max_tokens"], rec)

    threads = [threading.Thread(target=send, args=(n,), daemon=True,
                                name=f"bench-req-{n}")
               for n in range(len(schedule))]
    for t in threads:
        t.start()

    time.sleep(max(0.0, t_zero - time.perf_counter()))
    before = serving.engine_stats(handle)
    compiles0 = run.compiles.snapshot()["requests"]
    t_open = run.open_window(t_zero)
    t_close = t_open + seconds
    if trace:
        run.trace_during(t_open, tr.get("trace_seconds", 4))
    time.sleep(max(0.0, t_close - time.perf_counter()))
    compiles1 = run.compiles.snapshot()["requests"]
    after = serving.engine_stats(handle)
    # sent, nothing back yet: what is still queued when the window closes
    backlog_at_close = sum(1 for r in records
                           if r.sent_at is not None and not r.error
                           and r.first_token_at is None)
    if trace:
        run.finish_trace()

    deadline = time.perf_counter() + float(tr.get("drain_timeout_s", 120))
    for t in threads:
        t.join(max(0.0, deadline - time.perf_counter()))
    for rec, t in zip(records, threads):
        if t.is_alive() and rec.error is None:
            rec.error = "not finished when the drain timed out"

    window = [r for r in records if r.in_window]
    failed = sum(1 for r in window
                 if not r.ok or r.output_tokens != r.max_tokens)
    in_win = [r for r in records if r.first_token_at
              and t_open <= r.first_token_at <= t_close]
    return {
        "attempted": len(window), "failed": failed,
        "t_open": t_open, "t_close": t_close,
        "requests": window, "all_requests": records,
        "engine_before": before, "engine_after": after,
        "slots": tr["engine"]["max_slots"], "offered_rate_per_s": rate,
        "backlog_at_close": backlog_at_close,
        "first_tokens_in_window": len(in_win),
        "compiles_in_window": compiles1 - compiles0,
    }


def run(run) -> dict:
    import ray_tpu
    from ray_tpu import serve

    tr = run.traffic
    handle, checks, warm = setup(run)
    rate = tr["arrival"]["rate_per_s"]
    warmup_s = float(tr["warmup_s"]) if not run.tiny else 1.0
    rec = play(run, handle, rate, run.seconds, warmup_s,
               trace=run.tracer is not None)
    serve.shutdown()
    ray_tpu.shutdown()
    rec.update(kind="serve_open", checks=checks, warm=warm,
               correct=bool(checks["ok"]) and rec["failed"] == 0)
    if not run.tiny:             # a CPU run reports no time
        rec["client_ms"] = client_ms(rec)
    return rec


def client_ms(rec: dict) -> dict:
    """Time to first token at the client, in ms. NO metric of a cell of
    this kind reports it yet: over the ~134 requests of a window it
    spreads too widely from run to run to hold a bound (PERF.md), so it
    is printed with every run under ``counts`` (the driver ignores it)
    for the ``benchmark`` PR that gives TTFT a mix of its own."""
    from benchmark.lib import readers
    ttft = [r.ttft_s for r in readers.ok_requests(rec) if r.ttft_s is not None]
    return {"ttft_p50": readers.ttft_percentile_ms(rec, 50),
            "ttft_p90": readers.ttft_percentile_ms(rec, 90),
            "ttft_mean": 1e3 * sum(ttft) / len(ttft) if ttft else None,
            "ttft_overhead_p50": readers.ttft_overhead_ms(rec),
            "gen_lateness_p90": readers.gen_lateness_ms(rec)}
