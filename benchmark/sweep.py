"""Find the knee of an open-loop cell ONCE, on the chip, in one process:
one set-up, then one window per rate.

    python3 -m benchmark.sweep --workload <cell> --seed <n> --seconds <s> --rates 1.5,2.5,3.5

The knee is the highest swept rate at which the backlog does not grow
and 90 % of the window's requests meet both limits; the limits are twice
the median TTFT and twice the median TPOT read at the lowest swept rate.
The result goes into the traffic file by hand (``knee``, ``limits``,
``arrival.rate_per_s`` = 0.8 x knee); it is then data and does not move.
A benchmark run never searches for a rate.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

from benchmark import run as harness
from benchmark.lib.records import percentile


def main() -> int:
    ap = argparse.ArgumentParser(prog="python3 -m benchmark.sweep")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=40)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--tiny-cpu", action="store_true")
    args = ap.parse_args()
    args.trace = 0
    rates = sorted(float(r) for r in args.rates.split(","))
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    run = harness.Run(args, bench)

    if harness.start_backend(run, trace=False) is None:
        return harness.EXIT_NO_CHIP
    from benchmark.drivers import serve_open
    handle, checks, warm = serve_open.setup(run)
    rows, limits = [], None
    for k, rate in enumerate(rates):
        rec = serve_open.play(run, handle, rate, args.seconds,
                              1.0 if run.tiny else float(run.traffic["warmup_s"]),
                              salt=k + 1)
        ok = [r for r in rec["requests"] if r.ok]
        ttft = [r.ttft_s for r in ok if r.ttft_s is not None]
        tpot = [r.tpot_s for r in ok if r.tpot_s is not None]
        if limits is None:
            limits = {"ttft_s": 2 * percentile(ttft, 50),
                      "tpot_s": 2 * percentile(tpot, 50)}
        met = sum(1 for r in ok if r.ttft_s is not None
                  and r.ttft_s <= limits["ttft_s"]
                  and (r.tpot_s is None or r.tpot_s <= limits["tpot_s"]))
        a, b = rec["engine_before"], rec["engine_after"]
        row = {"rate_per_s": rate, "attempted": rec["attempted"],
               "failed": rec["failed"],
               "met_both_share": met / max(1, rec["attempted"]),
               "backlog_at_close": rec["backlog_at_close"],
               "ttft_p50_ms": 1e3 * percentile(ttft, 50),
               "ttft_p90_ms": 1e3 * percentile(ttft, 90),
               "tpot_p50_ms": 1e3 * percentile(tpot, 50),
               "tpot_p90_ms": 1e3 * percentile(tpot, 90),
               "decode_steps_per_s": (b["decode_steps"] - a["decode_steps"])
               / args.seconds,
               "tokens_per_s": (b["tokens_generated"] - a["tokens_generated"])
               / args.seconds,
               "preemptions": b["preemptions"] - a["preemptions"],
               "prefix_prefills": b["prefix_prefills"] - a["prefix_prefills"],
               "compiles_in_window": rec["compiles_in_window"]}
        rows.append(row)
        print("SWEEP " + json.dumps(row), flush=True)
    good = [r["rate_per_s"] for r in rows
            if r["met_both_share"] >= 0.9 and r["backlog_at_close"] <= 2]
    print("SWEEP_RESULT " + json.dumps({
        "limits": limits, "knee_rate_per_s": max(good) if good else None,
        "checks": checks, "warm": warm, "setup_phases": run.phases}),
        flush=True)
    sys.stdout.flush()
    os._exit(0)


if __name__ == "__main__":
    sys.exit(main())
