#!/usr/bin/env bash
# Control-plane perf smoke: a ~10-second mini envelope (tasks/s + a
# queued-submit drain) compared against the committed floor. Fails
# (exit 1) when any probe regresses more than 30% below its floor —
# wire it into CI next to the tier-1 tests (see docs/performance.md).
#
# Usage:
#   tools/perf_smoke.sh                      # in-process topology
#   tools/perf_smoke.sh daemons              # head+daemon wire topology
#   tools/perf_smoke.sh [daemons] --rebaseline   # rewrite the floor
#
# Floors live per topology: tools/perf_floor.json (in-process) and
# tools/perf_floor_daemons.json (daemons).
set -euo pipefail
cd "$(dirname "$0")/.."

REBASE=""
FLOOR="tools/perf_floor.json"
for arg in "$@"; do
    case "$arg" in
        --rebaseline) REBASE="--rebaseline" ;;
        daemons)
            export RAY_TPU_CLUSTER=daemons
            FLOOR="tools/perf_floor_daemons.json"
            ;;
        local|in-process) ;;
        *) echo "unknown argument: $arg" >&2; exit 2 ;;
    esac
done
export JAX_PLATFORMS=cpu
export RAY_TPU_LOG_TO_DRIVER=0
export PERF_SMOKE_FLOOR="$FLOOR"
# NOTE: probe 9's lag sampler reads the gauge at the DEFAULT 250ms
# probe interval on purpose. Arming a faster probe (50ms) to get more
# samples measurably perturbs the paired overhead probes on a
# GIL-saturated burst — three consecutive runs tripped the tracing
# gate with a fast probe armed, none with the default.

python - $REBASE <<'EOF'
import json
import os
import sys
import time

FLOOR_PATH = os.environ["PERF_SMOKE_FLOOR"]
TOLERANCE = 0.30    # fail on >30% regression vs the committed floor
rebaseline = "--rebaseline" in sys.argv

import ray_tpu  # noqa: E402

ray_tpu.init(num_nodes=1, resources={"CPU": 8})


@ray_tpu.remote
def noop():
    return None


@ray_tpu.remote(num_returns=2)
def duo():
    return None, None


results = {}

# probe 1: round-trip tasks/s (~4s)
ray_tpu.get([noop.remote() for _ in range(100)])    # warm
t0 = time.perf_counter()
count = 0
while time.perf_counter() - t0 < 4.0:
    ray_tpu.get([noop.remote() for _ in range(100)])
    count += 100
results["tasks_per_second"] = round(count / (time.perf_counter() - t0), 1)

# probe 2: queued burst submit rate + drain ratio (3k tasks, ~2-6s).
# queued_drain_ratio = drain rate ÷ submit rate — the drain-side
# result-pipeline metric (ROADMAP item 4: drain within 2x of submit
# means ratio >= 0.5). Ratio of the SAME burst, so box speed cancels.
t0 = time.perf_counter()
refs = [noop.remote() for _ in range(3000)]
t_submit = time.perf_counter() - t0
results["queued_submit_per_s"] = round(3000 / t_submit, 1)
ray_tpu.get(refs)
t_drain = time.perf_counter() - t0 - t_submit
# drain rate / submit rate = (N/t_drain) / (N/t_submit) = t_submit/t_drain
results["queued_drain_ratio"] = round(t_submit / t_drain, 3)

# probe 3: batched classic-path burst — exercises the submit coalescer
# wire path when this script is invoked with the `daemons` mode
# (process workers in-process otherwise). Tracing is ON by default, so
# this row measures the traced rate.


def burst_batched(n=600) -> float:
    t0 = time.perf_counter()
    refs = [duo.remote() for _ in range(n)]
    ray_tpu.get([r for ab in refs for r in ab])
    return n / (time.perf_counter() - t0)


burst_batched()     # warm the classic path
results["burst_batched_per_s"] = round(burst_batched(), 1)

# probe 4: tracing overhead — the same burst with spans ON vs OFF.
# MUST run before the object-plane probe: a put/get phase leaves the
# driver-side object bookkeeping in a state where traced bursts pay a
# consistent ~20% (see ROADMAP). This row measures the documented ~1%
# steady-state tracing tax on the burst path, not that interaction.
# Methodology: 5 PAIRED bursts in one cluster with BALANCED ordering
# (on-first on even rounds, off-first on odd) and the MEDIAN of the
# per-pair ratios; the raw per-pair ratios are printed with the verdict
# so a trip is diagnosable from the CI log. Anything weaker is a noise
# lottery on shared hardware: single-burst scatter here is +-25%, the
# real overhead ~1% (docs/observability.md). 3 pairs of 200-task
# bursts false-tripped repeatedly under correlated box load — one in
# eight even under pure coin-flip noise; 5 pairs of 300 needs 4/5
# slower AND an over-budget median. Budget: <= 5% on
# burst_submit_batched.
import statistics  # noqa: E402

from ray_tpu._private.config import apply_system_config  # noqa: E402


def traced_burst(on: bool) -> float:
    apply_system_config({"task_trace": on})
    return burst_batched(300)


ratios = []
for i in range(5):
    if i % 2 == 0:
        r_on = traced_burst(True)
        r_off = traced_burst(False)
    else:
        r_off = traced_burst(False)
        r_on = traced_burst(True)
    ratios.append(r_on / r_off)
apply_system_config(None)   # restore env/default flag resolution

# probe 7: continuous-sampler overhead — the same burst with the
# driver-process stack sampler ON (25 Hz, well above the suggested
# production 5-10 Hz) vs OFF, same interleaved-median methodology as
# the tracing row. Budget: <= 3% (docs/observability.md).
from ray_tpu.util import profiling as _profiling  # noqa: E402


def profiled_burst(on: bool) -> float:
    if on:
        _profiling.start_process_sampler("driver", hz=25.0)
    else:
        _profiling.stop_process_sampler()
    return burst_batched(300)


p_ratios = []
for i in range(5):
    if i % 2 == 0:
        p_on = profiled_burst(True)
        p_off = profiled_burst(False)
    else:
        p_off = profiled_burst(False)
        p_on = profiled_burst(True)
    p_ratios.append(p_on / p_off)
_profiling.stop_process_sampler()

# probe 6: object plane — worker-side 1MiB put+get round trips, in
# MiB/s moved (put and get each move the payload). In daemons mode
# this is the zero-copy arena path (direct put + frombuffer get); in
# the in-process topology it measures the worker-pipe round trip
# (docs/object_plane.md). Runs AFTER the paired overhead probes — see
# the probe 4 comment for the interaction this ordering avoids.
# The committed floor is the PRESSURE-DISARMED baseline: the
# memory_pressure subsystem (spill tier + PressureController) defaults
# off and this script never arms it, so the row doubles as the
# zero-overhead-when-disarmed gate for that subsystem
# (docs/fault_tolerance.md "Memory pressure & graceful degradation").


@ray_tpu.remote
def _put_get_1mib(seconds=1.5):
    import time as _time

    import numpy as _np

    import ray_tpu as _rt
    a = _np.ones((1 << 20) // 4, dtype=_np.float32)
    r = _rt.put(a)
    _rt.get([r])        # warm
    n = 0
    t0 = _time.perf_counter()
    while _time.perf_counter() - t0 < seconds:
        r = _rt.put(a)
        b = _rt.get([r])[0]
        assert b.nbytes == 1 << 20
        del b, r
        n += 1
    return n, _time.perf_counter() - t0


n_pg, dt_pg = ray_tpu.get(_put_get_1mib.remote(), timeout=60.0)
results["put_get_1MiB_mbps"] = round(n_pg * 2 / dt_pg, 1)

# probe 5: serving data plane — a small OPEN-LOOP burst through a
# 2-replica deployment via ray_tpu.loadgen (handle -> depth-aware P2C
# router -> replica), the row every serving-perf PR is gated against
# (docs/serving.md). Constant arrivals + fixed seed keep it stable.
from ray_tpu import serve  # noqa: E402
from ray_tpu.loadgen import (HandleTarget, LoadSpec,  # noqa: E402
                             SLO, run_load)


@serve.deployment(num_replicas=2, max_ongoing_requests=32)
def _smoke_echo(payload):
    return {"ok": True}


handle = serve.run(_smoke_echo.bind())
handle.remote({}).result(timeout=30)    # warm the route
spec = LoadSpec(rate=150.0, duration_s=2.0, clients=16,
                arrival="constant", stream=False, seed=0,
                prompt_len=4, output_len=1, slo=SLO(e2e_s=1.0),
                timeout_s=30, drain_timeout_s=60)
serving = run_load(HandleTarget(handle, stream=False, timeout_s=30),
                   spec)
results["serving_requests_per_s"] = serving["requests_per_second"]
if serving["requests"]["errors"]:
    print(f"serving probe errors: {serving['error_samples']}",
          file=sys.stderr)
    results["serving_requests_per_s"] = 0.0
serve.shutdown()
overhead = max(0.0, (1.0 - statistics.median(ratios)) * 100.0)
# Single-burst scatter on shared hardware is +-30-70%, far above the 5%
# budget, so the gate demands a CONSISTENT regression: a real overhead
# shows tracing slower in (nearly) every pair; noise flips signs. A
# median above budget with mixed signs reports but does not fail.
slower = sum(1 for r in ratios if r < 1.0)
consistent = slower >= len(ratios) - 1
results["tracing_overhead_pct"] = round(overhead, 1)
results["tracing_overhead_consistent"] = bool(consistent)
p_overhead = max(0.0, (1.0 - statistics.median(p_ratios)) * 100.0)
p_slower = sum(1 for r in p_ratios if r < 1.0)
p_consistent = p_slower >= len(p_ratios) - 1
results["profiling_overhead_pct"] = round(p_overhead, 1)
results["profiling_overhead_consistent"] = bool(p_consistent)

# probe 8: fair-share overhead on the queued-drain path — the SAME
# submit-then-drain burst with the tenancy subsystem disabled
# (`fairshare` off, the default — one enabled-flag check per submit)
# vs fairshare ON (verdicts + ledger ordering + quota gates + per-task
# accounting on the drain side). Both arms run on a FRESH cluster with
# an identical warm-up so neither inherits the long-warmed state of
# probes 1-7 (an asymmetric warm reads as fair-share overhead), and
# the arms alternate to spread box drift evenly.
# Budget: the ON path costs <= 3% drain rate vs OFF — which bounds the
# off-path tax at strictly less (docs/multitenancy.md).


def drain_rate(fairshare: bool, n=1500) -> float:
    kw = {"_system_config": {"fairshare": True}} if fairshare else {}
    ray_tpu.init(num_nodes=1, resources={"CPU": 8}, **kw)
    ray_tpu.get([noop.remote() for _ in range(300)])    # warm pools
    t0 = time.perf_counter()
    refs = [noop.remote() for _ in range(n)]
    t_submit = time.perf_counter() - t0
    ray_tpu.get(refs)
    rate = n / (time.perf_counter() - t0 - t_submit)
    ray_tpu.shutdown()
    return rate


ray_tpu.shutdown()
off_rates, on_rates = [], []
for _ in range(3):
    off_rates.append(drain_rate(False))
    on_rates.append(drain_rate(True))
fs_overhead = max(0.0, (1.0 - statistics.median(on_rates)
                        / statistics.median(off_rates)) * 100.0)
# cross-cluster rounds are noisier than same-cluster pairs: only a
# separation of the full samples counts as a consistent regression
fs_consistent = max(on_rates) < min(off_rates)
results["fairshare_overhead_pct"] = round(fs_overhead, 1)
results["fairshare_overhead_consistent"] = bool(fs_consistent)

# probe 9: event-loop lag under the queued submit→drain burst
# (docs/performance.md "Asyncio core"). Three fresh clusters, each with
# its own warm-up, submit and drain n tasks; while they run, a sampler
# thread records the PEAK driver-loop lag gauge — loop_lag_max_s is a
# budget row (lower is better) gated as a ceiling.
import threading  # noqa: E402

from ray_tpu.util import metrics as _metrics  # noqa: E402


def core_burst(n=2000) -> None:
    ray_tpu.init(num_nodes=1, resources={"CPU": 8})
    try:
        ray_tpu.get([noop.remote() for _ in range(300)])    # warm pools
        ray_tpu.get([noop.remote() for _ in range(n)])
    finally:
        ray_tpu.shutdown()


_lag_peak = [0.0]
_lag_stop = threading.Event()


def _sample_lag() -> None:
    # every gauge sample in this registry is this process's loop; max
    # over tags keys the row to the worst moment, not the last probe
    while not _lag_stop.wait(0.02):
        m = _metrics.registry().get("ray_tpu_event_loop_lag_seconds")
        if m is None:
            continue
        for _key, v in m.samples():
            if v > _lag_peak[0]:
                _lag_peak[0] = v


_sampler = threading.Thread(target=_sample_lag, daemon=True,
                            name="perf-smoke-lag-sampler")
_sampler.start()
for _ in range(3):
    core_burst()
_lag_stop.set()
_sampler.join(timeout=5.0)
results["loop_lag_max_s"] = round(_lag_peak[0], 3)

print(json.dumps(results, indent=2))

# tracing_overhead_pct / profiling_overhead_pct are BUDGET rows (lower
# is better), checked against fixed ceilings below — never against the
# rate floors.
TRACING_OVERHEAD_MAX = 5.0
PROFILING_OVERHEAD_MAX = 3.0
FAIRSHARE_OVERHEAD_MAX = 3.0

if rebaseline:
    floors = {k: v for k, v in results.items()
              if not k.startswith(("tracing_overhead",
                                   "profiling_overhead",
                                   "fairshare_overhead"))}
    with open(FLOOR_PATH, "w") as fh:
        json.dump(floors, fh, indent=2)
        fh.write("\n")
    print(f"wrote {FLOOR_PATH}")
    sys.exit(0)

try:
    with open(FLOOR_PATH) as fh:
        floors = json.load(fh)
except FileNotFoundError:
    print(f"no {FLOOR_PATH}; run tools/perf_smoke.sh "
          f"[daemons] --rebaseline")
    sys.exit(1)

# The object-plane row's daemons floor assumes the native shm arena;
# a no-compiler box runs the classic RPC path by design (graceful
# fallback) and must not fail the gate for missing g++.
try:
    from ray_tpu.native_store import available as _native_available
    _have_native = _native_available()
except Exception:
    _have_native = False
if not _have_native and "put_get_1MiB_mbps" in floors:
    print("put_get_1MiB_mbps: skipped (no native arena on this box; "
          "classic path is ungated)")
    floors.pop("put_get_1MiB_mbps")

failed = False
for name, floor in floors.items():
    if name.startswith(("tracing_overhead", "profiling_overhead",
                        "fairshare_overhead")):
        continue    # legacy floor entry: budget-checked below instead
    got = results.get(name, 0.0)
    if name.startswith("loop_lag"):
        # budget row, lower is better: the committed value is a
        # CEILING. The absolute slack is one probe interval — a real
        # blocking-callback regression shows sustained lag comparable
        # to the interval or worse, while a near-zero committed
        # baseline must not trip on one scheduler hiccup.
        limit = max(floor * (1.0 + TOLERANCE), floor + 0.25)
        verdict = "ok" if got <= limit else "REGRESSION"
        print(f"{name}: {got:.3f}s vs budget {floor:.3f}s "
              f"(max {limit:.3f}s) {verdict}")
        if got > limit:
            failed = True
        continue
    limit = floor * (1.0 - TOLERANCE)
    verdict = "ok" if got >= limit else "REGRESSION"
    if name.endswith("_ratio"):     # dimensionless rows (drain÷submit)
        print(f"{name}: {got:.2f} vs floor {floor:.2f} "
              f"(min {limit:.2f}) {verdict}")
    else:
        print(f"{name}: {got:,.0f}/s vs floor {floor:,.0f}/s "
              f"(min {limit:,.0f}/s) {verdict}")
    if got < limit:
        failed = True
trip = overhead > TRACING_OVERHEAD_MAX and consistent
verdict = ("REGRESSION" if trip else
           "ok" if overhead <= TRACING_OVERHEAD_MAX else
           "ok (noise: mixed-sign pairs)")
raw = "[" + ", ".join(f"{r:.3f}" for r in ratios) + "]"
print(f"tracing_overhead_pct: {overhead:.1f}% vs budget "
      f"{TRACING_OVERHEAD_MAX:.0f}% "
      f"({slower}/{len(ratios)} pairs slower, on/off ratios {raw}) "
      f"{verdict}")
if trip:
    failed = True
p_trip = p_overhead > PROFILING_OVERHEAD_MAX and p_consistent
p_verdict = ("REGRESSION" if p_trip else
             "ok" if p_overhead <= PROFILING_OVERHEAD_MAX else
             "ok (noise: mixed-sign pairs)")
p_raw = "[" + ", ".join(f"{r:.3f}" for r in p_ratios) + "]"
print(f"profiling_overhead_pct: {p_overhead:.1f}% vs budget "
      f"{PROFILING_OVERHEAD_MAX:.0f}% "
      f"({p_slower}/{len(p_ratios)} pairs slower, on/off ratios "
      f"{p_raw}) {p_verdict}")
if p_trip:
    failed = True
fs_trip = fs_overhead > FAIRSHARE_OVERHEAD_MAX and fs_consistent
fs_verdict = ("REGRESSION" if fs_trip else
              "ok" if fs_overhead <= FAIRSHARE_OVERHEAD_MAX else
              "ok (noise: overlapping samples)")
fs_raw = ("on=[" + ", ".join(f"{r:,.0f}" for r in on_rates)
          + "] off=[" + ", ".join(f"{r:,.0f}" for r in off_rates) + "]")
print(f"fairshare_overhead_pct: {fs_overhead:.1f}% vs budget "
      f"{FAIRSHARE_OVERHEAD_MAX:.0f}% (queued-drain rates/s {fs_raw}) "
      f"{fs_verdict}")
if fs_trip:
    failed = True
sys.exit(1 if failed else 0)
EOF
