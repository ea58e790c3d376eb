"""Generate the host-side core-op envelope kept in PERF.md section 3.

Runs `_private/perf.py` in both execution modes (in-process virtual
nodes, and real head+daemon OS processes) in fresh subprocesses and
renders one markdown table. These are HOST rates (control plane, CPU):
paste the output under "Control plane" in PERF.md; device metrics never
come from here. Usage: `python tools/gen_perf.py`.
"""

from __future__ import annotations

import json
import os
import platform
import re
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_mode(mode: str, envelope: bool = False) -> list:
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    env["JAX_PLATFORMS"] = "cpu"
    env["RAY_TPU_LOG_TO_DRIVER"] = "0"
    if envelope:
        env["PERF_ENVELOPE"] = "1"
    else:
        # the degraded retry must not re-inherit an exported
        # PERF_ENVELOPE=1 from the caller's environment
        env.pop("PERF_ENVELOPE", None)
    if mode == "daemons":
        env["RAY_TPU_CLUSTER"] = "daemons"
    else:
        env.pop("RAY_TPU_CLUSTER", None)
    out = subprocess.run(
        [sys.executable, "-m", "ray_tpu._private.perf"],
        capture_output=True, text=True, env=env,
        timeout=(3600 if envelope else 900))
    if out.returncode != 0:
        if envelope:
            # The envelope slices (100k drain / 5000 actors) exceed some
            # sandboxes' thread/PID limits and get SIGKILLed; degrade to
            # the core rows — the envelope section simply drops out of
            # PERF.md on hosts that cannot hold it.
            print(f"# {mode} envelope run failed (rc={out.returncode}); "
                  f"retrying without envelope", file=sys.stderr)
            return run_mode(mode, envelope=False)
        raise RuntimeError(f"{mode} perf run failed:\n{out.stderr[-2000:]}")
    return [json.loads(line) for line in out.stdout.splitlines()
            if line.strip().startswith("{")]


def main() -> int:
    envelope = os.environ.get("PERF_ENVELOPE", "1") == "1"
    rows = {}
    for mode in ("in-process", "daemons"):
        rows[mode] = {r["name"]: r
                      for r in run_mode(
                          mode, envelope=(envelope
                                          and mode == "in-process"))}

    def is_envelope(name: str) -> bool:
        if name in ("actors_5000_create_and_call",
                    "spread_256_tasks_64_nodes"):
            return True
        # the queued-drain ladder emits one row per rung that held
        # (queued_100000/300000/1000000_task_drain), so match by size
        m = re.match(r"queued_(\d+)_task_drain$", name)
        return bool(m) and int(m.group(1)) >= 100_000

    env_names = [n for n in rows["in-process"] if is_envelope(n)]
    names = [n for n in rows["in-process"] if n not in env_names]
    print("### Control plane: core-op envelope (host rates, CPU)")
    print()
    print(f"Recorded {time.strftime('%Y-%m-%d')} on "
          f"{os.cpu_count()} CPUs ({platform.machine()}), "
          f"Python {platform.python_version()}, CPU jax backend. "
          f"Harness: `ray_tpu/_private/perf.py` "
          f"(reference: `python/ray/_private/ray_perf.py:95`, envelope "
          f"targets `release/benchmarks/README.md:25-31`). Regenerate "
          f"with `python tools/gen_perf.py`.")
    print()
    print("| benchmark | in-process | daemons (wire protocol) |")
    print("|---|---|---|")
    for name in names:
        a = rows["in-process"].get(name, {})
        b = rows["daemons"].get(name, {})

        def fmt(r):
            if "throughput_per_s" in r:
                return f"{r['throughput_per_s']:,.0f}/s"
            if "drain_per_s" in r:
                return (f"submit {r['submit_per_s']:,.0f}/s, "
                        f"drain {r['drain_per_s']:,.0f}/s "
                        f"({r['total_seconds']}s total)")
            return "—"
        print(f"| {name} | {fmt(a)} | {fmt(b)} |")
    env_rows = [rows["in-process"][n] for n in env_names
                if n in rows["in-process"]]
    if env_rows:
        print()
        print("## Scale envelope (single-host slices of "
              "`release/benchmarks/README.md:5-31`)")
        print()
        print("| envelope probe | result |")
        print("|---|---|")
        for r in env_rows:
            if "drain_per_s" in r:
                print(f"| {r['name']} | submit {r['submit_per_s']:,.0f}/s, "
                      f"drain {r['drain_per_s']:,.0f}/s "
                      f"({r['total_seconds']}s total) |")
            elif r["name"] == "spread_256_tasks_64_nodes":
                print(f"| {r['name']} | {r['count']} distinct nodes hit, "
                      f"{r['throughput_per_s']:,.0f} tasks/s |")
            else:
                print(f"| {r['name']} | {r['throughput_per_s']:,.0f}/s "
                      f"({r['seconds']}s for {r['count']}) |")
    print()
    print("Notes: daemons mode pays the full serialization + RPC + "
          "process boundary on every op — the honest cost of the "
          "reference's default topology. The queued-drain rows probe "
          "the single-node scheduler backlog (reference envelope: "
          "1M+ queued; this record uses 10k and 30k per run to stay "
          "CI-sized; the 3x row shows the drain rate HOLDS as the "
          "backlog grows — no superlinear degradation). "
          "`burst_submit_batched` bursts two-return tasks — off the "
          "native fast lane — so the daemons column measures the "
          "batched push_task_batch wire path end to end "
          "(docs/performance.md). The envelope section appears only "
          "on hosts whose thread/PID limits can hold the 5000-actor "
          "slice; the queued-drain LADDER (100k -> 300k -> 1M) "
          "commits every rung the box held and stops at the first "
          "rung it could not — the largest committed rung is this "
          "box's backlog envelope, degrading gracefully on small "
          "hosts. Numbers are only comparable within one "
          "host generation: see tools/evidence/batching_ab_r6.md "
          "(control-plane submit 4.4-6.5x) and "
          "tools/evidence/drain_ab_r10.md (drain-side result "
          "pipeline: queued-drain rows within 2x of submit — ratio "
          "~0.5 — with no round-trip/submit regression) for the "
          "same-box A/Bs that isolate code changes from hardware "
          "changes.")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
