"""Which programs a benchmark run traces and lowers, and what each costs
the host: the part of a warm ``setup_s`` that no compile cache saves.

    chiprun -- python3 tools/setup_lowerings.py --workload <cell> --seed <n> \
        [--seconds <s>] [--out chiprun_out/lowerings]

Runs ``python3 -m benchmark.run`` with those arguments IN THIS PROCESS,
from the checkout that is the working directory (so the same tool reads
a parent commit unpacked beside it), with ``jax.monitoring`` listeners
on the three durations jax reports a program: tracing the function to a
jaxpr, the jaxpr to an MLIR module (a Pallas kernel's Mosaic lowering is
in here, BEFORE the compile cache's key exists), and the backend's
compile (on a warm cache: the read). Every event is appended to
``<out>.<seed>.jsonl`` as it ends, stamped on the run's own clock; the
run's result line goes to stdout as ever. Then

    python3 tools/setup_lowerings.py --table <out>.<seed>.jsonl <result line file>

prints, for every program lowered before the window opened, the phase of
``setup_phases`` it fell in and its seconds (PERF.md section 5, PR 53).
"""

from __future__ import annotations

import json
import os
import sys
import time

EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}


def record(path: str, argv: list) -> int:
    sys.path.insert(0, os.getcwd())
    from benchmark import run            # starts the run's clock

    import jax                           # noqa: F401 — before the listener
    from jax import monitoring

    out = open(path, "w", buffering=1)    # the run ends in os._exit

    def listener(event: str, seconds: float, **kw) -> None:
        kind = EVENTS.get(event)
        if kind is not None:
            out.write(json.dumps({
                "at": time.perf_counter() - run.T_START, "kind": kind,
                "fun": kw.get("fun_name"), "seconds": seconds}) + "\n")

    monitoring.register_event_duration_secs_listener(listener)
    return run.main(argv)


def table(events_path: str, line_path: str) -> None:
    with open(events_path) as f:
        events = [json.loads(line) for line in f if line.strip()]
    with open(line_path) as f:
        line = json.loads(f.read().strip().splitlines()[-1])
    ends, t = [], 0.0
    for name, seconds in line["setup_phases"]:
        t += seconds
        ends.append((name, t))
    t_open = ends[-1][1]

    def phase_of(at: float) -> str:
        return next((name for name, end in ends if at <= end), "window")

    # an inner jit's trace is an event of its own (``gmm``): keep it as a
    # row, and count an outer program by its lowering
    rows: dict = {}
    for e in events:
        if e["at"] > t_open:
            continue
        # jax names a lowering ``jit(f)`` and the same program's trace ``f``
        fun = str(e["fun"])
        if fun.startswith("jit(") and fun.endswith(")"):
            fun = fun[4:-1]
        key = (phase_of(e["at"]), fun)
        row = rows.setdefault(key, {"trace": 0.0, "lower": 0.0,
                                    "compile": 0.0, "n_trace": 0,
                                    "n_lower": 0})
        row[e["kind"]] += e["seconds"]
        if e["kind"] != "compile":
            row["n_" + e["kind"]] += 1
    print(f"{'phase':28} {'program':44} {'traces':>6} {'trace_s':>8} "
          f"{'lowers':>6} {'lower_s':>8} {'compile_s':>9}")
    for (phase, fun), r in sorted(rows.items(),
                                  key=lambda kv: -kv[1]["lower"]):
        if r["lower"] + r["trace"] + r["compile"] < 0.02:
            continue
        print(f"{phase:28} {fun[:44]:44} {r['n_trace']:6d} "
              f"{r['trace']:8.3f} {r['n_lower']:6d} {r['lower']:8.3f} "
              f"{r['compile']:9.3f}")
    total = {k: sum(r[k] for r in rows.values())
             for k in ("trace", "lower", "compile")}
    # an inner jit's trace lies inside its outer program's: the traces'
    # sum counts it twice, the lowerings' and compiles' sums do not
    print(f"programs lowered before the window: "
          f"{sum(r['n_lower'] for r in rows.values())}; lower "
          f"{total['lower']:.3f} s, compile or cache read "
          f"{total['compile']:.3f} s, traces (inner ones twice) "
          f"{total['trace']:.3f} s; setup_s "
          f"{line['metrics'].get('setup_s', {}).get('value')}")


def main(argv: list) -> int:
    if argv and argv[0] == "--table":
        table(argv[1], argv[2])
        return 0
    out = "chiprun_out/lowerings"
    if "--out" in argv:
        i = argv.index("--out")
        out = argv[i + 1]
        argv = argv[:i] + argv[i + 2:]
    seed = argv[argv.index("--seed") + 1] if "--seed" in argv else "0"
    os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
    return record(f"{out}.{seed}.jsonl", argv)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
