"""The benchmark's one command.

    python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, which owns the chip(s). It reads the cell from
``BENCHMARK.json``, its configuration from ``benchmark/configs``, its
traffic from ``benchmark/traffic``; the traffic's ``kind`` picks the
driver in ``benchmark/drivers``; every metric the cell reports is read
from the run's record by ``benchmark/metrics/<name>.py``. Set-up (load,
init from the seed, warm-up, correctness check) ends when the window
opens and is reported as ``setup_s``; then it measures for ``--seconds``
(a closed-loop cell: or until just before its first request would end,
``drivers/serve_closed.py``) and prints one JSON object as the last
line of stdout.

No TPU, or fewer chips than the cell asks for: exit 3, nothing on
stdout. A cell whose traffic no longer fits the system (a closed-loop
window that its first request's end would cut to a few seconds): exit 5,
the reason on stderr, nothing on stdout. ``--tiny-cpu`` is the explicit
request for the CPU at debug widths (four virtual devices); it prints
counts only, never a time, a rate or a share.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse
import faulthandler
import importlib
import importlib.util
import json
import os
import sys
import threading

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmark")
EXIT_NO_CHIP = 3
EXIT_MIS_SIZED = 5
DEADLINE_S = 1150            # stacks are dumped and the run ends


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_metric(name: str):
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(bench: dict, workload: str, group: str) -> list:
    return [m for m in bench[group]
            if "workloads" not in m or workload in m["workloads"]]


class Run:
    """What a driver is handed, and the clock of the run's phases."""

    def __init__(self, args, bench):
        cell = next((w for w in bench["workloads"]
                     if w["name"] == args.workload), None)
        if cell is None:
            raise SystemExit(f"unknown workload {args.workload!r}; known: "
                             + ", ".join(w["name"] for w in bench["workloads"]))
        conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
        self.workload = cell["name"]
        self.config_name, self.traffic_name = cell["config"], cell["traffic"]
        self.chips = int(cell["chips"])
        self.config = load_json(ROOT, conf["file"])
        self.traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")
        self.tiny = bool(args.tiny_cpu)
        if self.tiny:
            self.config = {**self.config, **self.config["tiny_cpu"]}
        self.seed = int(args.seed)
        self.jax_seed = self.seed % (2**31 - 1)
        self.seconds = float(args.seconds)
        self.root = ROOT
        self.builder = importlib.import_module(
            "benchmark.builders." + self.config["builder"])
        self.costs = importlib.import_module(
            "benchmark.costs." + self.config["costs"])
        self.tracer = None
        self.compiles = None
        self.phases: list = []
        self._last = T_START
        self.t_backend_up = None
        self.t_open = None
        self._trace_thread = None
        self.trace_edges: list = []

    def log(self, msg: str) -> None:
        print(f"[bench {time.perf_counter() - T_START:7.1f}s] {msg}",
              file=sys.stderr, flush=True)

    def phase(self, name: str) -> None:
        now = time.perf_counter()
        self.phases.append([name, now - self._last])
        self._last = now
        self.log(f"{name}: {self.phases[-1][1]:.2f} s")

    def backend_up(self) -> None:
        """``setup_s`` runs from here: what came before is the machine
        starting its runtime, which varies by seconds from run to run
        and holds no work of the program or of the benchmark."""
        self.phase("backend_start")
        self.t_backend_up = self._last

    def open_window(self, at: float | None = None) -> float:
        self.t_open = at if at is not None else time.perf_counter()
        self.phase("to_window_open")
        return self.t_open

    def trace_during(self, t_open: float, seconds: float,
                     snapshot=None) -> None:
        """Trace ``seconds`` of the window from a timer thread (serve
        drivers; the trainer starts and stops its own at step ends).
        ``snapshot()`` is called at both edges INSIDE the traced span,
        after the tracer has started and before it stops, and what it
        returns is kept in ``trace_edges``: a metric that divides work
        by traced time counts the work of the same span."""
        if self.tracer is None:
            return

        def work():
            time.sleep(max(0.0, t_open + min(2.0, self.seconds / 4)
                           - time.perf_counter()))
            self.tracer.start()
            try:
                if snapshot is not None:
                    self.trace_edges.append(snapshot())
                time.sleep(min(seconds, self.seconds / 2))
                if snapshot is not None:
                    self.trace_edges.append(snapshot())
            finally:
                self.tracer.stop()

        self._trace_thread = threading.Thread(target=work, daemon=True,
                                              name="bench-tracer")
        self._trace_thread.start()

    def finish_trace(self) -> None:
        if self._trace_thread is not None:
            self._trace_thread.join(300)


def reap_children(log) -> None:
    """Every process this run started has to be gone before it exits."""
    import signal

    def children():
        out = []
        for pid in filter(str.isdigit, os.listdir("/proc")):
            try:
                with open(f"/proc/{pid}/stat") as f:
                    fields = f.read().rsplit(")", 1)[1].split()
                if int(fields[1]) == os.getpid() and fields[0] != "Z":
                    out.append(int(pid))
            except (OSError, IndexError, ValueError):
                continue
        return out

    # ray_tpu.shutdown() has stopped the workers; what is left are
    # multiprocessing's helpers (forkserver, resource tracker), which live
    # until their parent ends
    deadline = time.perf_counter() + 1
    while children() and time.perf_counter() < deadline:
        time.sleep(0.05)
    for pid in children():
        try:
            with open(f"/proc/{pid}/cmdline") as f:
                what = f.read().replace("\0", " ")[:120]
        except OSError:
            what = "?"
        log(f"killing leftover child {pid}: {what}")
        try:
            os.kill(pid, signal.SIGKILL)
        except OSError:
            pass
    deadline = time.perf_counter() + 5
    try:
        while time.perf_counter() < deadline:
            if os.waitpid(-1, os.WNOHANG) == (0, 0):
                time.sleep(0.05)
    except ChildProcessError:
        pass


def start_backend(run: Run, trace: bool):
    """The chip (or, with ``--tiny-cpu``, four virtual CPU devices), the
    compile cache, the compile counter and the tracer. Returns the
    device as JAX reports it, or ``None`` where the cell's chips are not
    there: no chip, no result."""
    if run.tiny:
        from ray_tpu._private.platform import force_cpu_platform
        force_cpu_platform(4)
    # nothing of the repo is imported before the backend is up on the
    # chip: what the program's imports cost counts as set-up
    import jax
    run.phase("import_jax")
    devices = jax.devices()          # a backend that cannot start raises
    run.backend_up()

    from ray_tpu._private.platform import enable_compile_cache, on_chip

    from benchmark.lib import device as devlib
    from benchmark.lib.trace import Tracer

    info = devlib.device_info()
    if not run.tiny and (not on_chip(devices[0])
                         or len(devices) < run.chips):
        print(f"benchmark: {info['count']} device(s) of platform "
              f"{info['platform']!r}; {run.workload} needs {run.chips} TPU "
              f"chip(s). No chip, no result (--tiny-cpu is the explicit "
              f"CPU run).", file=sys.stderr)
        return None
    cache_dir = enable_compile_cache()
    run.compiles = devlib.CompileCounter()
    if trace:
        run.tracer = Tracer(os.path.join(ROOT, ".bench_out", "trace"))
    run.phase("program_imports_and_cache")
    run.log(f"{run.workload} seed={run.seed} seconds={run.seconds} "
            f"trace={int(trace)} on {info} cache={cache_dir}")
    return info


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python3 -m benchmark.run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--tiny-cpu", action="store_true")
    args = ap.parse_args(argv)
    faulthandler.dump_traceback_later(DEADLINE_S, exit=True)

    bench = load_json(ROOT, "BENCHMARK.json")
    if args.seconds is None:
        args.seconds = bench["run_seconds"]
    run = Run(args, bench)

    info = start_backend(run, bool(args.trace))
    if info is None:
        return EXIT_NO_CHIP
    from benchmark.lib import device as devlib
    from benchmark.lib.peaks import peaks_for

    from benchmark import drivers
    try:
        record = drivers.load(run.traffic["kind"]).run(run)
    except drivers.MisSized as e:
        reap_children(run.log)
        run.log(f"NO RESULT: {e}")
        sys.stderr.flush()
        os._exit(EXIT_MIS_SIZED)     # as below: threads may still block

    record.update(
        tiny=run.tiny, seconds=run.seconds, chips=run.chips,
        config=run.config, traffic=run.traffic, costs=run.costs,
        setup_s=run.t_open - run.t_backend_up,
        peaks=None if run.tiny else peaks_for(info["kind"]),
        trace=run.tracer.reduced if run.tracer else None,
        memory_peak_bytes=devlib.memory_peak_bytes(run.chips))

    group = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in cell_metrics(bench, run.workload, group):
        if run.tiny and m["unit"] != "count":
            continue             # a CPU run measures no time, rate or share
        value = load_metric(m["name"]).read(record)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    info = dict(info, memory_peak_bytes=record["memory_peak_bytes"])
    line = {"correct": bool(record["correct"]),
            "attempted": int(record["attempted"]),
            "failed": int(record["failed"]), "metrics": metrics,
            "device": info}
    tr = record["trace"]
    if tr and not run.tiny:
        info["busy_s"], info["window_s"] = tr["busy_s"], tr["window_s"]
        line["breakdown"] = {"device_ops": tr["device_ops"],
                             "idle_gaps": tr["idle_gaps"]}
    line["checks"] = record["checks"]
    line["setup_phases"] = run.phases
    line["compile_cache"] = run.compiles.snapshot()
    line["counts"] = {k: record[k] for k in (
        "compiles_in_window", "tokens_received_in_window",
        "first_tokens_in_window", "requests_ended_in_window",
        "window_s", "closed_early", "stalled_at_close",
        "decode_steps_per_s", "cap_steps_per_s", "cap_steps",
        "margin_steps", "token_gap_ms",
        "backlog_at_close", "warm",
        "offered_rate_per_s", "client_ms") if k in record}
    for k in ("engine_before", "engine_after", "engine_trace_edges"):
        if k in record:
            line["counts"][k] = record[k]
    reap_children(run.log)
    # each number compared beside its limit, as the last line of stderr
    run.log(f"correct={line['correct']} failed={line['failed']} of "
            f"{line['attempted']} (limit 0); checks: "
            + json.dumps(record["checks"]))
    faulthandler.cancel_dump_traceback_later()
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    # client threads may still block on streams of a replica that is
    # gone; interpreter teardown under them aborts, so leave at once
    sys.stdout.flush()
    os._exit(0)


if __name__ == "__main__":
    sys.exit(main())
