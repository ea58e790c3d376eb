"""Each cell's ``--tiny-cpu`` run end to end, as the driver would start
it: a fresh process, the one JSON line validated against the contract's
keys; and the refusal without a chip. The four-chip cell runs on four
virtual CPU devices. No TPU topology is described anywhere here."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import run as harness

BENCH = harness.load_json(harness.ROOT, "BENCHMARK.json")
CELLS = [w["name"] for w in BENCH["workloads"]]
DEVICE_UNITS = {"ms", "s", "%", "tokens/s", "tokens/s/chip", "GiB"}


def start(args, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "BENCH_RUN"}
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "-m", "benchmark.run", *args], cwd=harness.ROOT,
        env=env, capture_output=True, text=True, timeout=600)


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_cpu_run_prints_the_contract_line_and_no_device_number(
        cell, trace):
    if trace and "train_1chip" not in cell and "chat" not in cell:
        pytest.skip("one serve and one train cell rehearse the traced path")
    p = start(["--workload", cell, "--seed", "3000000123", "--seconds", "3",
               "--trace", str(trace), "--tiny-cpu"])
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    assert {"kind", "count", "memory_peak_bytes"} <= set(line["device"])
    assert "busy_s" not in line["device"] and "breakdown" not in line
    for name, m in line["metrics"].items():
        assert m["unit"] not in DEVICE_UNITS, (name, m)
    if "decode" in cell:
        c = line["counts"]
        assert c["engine_after"]["prefills"] == c["engine_before"]["prefills"]
        assert c["compiles_in_window"] == 0


def test_no_chip_no_result():
    p = start(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
               "--trace", "0"], {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert p.stdout.strip() == ""


STALLED_SHUTDOWN = """
import sys, time
import ray_tpu
real = ray_tpu.shutdown
def slow_shutdown(*a, **k):
    real(*a, **k)
    time.sleep(1.0)      # a stalled host: the streams' errors all land
ray_tpu.shutdown = slow_shutdown
from benchmark import run
sys.exit(run.main(sys.argv[1:]))
"""


def test_closed_loop_clients_are_judged_before_the_replica_goes_down():
    """PR 23's first check was refused for this: the closed loop ends by
    shutting the replica down under 32 live streams, each of which then
    ends in an error; read after the shutdown, on a host slow enough for
    the client threads to run first, they made every client 'failed'."""
    cell = next(c for c in CELLS if c.endswith("batch_decode"))
    p = subprocess.run(
        [sys.executable, "-c", STALLED_SHUTDOWN, "--workload", cell,
         "--seed", "2147483999", "--seconds", "3", "--trace", "0",
         "--tiny-cpu"], cwd=harness.ROOT, capture_output=True, text=True,
        timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0, line
