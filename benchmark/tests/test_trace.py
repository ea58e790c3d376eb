"""The trace reduction: on synthetic planes whose answer is known by
hand, and on a small trace recorded on the chip
(``benchmark/tests/data``, a few decode steps of this PR's batch_decode
cell cut to its first events)."""

import json
import os

import pytest

from benchmark.lib import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MS = 1_000_000


def synthetic():
    dev = {
        trace.OPS_LINE: [("%fusion.1 = bf16[8]", 0, 4 * MS),
                         ("%all-reduce.2 = f32[8]", 3 * MS, 3 * MS),
                         ("fusion.1", 10 * MS, 4 * MS),
                         ("all-reduce.2", 14 * MS, 2 * MS)],
        trace.MODULES_LINE: [("jit_step(123)", 0, 6 * MS),
                             ("jit_step(123)", 10 * MS, 6 * MS)],
    }
    host = {"engine": [("bench.wait.stream_read", 0, 20 * MS),
                       ("bench.step_call", 6 * MS, 3 * MS)]}
    return {"/device:TPU:0": dev, "/host:CPU": host}


def test_busy_idle_programs_collectives_and_gaps_by_hand():
    r = trace.reduce_planes(synthetic(), window_ns=(0, 20 * MS))
    assert r["window_s"] == pytest.approx(0.020)
    assert r["busy_s"] == pytest.approx(0.012)          # [0,6] + [10,16]
    assert r["programs"]["step"] == {"calls": 2, "seconds": pytest.approx(0.012)}
    assert r["collective_s"] == pytest.approx(0.005)
    assert r["collective_exposed_s"] == pytest.approx(0.004)   # [4,6]+[14,16]
    ops = dict(r["device_ops"])
    assert ops["fusion.1"] == pytest.approx(0.008)
    gaps = dict(r["idle_gaps"])
    # gap [6,10]: 3 ms under bench.step_call, 1 ms uncovered; gap [16,20]
    # has only a waiting span over it
    assert gaps["bench.step_call"] == pytest.approx(0.003)
    assert gaps["unattributed"] == pytest.approx(0.005)


def test_a_trace_without_device_operations_reduces_to_nothing():
    assert trace.reduce_planes({"/host:CPU": {"t": [("x", 0, 5)]}}) is None


def test_recorded_chip_trace():
    path = os.path.join(DATA, "decode_steps.planes.json")
    if not os.path.exists(path):
        pytest.skip("no recorded trace in benchmark/tests/data")
    with open(path) as f:
        rec = json.load(f)
    r = trace.reduce_planes(rec["planes"])
    want = rec["expected"]
    assert r["n_devices"] == 1
    assert r["busy_s"] == pytest.approx(want["busy_s"], rel=1e-6)
    assert r["window_s"] == pytest.approx(want["window_s"], rel=1e-6)
    assert 0 < r["busy_s"] <= r["window_s"]
    p = r["programs"][want["program"]]
    assert p["calls"] == want["calls"]
    assert p["seconds"] == pytest.approx(want["program_seconds"], rel=1e-6)
    assert r["device_ops"][0][0] == want["top_op"]
