"""The closed-loop cells' own arithmetic: a request's end is seen from
the client's finish chunk down to the result line, and a window that
meets its cap closes before one (the rule itself:
``test_window_close.py``); ``decode_program_roofline`` counts the K/V of
the traced span from the engine's two snapshots; the cells' sizing holds
what their traffic files say."""

import importlib
import json
import re
import subprocess
import sys

import pytest

from benchmark import run as harness
from benchmark.drivers import serve_closed
from benchmark.lib import serving
from benchmark.lib.peaks import peaks_for
from benchmark.lib.records import RequestRecord

BENCH = harness.load_json(harness.ROOT, "BENCHMARK.json")
CLOSED = [w for w in BENCH["workloads"]
          if harness.load_json(harness.HERE, "traffic", w["traffic"] + ".json")
          ["kind"] == "serve_closed"]
roofline = harness.load_metric("decode_program_roofline")


class FakeStream:
    def __init__(self, chunks):
        self.chunks = iter(chunks)

    def next(self, timeout=None):
        return next(self.chunks)


class FakeHandle:
    """``handle.options(stream=True).remote(body)`` -> a stream."""

    def __init__(self, chunks):
        self.chunks = chunks

    def options(self, **kw):
        return self

    def remote(self, body):
        return FakeStream(self.chunks)


def test_a_finish_chunk_in_the_window_is_a_request_ended_in_it():
    rec = RequestRecord(index=0, due_at=0.0)
    toks = serving.stream_request(
        FakeHandle([{"token_id": 5}, {"token_id": 6},
                    {"done": True, "finish_reason": "length",
                     "ttft_s": 0.01}]), [1, 2, 3], 2, rec)
    assert toks == [5, 6] and rec.finish_reason == "length"
    assert rec.done_at is not None and rec.done_at >= rec.finished_at
    # a stream that is cut (the replica went down under it) has no
    # finish chunk: an error, not an end
    cut = RequestRecord(index=1, due_at=0.0)
    serving.stream_request(FakeHandle([{"token_id": 5}]), [1], 2, cut)
    assert cut.done_at is None and cut.error

    lo, hi = rec.done_at - 1.0, rec.done_at + 1.0
    assert serve_closed.requests_ended([rec, cut], lo, hi) == 1
    assert serve_closed.requests_ended([rec, cut], hi, hi + 1.0) == 0
    assert serve_closed.requests_ended([rec, cut], lo - 1.0, lo) == 0


def test_a_stall_across_the_close_is_not_a_dead_stream():
    """Three clients at the close (t = 100, limit 1 s): one streaming,
    one that stood still for 3 s and resumes 2 s later, one that never
    does. Only the last is dead, and only the deadline ends the wait."""
    stamps = [[90.0, 99.99], [90.0, 97.0], [90.0, 96.0]]
    last = [s[-1] for s in stamps]
    silent = serve_closed.silent_at(last, 100.0, 1.0)
    assert silent == [1, 2]
    assert serve_closed.silent_at([None, 99.5], 100.0, 1.0) == [0]
    now = [100.0]

    def sleep(dt):
        now[0] += dt
        if now[0] >= 102.0 and stamps[1][-1] == 97.0:
            stamps[1].append(now[0])

    resumed = serve_closed.resumed_by(stamps, last, silent, 120.0,
                                      clock=lambda: now[0], sleep=sleep)
    assert resumed == [1]
    assert 120.0 <= now[0] < 120.1          # waited for the dead one
    # nobody silent: no wait at all
    assert serve_closed.resumed_by(stamps, last, [], 120.0,
                                   clock=lambda: 1 / 0, sleep=None) == []


SHORT_REQUESTS = """
import sys
from benchmark import run
from benchmark.lib import serving
real = run.load_json
def short(*parts):
    out = real(*parts)
    if parts[-1] == "batch_decode.json":
        out = dict(out, max_tokens={max_tokens})
    return out
run.load_json = short
if {stale}:       # an engine whose count of its decode steps stands still
    stats, first = serving.engine_stats, {{}}
    def stale(handle):
        out = stats(handle)
        out["decode_steps"] = first.setdefault("steps", out["decode_steps"])
        return out
    serving.engine_stats = stale
sys.exit(run.main(sys.argv[1:]))
"""


def run_short(max_tokens: int, seconds: int, stale: bool = False):
    """``batch_decode`` on the CPU at debug widths with ``max_tokens`` a
    request in place of 7900: ``(result line, stderr)``."""
    cell = next(w["name"] for w in CLOSED if w["traffic"] == "batch_decode")
    p = subprocess.run(
        [sys.executable, "-c",
         SHORT_REQUESTS.format(max_tokens=max_tokens, stale=stale),
         "--workload", cell, "--seed", "2147484001", "--seconds",
         str(seconds), "--trace", "0", "--tiny-cpu"], cwd=harness.ROOT,
        capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    assert p.stderr.strip().splitlines()[-1].count("checks:") == 1
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stderr


def test_a_close_that_comes_too_late_reaches_the_line_and_stderr():
    """The close rests on the engine's own count of its steps. One that
    under-reports (here: stands still) never meets the cap: 128-token
    requests, ~90 steps from their end at the open, end inside the 60 s
    asked for; the clients resubmit, and the run says so on the line and
    on stderr: the report that guards the close itself."""
    line, err = run_short(128, 60, stale=True)
    counts = line["counts"]
    assert counts["requests_ended_in_window"] > 0
    assert counts["first_tokens_in_window"] > 0
    assert counts["closed_early"] is False
    assert counts["decode_steps_per_s"] is None      # a CPU names no rate
    assert "window closed at its 60 s" in err
    assert "the close came too late" in err


def test_a_window_that_meets_its_cap_closes_before_a_request_ends():
    """``batch_decode`` with 128 tokens a request in place of 7900 and 40 s
    asked for: the engine's first request (a head start of ~40 tokens) is
    ~90 steps from its end when the window opens, so the window closes
    after the ~25 steps that leave the margin of 64, seconds in, and
    says so; no request has ended in it, none was sent again, and
    ``correct`` keeps its meaning (every stream still flows)."""
    line, err = run_short(128, 40)
    counts = line["counts"]
    assert line["correct"] and line["failed"] == 0
    assert counts["requests_ended_in_window"] == 0
    assert counts["first_tokens_in_window"] == 0
    assert counts["closed_early"] is True
    assert 0 < counts["window_s"] < 39
    assert counts["cap_steps"] < 128 and counts["margin_steps"] == 64
    steps = (counts["engine_after"]["decode_steps"]
             - counts["engine_before"]["decode_steps"])
    assert 0 < steps < counts["cap_steps"]
    assert counts["decode_steps_per_s"] is None      # a CPU names no rate
    assert counts["cap_steps_per_s"] is None
    assert "window closed EARLY" in err


def snapshots(steps, blocks):
    return [{"decode_steps": 1000, "decode_kv_blocks_live": 50_000},
            {"decode_steps": 1000 + steps,
             "decode_kv_blocks_live": 50_000 + blocks}]


# K/V bytes a decode step reads at 25,600 and at 51,200 live rows over
# the slots, by hand from each configuration's published shapes: rows x
# layers x (K and V) x KV heads x 128 x 2 bytes, by the layer's kind
KV_BYTES_BY_HAND = {
    # 6 layers of 8 KV heads: 4 KiB a row a layer
    "mistral-7b-v0.3-d6": (25_600 * 6 * 4096, 51_200 * 6 * 4096),
    # 3 layers of 16 KV heads: 8 KiB a row a layer
    "olmoe-1b-7b-d3": (25_600 * 3 * 8192, 51_200 * 3 * 8192),
    # 4 KV heads: 2 KiB a row a layer; the 2 full layers read every row,
    # the 6 sliding ones at most a window and a block a slot:
    # 32 x (1024 + 32) = 33,792 rows, which 25,600 rows stay under
    "mellum2-12b-a2.5b-d8": (25_600 * (2 + 6) * 2048,
                             (51_200 * 2 + 33_792 * 6) * 2048),
    # 8 layers of 32 KV heads: 16 KiB a row, exact or summary alike
    "evabyte-6.5b-d8": (25_600 * 8 * 16_384, 51_200 * 8 * 16_384),
}


@pytest.mark.parametrize("cell", [w["name"] for w in CLOSED])
def test_roofline_counts_the_kv_of_the_traced_span(cell):
    """200 steps whose attention read 160,000 blocks of 32 rows: 25,600
    live rows a step over the slots, whatever the clients had at
    mid-window; the share follows from the bytes the cell's OWN cost
    model (the configuration's ``costs``, as the harness loads it)
    counts for that many rows, and its K/V term is held to the bytes
    worked out by hand above."""
    w = next(w for w in BENCH["workloads"] if w["name"] == cell)
    conf = next(c for c in BENCH["configs"] if c["name"] == w["config"])
    cfg = harness.load_json(harness.ROOT, conf["file"])
    traffic = harness.load_json(harness.HERE, "traffic",
                                w["traffic"] + ".json")
    costs = importlib.import_module("benchmark.costs." + cfg["costs"])
    rec = {"engine_trace_edges": snapshots(200, 160_000), "config": cfg,
           "traffic": traffic, "costs": costs,
           "peaks": peaks_for("TPU v5 lite"),
           "trace": {"programs": {"decode_step_paged": {
               "calls": 200, "seconds": 200 * 0.010}}}}
    assert roofline.live_tokens_per_step(rec) == 25_600
    bytes_ = costs.decode_step_bytes(cfg, 25_600)
    weights = costs.decode_step_bytes(cfg, 0)
    by_hand = KV_BYTES_BY_HAND[w["config"]]
    assert bytes_ - weights == by_hand[0]
    assert costs.decode_step_bytes(cfg, 51_200) - weights == by_hand[1]
    assert 2e9 < weights < 8e9              # bf16 matmul weights a step
    assert roofline.read(rec) == pytest.approx(
        100 * bytes_ / 819e9 / 0.010)
    # twice the live K/V at the same program time reads higher: the
    # count follows the snapshots, nothing else in the record
    more = dict(rec, engine_trace_edges=snapshots(200, 320_000))
    assert roofline.read(more) > roofline.read(rec)
    # nothing to read: an untraced run, a driver without snapshots, a
    # program without the counter, a span with no step
    assert roofline.read(dict(rec, trace=None)) is None
    assert roofline.read(dict(rec, engine_trace_edges=[])) is None
    assert roofline.read(dict(rec, engine_trace_edges=[
        {"decode_steps": 1}, {"decode_steps": 2}])) is None
    assert roofline.read(dict(
        rec, engine_trace_edges=snapshots(0, 0))) is None


@pytest.mark.parametrize("cell", [w["name"] for w in CLOSED])
def test_a_request_fits_its_slot_and_the_models_positions(cell):
    w = next(w for w in BENCH["workloads"] if w["name"] == cell)
    conf = next(c for c in BENCH["configs"] if c["name"] == w["config"])
    cfg = harness.load_json(harness.ROOT, conf["file"])
    tr = harness.load_json(harness.HERE, "traffic", w["traffic"] + ".json")
    eng = tr["engine"]
    assert tr["clients"] == eng["max_slots"]
    assert tr["prompt_len"]["value"] + tr["max_tokens"] <= eng["max_seq"]
    assert eng["max_seq"] % eng["block_size"] == 0
    published = cfg.get("published", {}).get(
        "max_position_embeddings", cfg["max_position_embeddings"])
    assert eng["max_seq"] <= cfg["max_position_embeddings"] <= published
    # the rate the cell's ``why`` quotes, past which its window is
    # shorter than ``run_seconds``: one token a slot a step, less a head
    # start (8 to ~700 tokens) and the close's margin (64 to ~350 steps)
    quoted = int(re.search(r"past ~?(\d+) steps/s", w["why"]).group(1))
    assert (tr["max_tokens"] - 1100) / BENCH["run_seconds"] <= quoted \
        <= (tr["max_tokens"] - 64) / BENCH["run_seconds"]


def test_the_deployment_is_pickled_as_a_name_not_with_the_live_server():
    """Serve's controller checkpoints each deployment with cloudpickle.
    Until PR 30 the class was made inside a function, so it was pickled
    by value with the ``SERVERS`` global its ``__init__`` names: every
    serve run copied weights and KV pool to the host and pickled them."""
    import cloudpickle

    from ray_tpu import serve

    from benchmark.lib import bench_server

    bench_server.SERVERS.append(b"w" * 20_000_000)    # a live server's bulk
    try:
        dep = serve.deployment(bench_server.BenchLLMServer, name="m",
                               num_replicas=1, max_ongoing_requests=64)
        assert len(cloudpickle.dumps((dep, (), {}, 1))) < 100_000
    finally:
        bench_server.SERVERS.pop()


@pytest.mark.parametrize("cell", [w["name"] for w in CLOSED])
def test_the_cells_decode_program_fits_a_described_v5e(cell):
    """The chip-less AOT compile of the decode program at the traffic
    file's engine shape, published widths, the weights as an engine
    holds them (bf16) and the K/V as the engine lays it out for the
    model's kind (one pool; a pool a kind; a pool a part): the v5e's
    compiler places it with a GiB to spare, paged kernel inside. A
    process of its own: the tool tells the model it is on the chip."""
    import os

    p = subprocess.run(
        [sys.executable, "-m", "benchmark.aot_fit", cell], cwd=harness.ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=900)
    if "topology" in p.stderr and p.returncode != 0 and not p.stdout.strip():
        pytest.skip("no v5e topology can be described here: "
                    + p.stderr[-300:])
    assert p.returncode == 0, p.stderr[-3000:]
    mem = json.loads(p.stdout.strip().splitlines()[-1])
    assert mem["attention"] == "pallas" and mem["mosaic_calls"] >= 1
    assert mem["spare_gib"] >= 1.0, mem


def test_a_stall_of_every_stream_is_reported_with_the_stacks(
        monkeypatch, capsys):
    import threading
    import time

    class FakeRun:
        t_open = time.perf_counter()

        def log(self, msg):
            print(msg, file=sys.stderr)

    monkeypatch.setattr(serve_closed, "STALL_DUMP_S", 0.05)
    stop = threading.Event()
    parked = threading.Thread(target=stop.wait, name="some-engine-thread",
                              daemon=True)
    parked.start()
    serve_closed.watch_for_stalls(FakeRun(), [[1.0, 2.0], [1.5]], stop,
                                  limit=1)
    time.sleep(0.6)
    stop.set()
    err = capsys.readouterr().err
    assert err.count("STALL: no token") == 1
    assert "-- thread some-engine-thread" in err
    assert "-- thread bench-" not in err
