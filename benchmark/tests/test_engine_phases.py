"""The readers of the engine's phase counters on a synthetic record:
the arithmetic, and ``None`` wherever there is nothing to read (a
program without the counters, as the parent of the PR that added them;
a window in which no decode step ran)."""

import pytest

from benchmark import run as harness

BENCH = harness.load_json(harness.ROOT, "BENCHMARK.json")
BEFORE = {"decode_steps": 100, "tokens_generated": 3000, "preemptions": 0,
          "t_step_s": 10.0, "t_schedule_s": 0.10, "t_host_arrays_s": 0.20,
          "t_enqueue_s": 0.30, "t_readback_s": 8.0, "t_emit_s": 0.40,
          "t_prefill_s": 1.0, "t_idle_s": 5.0, "cpu_host_s": 0.9,
          "prefill_tokens": 1000, "prefill_padded_tokens": 2000,
          "admitted": 10, "queue_wait_s": 0.5}
AFTER = {"decode_steps": 500, "tokens_generated": 15000, "preemptions": 0,
         "t_step_s": 56.0, "t_schedule_s": 0.50, "t_host_arrays_s": 1.00,
         "t_enqueue_s": 1.50, "t_readback_s": 44.0, "t_emit_s": 2.00,
         "t_prefill_s": 5.0, "t_idle_s": 5.0, "cpu_host_s": 3.9,
         "prefill_tokens": 31000, "prefill_padded_tokens": 42000,
         "admitted": 130, "queue_wait_s": 6.5}
# over 400 steps: step 46 s, schedule 0.4, host arrays 0.8, enqueue 1.2,
# emit 1.6 (host 4.0 s, of it 3.0 s on the CPU), prefill 4.0;
# 30,000 real of 40,000 computed prefill positions
WANT = {"engine.step_ms": 115.0, "engine.host_ms_per_step": 10.0,
        "engine.schedule_ms_per_step": 1.0,
        "engine.host_arrays_ms_per_step": 2.0,
        "engine.enqueue_ms_per_step": 3.0, "engine.emit_ms_per_step": 4.0,
        "engine.host_cpu_share": 75.0,
        "engine.prefill_stall_ms_per_step": 10.0,
        "engine.prefill_padding_share": 25.0}
NEW = [m for m in BENCH["per_layer"]
       if m["name"].rsplit(".", 1)[0] in WANT]
OLD_KEYS = ("decode_steps", "tokens_generated", "preemptions")


def test_the_phase_metrics_are_declared_for_their_cells():
    """A ``.decode`` or ``.stream`` reader is declared for every
    closed-loop cell and a ``.chat`` reader for every open-loop one, by
    the KIND of the cell's traffic (its driver), whatever the traffic or
    the configuration is called: the suffix says which end-to-end metric
    the reader moves, and a reader's cells are that metric's cells of
    that kind. The two closed-loop metrics share no cell and leave none
    out."""
    assert len(NEW) == 16 + 7            # the seven ``.decode`` twins
    kind_of = {w["name"]: harness.load_json(
        harness.HERE, "traffic", w["traffic"] + ".json")["kind"]
        for w in BENCH["workloads"]}
    moved = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in NEW:
        assert m["layer"] == "Engine scheduler"
        assert m["source"] == "program_counter"
        kind, moves = {
            "decode": ("serve_closed", "serve_out_tokens_per_s"),
            "stream": ("serve_closed", "serve_out_tokens_per_s.stream"),
            "chat": ("serve_open", "tpot_p50_ms")}[
            m["name"].rsplit(".", 1)[1]]
        assert m["moves"] == moves
        # every cell of that kind that reports the metric, and no other
        assert sorted(m["workloads"]) == sorted(
            c for c in moved[moves]["workloads"] if kind_of[c] == kind)
    closed = (moved["serve_out_tokens_per_s"]["workloads"]
              + moved["serve_out_tokens_per_s.stream"]["workloads"])
    assert sorted(closed) == sorted(
        c for c, k in kind_of.items() if k == "serve_closed")


@pytest.mark.parametrize("name", [m["name"] for m in NEW])
def test_metric_reads_its_value_and_none_where_there_is_nothing(name):
    read = harness.load_metric(name).read
    rec = {"engine_before": BEFORE, "engine_after": AFTER, "slots": 32}
    assert read(rec) == pytest.approx(WANT[name.rsplit(".", 1)[0]])
    # a program without the counters: its stats hold the old keys only
    old = {"engine_before": {k: BEFORE[k] for k in OLD_KEYS},
           "engine_after": {k: AFTER[k] for k in OLD_KEYS}}
    assert read(old) is None
    assert read({}) is None                     # a train cell's record
    # no decode step in the window
    stalled = {"engine_before": BEFORE,
               "engine_after": {**AFTER, "decode_steps": 100}}
    assert read(stalled) is None
