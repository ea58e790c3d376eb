"""The readers of the Serve stream path's account on a synthetic record:
the arithmetic, and ``None`` wherever there is nothing to read (a
program without the counters, as the parent of the PR that added them;
a train cell's record; a window in which no item moved)."""

import pytest

from benchmark import run as harness

BENCH = harness.load_json(harness.ROOT, "BENCHMARK.json")
BEFORE = {"decode_steps": 100, "tokens_generated": 3000, "admitted": 10,
          "queue_wait_s": 0.5, "t_readback_s": 8.0, "t_deliver_s": 0.1,
          "stream_puts": 1000, "stream_takes": 1000,
          "stream_items_reported": 1000, "stream_items_consumed": 900,
          "streams_live": 32, "stream_producer_cpu_s": 1.0,
          "stream_consumer_cpu_s": 0.5, "engine_thread_cpu_s": 2.0,
          "stream_produce_s": 0.010, "stream_items_timed_produce": 100,
          "stream_consume_s": 0.002, "stream_items_timed_consume": 90}
AFTER = {"decode_steps": 500, "tokens_generated": 15000, "admitted": 130,
         "queue_wait_s": 6.5, "t_readback_s": 12.0, "t_deliver_s": 0.3,
         "stream_puts": 13000, "stream_takes": 12000,
         "stream_items_reported": 12000, "stream_items_consumed": 9900,
         "streams_live": 32, "stream_producer_cpu_s": 3.2,
         "stream_consumer_cpu_s": 1.4, "engine_thread_cpu_s": 4.9,
         "stream_produce_s": 0.160, "stream_items_timed_produce": 850,
         "stream_consume_s": 0.062, "stream_items_timed_consume": 690}
# over 10 s and 400 steps: 12,000 put, 11,000 taken and reported, 9,000
# consumed; producers 2.2 s, consumers 0.9 s, the engine thread 2.9 s of
# CPU; 750 and 600 timed items of 0.15 and 0.06 s; read-back 4 s,
# deliver 0.2 s; 120 admitted after 6 s of waiting
WANT = {"serve.stream_consumed_share": 75.0,
        "serve.stream_replica_backlog_per_s": 100.0,
        "serve.stream_handle_backlog_per_s": 200.0,
        "serve.stream_producer_cpu_us_per_item": 200.0,
        "serve.stream_consumer_cpu_us_per_item": 100.0,
        "serve.stream_produce_us_per_item": 200.0,
        "serve.stream_consume_us_per_item": 100.0,
        "serve.python_cpu_share": 60.0,
        "engine.readback_wait_ms_per_step": 10.0,
        "engine.deliver_ms_per_step": 0.5,
        "engine.queue_wait_ms": 50.0}
NEW = [m for m in BENCH["per_layer"]
       if m["name"].rsplit(".", 1)[0] in WANT]
OLD_KEYS = ("decode_steps", "tokens_generated")


def record(before=BEFORE, after=AFTER):
    return {"engine_before": before, "engine_after": after,
            "t_open": 100.0, "t_close": 110.0, "slots": 32}


def test_the_stream_metrics_are_declared_for_their_cells():
    """Eight ``serve.*`` readers for every closed-loop cell (``.decode``
    or ``.stream`` by the end-to-end metric the cell reports), the two
    engine phases for the open-loop cell too, the queue wait for it
    alone: each reader's cells are its end-to-end metric's."""
    assert len(NEW) == 8 * 2 + 2 * 3 + 1
    moved = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in NEW:
        base, suffix = m["name"].rsplit(".", 1)
        assert m["source"] == "program_counter"
        assert m["layer"] == ("Serve ingress, router, replica"
                              if base.startswith("serve.")
                              else "Engine scheduler")
        moves = {"decode": "serve_out_tokens_per_s",
                 "stream": "serve_out_tokens_per_s.stream",
                 "chat": "tpot_p50_ms"}[suffix]
        assert m["moves"] == moves
        assert sorted(m["workloads"]) == sorted(moved[moves]["workloads"])
        mod = harness.load_metric(m["name"])
        assert (mod.LAYER, mod.UNIT, mod.BETTER, mod.SOURCE, mod.MOVES) == (
            m["layer"], m["unit"], m["better"], m["source"], m["moves"])


@pytest.mark.parametrize("name", [m["name"] for m in NEW])
def test_metric_reads_its_value_and_none_where_there_is_nothing(name):
    read = harness.load_metric(name).read
    assert read(record()) == pytest.approx(WANT[name.rsplit(".", 1)[0]])
    # a program without the counters: its stats hold the old keys only
    old = record({k: BEFORE[k] for k in OLD_KEYS},
                 {k: AFTER[k] for k in OLD_KEYS})
    assert read(old) is None
    assert read({}) is None                     # a train cell's record
    # nothing moved in the window: no item, no step, no admission
    assert read(record(BEFORE, BEFORE)) is None
