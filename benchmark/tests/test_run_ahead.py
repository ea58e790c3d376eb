"""PR 60's two readers of the engine's run-ahead counters on a synthetic
record (``engine.run_ahead_share.chat``, ``engine.dropped_row_share.chat``):
the arithmetic, their entries in ``BENCHMARK.json``, and ``None`` where
there is nothing to read: a parent's record, whose engine has neither
counter, a train cell's, a window in which nothing ran."""

import pytest

from benchmark import run as harness

BENCH = harness.load_json(harness.ROOT, "BENCHMARK.json")
# a parent's ``stats``: steps and tokens, no run-ahead counters
BEFORE = {"decode_steps": 100, "tokens_generated": 3000}
AFTER = {"decode_steps": 500, "tokens_generated": 15000}
# over 400 steps: 360 dispatched with the step before unread; 12,000
# tokens booked beside 60 rows dropped
AHEAD = ({"decode_steps_ahead": 40, "decode_rows_dropped": 5},
         {"decode_steps_ahead": 400, "decode_rows_dropped": 65})


@pytest.mark.parametrize("name,want", [
    ("engine.run_ahead_share.chat", 100.0 * 360 / 400),
    ("engine.dropped_row_share.chat", 100.0 * 60 / (12000 + 60))])
def test_the_run_ahead_readers_and_a_parent_without_the_counters(name, want):
    declared = next(m for m in BENCH["per_layer"] if m["name"] == name)
    mod = harness.load_metric(name)
    assert declared == {
        "name": name, "unit": mod.UNIT, "better": mod.BETTER,
        "source": mod.SOURCE, "layer": mod.LAYER, "moves": mod.MOVES,
        "workloads": ["mistral-7b-v0.3-d6.chat_mixed"]}
    assert (mod.SOURCE, mod.LAYER, mod.MOVES) == (
        "program_counter", "Engine scheduler", "tpot_p50_ms")
    rec = {"engine_before": {**BEFORE, **AHEAD[0]},
           "engine_after": {**AFTER, **AHEAD[1]}}
    assert mod.read(rec) == pytest.approx(want)
    # the parent's engine has neither counter: nothing, and no raise
    assert mod.read({"engine_before": BEFORE, "engine_after": AFTER}) is None
    assert mod.read({}) is None                 # a train cell's record
    # a window in which no step ran and no token came
    still = {"engine_before": rec["engine_before"],
             "engine_after": rec["engine_before"]}
    assert mod.read(still) is None


def test_the_new_entries_stand_last_and_change_no_other():
    assert [m["name"] for m in BENCH["per_layer"][-2:]] == [
        "engine.run_ahead_share.chat", "engine.dropped_row_share.chat"]
    assert len(BENCH["per_layer"]) == 104 and len(BENCH["workloads"]) == 13
