"""The readers of the engine's account of the device
(``benchmark/lib/device_account.py``) on a synthetic record: the
arithmetic over the window and over the traced span, ``None`` wherever
there is nothing to read (a program without the counters, as the parent
of the PR that added them; a record without the traced span's
snapshots, as ``chat_mixed``'s; a span in which no decode step ran), and
the command that prints the same families from a result line."""

import json

import pytest

from benchmark import run as harness
from benchmark.lib import device_account

BENCH = harness.load_json(harness.ROOT, "BENCHMARK.json")
BEFORE = {"decode_steps": 100, "t_step_s": 1.0, "t_lock_wait_s": 0.001,
          "t_device_starved_s": 0.5, "decode_steps_waited": 80,
          "decode_steps_device_paced": 60, "t_device_paced_s": 0.36,
          "t_now_s": 1000.0}
AFTER = {"decode_steps": 500, "t_step_s": 4.2, "t_lock_wait_s": 0.005,
         "t_device_starved_s": 1.5, "decode_steps_waited": 400,
         "decode_steps_device_paced": 360, "t_device_paced_s": 2.16,
         "t_now_s": 1010.5}
# the traced span: 2 s by the snapshots' own clocks, 250 steps of 1.9 s,
# 200 of them paced in 1.1 s, the device starved for 0.05 s
EDGES = [{**BEFORE, "decode_steps": 150, "t_step_s": 1.4,
          "t_device_starved_s": 0.60, "decode_steps_device_paced": 100,
          "t_device_paced_s": 0.60, "t_now_s": 1002.0},
         {**BEFORE, "decode_steps": 400, "t_step_s": 3.3,
          "t_device_starved_s": 0.65, "decode_steps_device_paced": 300,
          "t_device_paced_s": 1.70, "t_now_s": 1004.0}]
# over a window of 10 s and 400 steps: starved 1.0 s, 300 steps paced in
# 1.8 s, 4 ms at the lock
WANT = {"engine.device_starved_share": 10.0,
        "engine.device_paced_step_ms": 6.0,
        "engine.device_paced_step_share": 75.0,
        "engine.lock_wait_ms_per_step": 0.01,
        "engine.device_starved_share_in_trace": 2.5,
        "engine.device_paced_step_ms_in_trace": 5.5,
        "engine.device_paced_step_share_in_trace": 80.0,
        "engine.step_ms_in_trace": 7.6}
IN_TRACE = [family for family in WANT if family.endswith("_in_trace")]
NEW = [m for m in BENCH["per_layer"]
       if m["name"].rsplit(".", 1)[0] in WANT]
OLD_KEYS = ("decode_steps", "t_step_s")


def record(before=BEFORE, after=AFTER, edges=EDGES):
    return {"engine_before": before, "engine_after": after,
            "engine_trace_edges": edges, "t_open": 100.0, "t_close": 110.0}


def test_the_device_account_is_declared_for_its_cells():
    """Eight readers: the four readings of the traced span in the
    closed-loop cells (``chat_mixed``'s driver hands ``trace_during`` no
    snapshot). The whole window's families are no metrics: the harness
    reads ``per_layer`` in the traced run alone, whose window holds the
    trace's reduction; they are the command's. A reader's cells are
    those of the ``engine.step_ms`` reader of its suffix."""
    assert set(WANT) == set(device_account.FAMILIES)
    assert sorted(m["name"] for m in NEW) == sorted(
        f"{family}.{suffix}" for family in IN_TRACE
        for suffix in ("decode", "stream"))
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for m in NEW:
        suffix = m["name"].rsplit(".", 1)[1]
        twin = by_name["engine.step_ms." + suffix]
        assert (m["layer"], m["source"]) == ("Engine scheduler",
                                             "program_counter")
        assert (m["moves"], m["workloads"]) == (twin["moves"],
                                                twin["workloads"])
    # appended: nothing the benchmark had stands behind them
    assert [m["name"] for m in BENCH["per_layer"][-len(NEW):]] \
        == [m["name"] for m in NEW]


def old_engine(snapshot):
    return {k: snapshot[k] for k in OLD_KEYS}


@pytest.mark.parametrize("name", sorted(WANT) + [m["name"] for m in NEW])
def test_reader_reads_its_value_and_none_where_there_is_nothing(name):
    """Every family through ``device_account.read``, and every metric
    file through the harness's loader."""
    if name in WANT:
        family = name

        def read(rec):
            return device_account.read(family, rec)
    else:
        family, read = name.rsplit(".", 1)[0], harness.load_metric(name).read
    assert read(record()) == pytest.approx(WANT[family])
    assert read({}) is None                     # a train cell's record
    # no decode step in the span
    flat = {**AFTER, "decode_steps": 100, "decode_steps_device_paced": 60}
    still = [EDGES[0], {**EDGES[1], "decode_steps": 150,
                        "decode_steps_device_paced": 100}]
    assert read(record(after=flat, edges=still)) is None
    # the parent's engine under these files: the old keys only. The step
    # over the traced span is made of counters that engine has
    old = record(old_engine(BEFORE), old_engine(AFTER),
                 edges=[old_engine(s) for s in EDGES])
    assert read(old) == (pytest.approx(7.6)
                         if family == "engine.step_ms_in_trace" else None)
    # a record with no snapshot inside the traced span (``chat_mixed``'s,
    # an untraced run's)
    if family in IN_TRACE:
        assert read(record(edges=[])) is None
        assert read({k: v for k, v in record().items()
                     if k != "engine_trace_edges"}) is None


def test_the_command_prints_the_families_of_an_untraced_line(tmp_path,
                                                            capsys):
    """``python3 -m benchmark.lib.device_account``: from a result line's
    ``counts``, a closed-loop cell's (``window_s``) and an open-loop
    cell's (no ``window_s``: the seconds between the two snapshots)."""
    closed = {"counts": {"engine_before": BEFORE, "engine_after": AFTER,
                         "engine_trace_edges": [], "window_s": 10.0}}
    opened = {"counts": {"engine_before": BEFORE, "engine_after": AFTER}}
    path = tmp_path / "lines.jsonl"
    path.write_text("a log line\n" + json.dumps(closed) + "\n"
                    + json.dumps(opened) + "\n")
    assert device_account.main([str(path)]) == 0
    first, second = map(json.loads, capsys.readouterr().out.splitlines())
    assert (first["line"], second["line"]) == (0, 1)
    for family, want in WANT.items():
        if family in IN_TRACE:
            assert first[family] is None and second[family] is None
        else:
            assert first[family] == pytest.approx(want)
    assert second["engine.device_starved_share"] == pytest.approx(
        100.0 * 1.0 / 10.5)
