"""Readers of the engine's account of the DEVICE, kept with no profiler
(``ray_tpu/llm/engine.py``: ``_enqueued``, ``_decode_step``;
docs/serving.md, "Is my chip waiting for my host?"). Where the engine
thread meets the device it asks ``jax.Array.is_ready()`` who was ahead:

- ``t_device_starved_s``: seconds between the host KNOWING the device's
  queue empty (the end of a blocking read of the newest program, or a
  probe that found the step in flight done) and the end of its next
  enqueue: a LOWER bound of the device time lost to the host, short by
  the lag between the device's finish and the host seeing it;
- ``decode_steps_device_paced`` / ``t_device_paced_s``: the decode steps
  that ran back to back with the step before them while the host stood
  waiting at both ends, and the seconds between those two read-backs'
  ends: one decode program's device time, launch gap included;
- ``t_lock_wait_s``: ``step()``'s wait for its lock, a float with no span.

Deltas between two of the engine's ``stats`` snapshots, as
``engine_phases.delta`` takes them: the window's edges
(``rec["engine_before"]`` / ``rec["engine_after"]``, traced run or not)
or the two taken INSIDE the traced span (``rec["engine_trace_edges"]``:
to be read against ``device_idle_share.*`` / ``decode_program_ms.*`` of
the same span, before ``Tracer.stop()`` starts reducing). The seconds
between the traced span's snapshots are the snapshots' own (``t_now_s``).
Only the readings of the traced span are metrics (``benchmark/metrics/
engine.*_in_trace.*``): the harness reads ``per_layer`` in the TRACED run
alone, whose whole window holds the seconds in which ``Tracer.stop()``
reduces the trace beside the engine, so a whole-window share read there
would say "starved" of a device-bound cell. The whole window is read from
an UNTRACED run's line, by the command below.
A program without the counters (the parent of the PR that added them), a
record without the snapshots or a span in which no decode step ran reads
``None``: the harness then leaves the metric out.

``python3 -m benchmark.lib.device_account <file of result lines>...``
prints every family, whole window and traced span, from the lines'
``counts``, which an UNTRACED run prints too: the engine read with no
profiler in the process.
"""

from __future__ import annotations

import json
import sys
from typing import Optional

from benchmark.lib.engine_phases import delta, ms_per_step
from benchmark.lib.stream_phases import _window_s


def in_trace(rec) -> dict:
    """The record cut to the traced span: its two snapshots stand where
    the window's stand. Empty where the run took none."""
    edges = rec.get("engine_trace_edges") or ()
    if len(edges) != 2:
        return {}
    span = delta({"engine_before": edges[0], "engine_after": edges[1]},
                 "t_now_s")
    return {"engine_before": edges[0], "engine_after": edges[1],
            "t_open": 0.0, "t_close": span}


def starved_share(rec) -> Optional[float]:
    """Seconds the device stood KNOWN to be starved by the host over the
    seconds of the span, %: the untraced twin of ``device_idle_share.*``,
    a lower bound of it (less what idled for want of requests)."""
    starved, seconds = delta(rec, "t_device_starved_s"), _window_s(rec)
    if starved is None or not seconds or not delta(rec, "decode_steps"):
        return None
    return 100.0 * starved / seconds


def paced_step_ms(rec) -> Optional[float]:
    """One decode program's time on the device as the host saw it, ms,
    over the steps it stood waiting for at both ends."""
    paced, secs = (delta(rec, "decode_steps_device_paced"),
                   delta(rec, "t_device_paced_s"))
    if not paced or paced <= 0 or secs is None:
        return None
    return 1e3 * secs / paced


def paced_step_share(rec) -> Optional[float]:
    """Share of the decode steps the host stood waiting for at both ends,
    %: a lower bound of the steps whose pace the device set (a read-back
    that found its tokens ready at a dispatch that was not starved is
    the device's step too, with no slack left on the host)."""
    paced, steps = (delta(rec, "decode_steps_device_paced"),
                    delta(rec, "decode_steps"))
    if paced is None or not steps or steps <= 0:
        return None
    return 100.0 * paced / steps


# family -> (reader, over the traced span alone?). Those of the traced
# span are the metrics; the others are for the command alone
FAMILIES = {
    "engine.device_starved_share": (starved_share, False),
    "engine.device_paced_step_ms": (paced_step_ms, False),
    "engine.device_paced_step_share": (paced_step_share, False),
    "engine.lock_wait_ms_per_step": (
        lambda rec: ms_per_step(rec, "t_lock_wait_s"), False),
    "engine.device_starved_share_in_trace": (starved_share, True),
    "engine.device_paced_step_ms_in_trace": (paced_step_ms, True),
    "engine.device_paced_step_share_in_trace": (paced_step_share, True),
    "engine.step_ms_in_trace": (
        lambda rec: ms_per_step(rec, "t_step_s"), True),
}


def read(family: str, rec) -> Optional[float]:
    reader, traced_span = FAMILIES[family]
    return reader(in_trace(rec) if traced_span else rec)


def record_of(line: dict) -> dict:
    """What the readers need of a result line's ``counts``. The window's
    seconds are the line's ``window_s`` (a closed-loop cell) or lie
    between its two snapshots' own clocks (an open-loop cell, whose
    driver takes them as the window opens and closes)."""
    counts = line.get("counts", {})
    rec = {k: counts[k] for k in ("engine_before", "engine_after",
                                  "engine_trace_edges") if k in counts}
    rec["t_open"] = 0.0
    rec["t_close"] = counts.get("window_s") or delta(rec, "t_now_s")
    return rec


def main(paths) -> int:
    for path in paths:
        with open(path) as f:
            lines = [ln for ln in f if ln.startswith("{")]
        for n, text in enumerate(lines):
            rec = record_of(json.loads(text))
            print(json.dumps({"file": path, "line": n, **{
                family: read(family, rec) for family in FAMILIES}}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
