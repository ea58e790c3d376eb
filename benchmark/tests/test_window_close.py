"""When a closed-loop window closes (``drivers/serve_closed.py``): at
``run_seconds`` far under the cap; past the cap before the engine's
first request ends, a margin of steps short of it; a window that comes
out under the minimum fails the run and names the cell. The rule is fed
a fake engine on a fake clock: a step count that grows at a given rate,
read through a call that takes its own few milliseconds."""

import subprocess
import sys

import pytest

from benchmark import run as harness
from benchmark.drivers import serve_closed

T_OPEN, STEPS_OPEN = 1000.0, 5000


class FakeEngine:
    """``rate(t)`` decode steps a second, ``t`` seconds into the window;
    every look costs ``call_s``; the clock only moves when slept on."""

    def __init__(self, rate, call_s: float = 0.005):
        self.rate = rate if callable(rate) else (lambda t: rate)
        self.call_s = call_s
        self.now = T_OPEN
        self.done = 0.0
        self.looked_at = []             # seconds into the window

    def clock(self):
        return self.now

    def sleep(self, dt):
        assert dt >= 0
        t = self.now
        while t < self.now + dt:          # 10 ms at a time: rates may change
            step = min(0.01, self.now + dt - t)
            self.done += self.rate(t - T_OPEN) * step
            t += step
        self.now += dt

    def steps(self):
        self.looked_at.append(self.now - T_OPEN)
        self.sleep(self.call_s)
        return STEPS_OPEN + int(self.done)

    def watch(self, seconds, cap):
        return serve_closed.watch_window(
            self.steps, t_open=T_OPEN, seconds=seconds,
            steps_open=STEPS_OPEN, steps_cap=cap,
            clock=self.clock, sleep=self.sleep)


# (rate, seconds, steps to the first request's end): the four cells as
# the ledger's PR 35 lines have them, all under their caps
@pytest.mark.parametrize("rate, seconds, cap", [
    (66.6, 48, 3760), (98.0, 48, 7850), (16.3, 48, 7300), (70.0, 48, 7770)])
def test_far_under_the_cap_the_window_lasts_run_seconds(rate, seconds, cap):
    eng = FakeEngine(rate)
    close = eng.watch(seconds, cap)
    assert not close.closed_early
    assert close.t_closed == pytest.approx(T_OPEN + seconds, abs=1e-6)
    assert eng.done < cap                      # no request has ended
    # one look after a second, then one every LOOK_FAR_S: none inside the
    # traced span (seconds 2-6, its two edge snapshots a little later);
    # every LOOK_S only once the cap is within two far looks
    far, near = serve_closed.LOOK_FAR_S, serve_closed.LOOK_S
    assert close.looks == len(eng.looked_at)
    assert seconds / far <= close.looks <= seconds / far + 2 * far / near
    assert not [t for t in eng.looked_at if 1.5 < t < 6.9]
    gaps = [b - a for a, b in zip(eng.looked_at, eng.looked_at[1:])]
    assert all(g == pytest.approx(far, abs=0.01)
               or g == pytest.approx(near, abs=0.01) for g in gaps)
    kinds = [g > 2 * near for g in gaps]
    assert kinds == sorted(kinds, reverse=True)     # far looks, then near
    if False in kinds:      # the look that turned near saw the cap this close
        t = eng.looked_at[1 + kinds.index(False) - 1]
        assert cap - 2 * near * rate - rate * t <= 2 * far * rate + rate * 0.1
    assert serve_closed.mis_sized("a.cell", close, T_OPEN, cap, 7900) is None


@pytest.mark.parametrize("rate, seconds, cap", [
    (66.6, 90, 3760),        # batch_decode_moe asked for 90 s: ~55 s
    (98.0, 120, 7850),       # batch_decode asked for 120 s: ~78 s
    (100.0, 48, 3760),       # batch_decode_moe without its stack copies
    (179.0, 48, 7850),       # batch_decode at its program's own pace
    (300.0, 48, 7850)])
def test_past_the_cap_it_closes_a_margin_before_the_first_request_ends(
        rate, seconds, cap):
    eng = FakeEngine(rate)
    close = eng.watch(seconds, cap)
    assert close.closed_early
    assert close.t_closed < T_OPEN + seconds
    assert close.margin_steps == pytest.approx(
        max(serve_closed.MIN_MARGIN_STEPS, 2 * rate), abs=3)
    # closed inside the margin, and still a whole look of steps short of
    # the end: no request has ended, none ends before the driver reads
    # its counts
    left = cap - eng.done
    assert rate * serve_closed.LOOK_S * 0.95 <= left <= close.margin_steps
    assert close.steps_seen == int(eng.done)
    # as late as the margin allows: within a look of the threshold
    at_threshold = (cap - close.margin_steps) / rate
    window_s = close.t_closed - T_OPEN
    assert at_threshold <= window_s <= at_threshold + serve_closed.LOOK_S + 0.1
    assert window_s >= serve_closed.MIN_WINDOW_S
    assert serve_closed.mis_sized("a.cell", close, T_OPEN, cap, 7900) is None


def test_the_margin_never_falls_under_its_floor_and_outlives_a_stall():
    # a slow engine: two seconds of steps are 33, the margin stays 64
    slow = FakeEngine(16.3)
    close = slow.watch(400, 3000)
    assert close.closed_early and close.margin_steps == 64
    assert 64 - 16.3 * 1.1 <= 3000 - slow.done <= 64
    # an engine that stands still for 3 s just short of its margin and
    # then runs on at 100 steps/s: the margin is the highest rate's, so
    # the look after the stall still comes before the end
    stalls = FakeEngine(lambda t: 0.0 if 33.9 <= t < 36.9 else 100.0)
    close = stalls.watch(48, 3760)
    assert close.closed_early and close.margin_steps >= 200
    assert stalls.done < 3760 - 100 * 0.9
    # a stall under the FIRST look, whose low rate makes the cap look far:
    # the next look, LOOK_FAR_S on, sees the true rate
    early = FakeEngine(lambda t: 0.0 if t < 0.9 else 100.0)
    close = early.watch(48, 3760)
    assert close.closed_early and close.margin_steps >= 200
    assert 100 * 0.9 <= 3760 - early.done <= close.margin_steps


def test_a_look_that_comes_back_late_still_closes_the_window():
    # every look takes 0.7 s (a replica that answers slowly): the close
    # comes later than asked for by no more than one look
    eng = FakeEngine(66.6, call_s=0.7)
    close = eng.watch(48, 3760)
    assert not close.closed_early
    assert T_OPEN + 48 <= close.t_closed <= T_OPEN + 48.71
    eng = FakeEngine(100.0, call_s=0.7)
    close = eng.watch(48, 3760)
    assert close.closed_early and eng.done < 3760


@pytest.mark.parametrize("rate, cap", [(400.0, 3760), (2000.0, 7850),
                                       (100.0, 150)])
def test_a_window_under_the_minimum_fails_by_name(rate, cap):
    """A cell sized for a 16 ms step that now steps in under 3: the
    window would last a few seconds, and no rate is reported from it."""
    eng = FakeEngine(rate)
    close = eng.watch(48, cap)
    assert close.closed_early and eng.done < max(cap, 1)
    assert close.t_closed - T_OPEN < serve_closed.MIN_WINDOW_S
    message = serve_closed.mis_sized("olmoe-1b-7b-d3.batch_decode_moe",
                                     close, T_OPEN, cap, 3800)
    assert message.startswith("olmoe-1b-7b-d3.batch_decode_moe is MIS-SIZED")
    assert "no rate is reported" in message and "benchmark PR" in message


FAILS_AS_MIS_SIZED = """
import sys, types
from benchmark import drivers, run

def start_backend(r, trace):
    r.compiles = types.SimpleNamespace(snapshot=lambda: {})
    return {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}

def driver(r):
    raise drivers.MisSized(f"{r.workload} is MIS-SIZED for this engine")

run.start_backend = start_backend
drivers.load = lambda kind: types.SimpleNamespace(run=driver)
sys.exit(run.main(sys.argv[1:]))
"""


def test_a_mis_sized_cell_prints_no_result_and_exits_with_its_own_code():
    p = subprocess.run(
        [sys.executable, "-c", FAILS_AS_MIS_SIZED, "--workload",
         "olmoe-1b-7b-d3.batch_decode_moe", "--seed", "1"],
        cwd=harness.ROOT, capture_output=True, text=True, timeout=300)
    assert p.returncode == harness.EXIT_MIS_SIZED != 0
    assert p.stdout.strip() == ""
    last = p.stderr.strip().splitlines()[-1]
    assert "NO RESULT: olmoe-1b-7b-d3.batch_decode_moe is MIS-SIZED" in last
