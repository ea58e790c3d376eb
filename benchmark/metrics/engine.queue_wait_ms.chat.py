"""Mean wait of a request between its submission (or its preemption) and its
admission to a slot: ``queue_wait_s`` / ``admitted`` over the window, ms. Part
of TTFT, and of TPOT only through what admission does to the running batch."""

from benchmark.lib import engine_phases

LAYER = "Engine scheduler"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "tpot_p50_ms"


def read(rec):
    wait = engine_phases.delta(rec, "queue_wait_s")
    admitted = engine_phases.delta(rec, "admitted")
    if wait is None or not admitted or admitted <= 0:
        return None
    return 1e3 * wait / admitted
