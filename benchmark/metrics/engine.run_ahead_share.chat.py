"""Of the window's decode steps, the share DISPATCHED WHILE THE STEP BEFORE
WAS UNREAD (``d decode_steps_ahead / d decode_steps``, the engine's own
counts, read with its ``stats`` at both edges): the device went from that
step's program to the next with no host in between, and the read-back,
the emit and the delivery ran under a program. What is left is the step
after a request came in or ended (the device's inputs are rebuilt from
the host's arrays: one round trip a request's arrival and end, not a
token) and the steps a waiting request held back. An engine that runs a
step ahead only with every slot taken reads 0 in an open-loop cell; a
program without the counter reads nothing."""

from benchmark.lib import engine_phases

LAYER = "Engine scheduler"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "tpot_p50_ms"


def read(rec):
    ahead = engine_phases.delta(rec, "decode_steps_ahead")
    steps = engine_phases.delta(rec, "decode_steps")
    if ahead is None or not steps or steps <= 0:
        return None
    return 100.0 * ahead / steps
