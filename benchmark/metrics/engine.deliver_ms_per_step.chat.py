"""Phase ``engine.deliver`` (a decode step's tokens put on their requests' queues,
as a rule under the next step's program; each put wakes a replica thread) per
decode step: ``t_deliver_s`` / ``decode_steps``.

The ``.chat`` twin of ``engine.deliver_ms_per_step.decode``: the same
reading in ``chat_mixed``, where it moves ``tpot_p50_ms``."""

from benchmark.lib import engine_phases

LAYER = "Engine scheduler"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "tpot_p50_ms"


def read(rec):
    return engine_phases.ms_per_step(rec, "t_deliver_s")
