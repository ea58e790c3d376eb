"""Phase ``engine.deliver`` (a decode step's tokens put on their requests' queues,
as a rule under the next step's program; each put wakes a replica thread) per
decode step: ``t_deliver_s`` / ``decode_steps``."""

from benchmark.lib import engine_phases

LAYER = "Engine scheduler"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "serve_out_tokens_per_s"


def read(rec):
    return engine_phases.ms_per_step(rec, "t_deliver_s")
