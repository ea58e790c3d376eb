"""Phase ``engine.decode_enqueue`` (the calls of the decode and sample programs until
they return: dispatch, not execution)
per decode step: ``t_enqueue_s`` / ``decode_steps``."""

from benchmark.lib import engine_phases

LAYER = "Engine scheduler"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "tpot_p50_ms"


def read(rec):
    return engine_phases.ms_per_step(rec, "t_enqueue_s")
