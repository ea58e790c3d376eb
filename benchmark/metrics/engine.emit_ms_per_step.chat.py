"""Phase ``engine.emit`` (one span around a step's, or an admitted group's, tokens:
append, ``queue.put``, stop test, freeing the slot; and, once the step's last
device arrays are freed, the wait for the GIL while the woken streams run)
per decode step: ``t_emit_s`` / ``decode_steps``."""

from benchmark.lib import engine_phases

LAYER = "Engine scheduler"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "tpot_p50_ms"


def read(rec):
    return engine_phases.ms_per_step(rec, "t_emit_s")
