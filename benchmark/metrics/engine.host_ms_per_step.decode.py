"""The engine loop's own Python per decode step: the phases
``engine.schedule`` + ``engine.host_arrays`` + ``engine.decode_enqueue`` +
``engine.emit`` (their ``t_*_s`` counters / ``decode_steps``)."""

from benchmark.lib import engine_phases

LAYER = "Engine scheduler"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "serve_out_tokens_per_s"


def read(rec):
    return engine_phases.ms_per_step(rec, *engine_phases.HOST_PHASES)
