"""The engine loop's own Python per decode step: the phases
``engine.schedule`` + ``engine.host_arrays`` + ``engine.decode_enqueue`` +
``engine.emit`` (their ``t_*_s`` counters / ``decode_steps``).

The ``.stream`` twin of ``engine.host_ms_per_step.decode``: the same
reading in the cell whose clients' rate the Serve stream path sets
(``batch_decode``), where it moves ``serve_out_tokens_per_s.stream`` and
that metric's wider bound."""

from benchmark.lib import engine_phases

LAYER = "Engine scheduler"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "serve_out_tokens_per_s.stream"


def read(rec):
    return engine_phases.ms_per_step(rec, *engine_phases.HOST_PHASES)
