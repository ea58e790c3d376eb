"""Phase ``engine.emit`` (one span around a step's, or an admitted group's, tokens:
append, ``queue.put``, stop test, freeing the slot; and, once the step's last
device arrays are freed, the wait for the GIL while the woken streams run)
per decode step: ``t_emit_s`` / ``decode_steps``.

The ``.stream`` twin of ``engine.emit_ms_per_step.decode``: the same
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
    return engine_phases.ms_per_step(rec, "t_emit_s")
