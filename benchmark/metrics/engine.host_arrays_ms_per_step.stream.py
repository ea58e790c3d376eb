"""Phase ``engine.host_arrays`` (the decode step's three numpy arrays, five
host-to-device arrays and the key split)
per decode step: ``t_host_arrays_s`` / ``decode_steps``.

The ``.stream`` twin of ``engine.host_arrays_ms_per_step.decode``: the
same reading in the cell whose clients' rate the Serve stream path sets
(``batch_decode``), where it moves ``serve_out_tokens_per_s.stream`` and
that metric's wider bound."""

from benchmark.lib import engine_phases

LAYER = "Engine scheduler"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "serve_out_tokens_per_s.stream"


def read(rec):
    return engine_phases.ms_per_step(rec, "t_host_arrays_s")
