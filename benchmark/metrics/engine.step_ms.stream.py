"""Wall time inside ``engine.step()`` per decode step of the window
(``t_step_s`` / ``decode_steps``): admission, prefill, the host phases and the
wait for the device, from before the step takes the engine's lock.

The ``.stream`` twin of ``engine.step_ms.decode``: the same reading in
the cell whose clients' rate the Serve stream path sets
(``batch_decode``), where it moves ``serve_out_tokens_per_s.stream`` and
that metric's wider bound."""

from benchmark.lib import engine_phases

LAYER = "Engine scheduler"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "serve_out_tokens_per_s.stream"


def read(rec):
    return engine_phases.ms_per_step(rec, "t_step_s")
