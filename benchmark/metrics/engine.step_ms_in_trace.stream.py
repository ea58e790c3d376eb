"""``engine.step_ms.*`` over the TRACED span alone: ``t_step_s`` / ``decode_steps``
between the two ``stats`` snapshots taken inside it (``counts.engine_trace_edges``).
The engine's step over the SAME steps whose programs ``decode_program_ms.*`` times
and whose gaps ``device_idle_share.*`` counts, before ``Tracer.stop()`` reduces the
trace beside the engine for some seconds of the window.

The ``.stream`` twin of ``engine.step_ms_in_trace.decode``: the same reading in the cell whose
clients' rate the Serve stream path sets (``batch_decode``), where it moves
``serve_out_tokens_per_s.stream`` and that metric's wider bound."""

from benchmark.lib import device_account

LAYER = "Engine scheduler"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "serve_out_tokens_per_s.stream"


def read(rec):
    return device_account.read("engine.step_ms_in_trace", rec)
