"""Device time of the decode program per call (``XLA Modules`` events of
``jit_decode_step_paged``) in the traced part of the window.

The ``.stream`` twin of ``decode_program_ms.decode``: the same reading
in the cell whose clients' rate the Serve stream path sets
(``batch_decode``), where it moves ``serve_out_tokens_per_s.stream`` and
that metric's wider bound."""

from benchmark.lib import readers

PROGRAM = "decode_step_paged"

LAYER = "Model step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "serve_out_tokens_per_s.stream"


def read(rec):
    return readers.program_ms_per_call(rec, PROGRAM)
