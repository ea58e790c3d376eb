"""``tokens_generated`` / (``decode_steps`` x slots) over the window, from
the engine's ``stats`` read at both edges.

The ``.stream`` twin of ``engine.batch_occupancy.decode``: the same
reading in the cell whose clients' rate the Serve stream path sets
(``batch_decode``), where it moves ``serve_out_tokens_per_s.stream`` and
that metric's wider bound."""

from benchmark.lib import readers

LAYER = "Engine scheduler"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "serve_out_tokens_per_s.stream"


def read(rec):
    return readers.batch_occupancy(rec)
