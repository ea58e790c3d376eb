"""Compile-cache requests during the window; must be 0.

The ``.stream`` twin of ``compiles_in_window.decode``: the same reading
in the cell whose clients' rate the Serve stream path sets
(``batch_decode``), where it moves ``serve_out_tokens_per_s.stream`` and
that metric's wider bound."""

from benchmark.lib import readers

LAYER = "Device"
UNIT = "count"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "serve_out_tokens_per_s.stream"


def read(rec):
    return readers.compiles_in_window(rec)
