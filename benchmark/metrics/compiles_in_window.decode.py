"""Compile-cache requests during the window; must be 0."""

from benchmark.lib import readers

LAYER = "Device"
UNIT = "count"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "serve_out_tokens_per_s"


def read(rec):
    return readers.compiles_in_window(rec)
