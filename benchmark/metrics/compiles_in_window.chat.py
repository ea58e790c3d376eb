"""Compile-cache requests during the window; must be 0."""

from benchmark.lib import readers

LAYER = "Device"
UNIT = "count"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "tpot_p50_ms"


def read(rec):
    return readers.compiles_in_window(rec)
