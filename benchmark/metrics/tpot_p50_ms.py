"""Median over the window's requests of the mean gap between a request's
output tokens, at the client."""

from benchmark.lib import readers

LAYER = "end to end"
UNIT = "ms"
BETTER = "lower"
SOURCE = "host_clock"
MOVES = None


def read(rec):
    return readers.tpot_percentile_ms(rec, 50)
