"""Tokens trained per second per chip: whole steps between the first and
the last ``block_until_ready`` inside the window, input pipeline running."""

from benchmark.lib import readers

LAYER = "end to end"
UNIT = "tokens/s/chip"
BETTER = "higher"
SOURCE = "host_clock"
MOVES = None


def read(rec):
    return readers.train_rate_tokens_per_s_chip(rec)
