"""``tokens_generated`` / (``decode_steps`` x slots) over the window, from
the engine's ``stats`` read at both edges."""

from benchmark.lib import readers

LAYER = "Engine scheduler"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "serve_out_tokens_per_s"


def read(rec):
    return readers.batch_occupancy(rec)
