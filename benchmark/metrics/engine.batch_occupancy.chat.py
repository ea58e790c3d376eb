"""``tokens_generated`` / (``decode_steps`` x slots) over the window, from
the engine's ``stats`` read at both edges."""

from benchmark.lib import readers

LAYER = "Engine scheduler"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "tpot_p50_ms"


def read(rec):
    return readers.batch_occupancy(rec)
