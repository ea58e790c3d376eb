"""Host clock between consecutive ``block_until_ready`` returns, median
step inside the window."""

from benchmark.lib import readers

LAYER = "Train step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "host_clock"
MOVES = "train_tokens_per_s_chip"


def read(rec):
    s = readers.median_step_s(rec)
    return None if s is None else 1e3 * s
