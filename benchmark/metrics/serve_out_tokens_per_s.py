"""Tokens streamed to the clients per second by a full batch of long
generations: each client's rate over the WHOLE inter-token intervals it
saw inside the window, summed over the clients — all the work and all
the time of the window, less at most one step at each edge."""

from benchmark.lib import readers

LAYER = "end to end"
UNIT = "tokens/s"
BETTER = "higher"
SOURCE = "host_clock"
MOVES = None


def read(rec):
    return readers.streamed_tokens_per_s(rec)
