"""Wall time of a SAMPLED item (one in 16 by its index) on its consumer's thread,
from ``next`` having been handed the ref to ``get`` having returned the chunk
(span ``serve.stream.consume`` -> ``stream_consume_s`` over
``stream_items_timed_consume``); the wait for the item is outside it."""

from benchmark.lib import stream_phases

LAYER = "Serve ingress, router, replica"
UNIT = "us"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "serve_out_tokens_per_s"


def read(rec):
    return stream_phases.us_per_item(rec, "stream_consume_s",
                                     "stream_items_timed_consume")
