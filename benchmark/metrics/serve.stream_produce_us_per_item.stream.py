"""Wall time of a SAMPLED item (one in 16 by its index) on its replica thread, from
the token taken off the request's queue to the chunk stored and reported
(span ``serve.stream.produce`` -> ``stream_produce_s`` over
``stream_items_timed_produce``). Less the producer's CPU per item: the thread's
wait for the interpreter lock and the stream's lock inside one item.

The ``.stream`` twin of ``serve.stream_produce_us_per_item.decode``: the same
reading in the cell whose clients' rate the Serve stream path sets
(``batch_decode``), where it moves ``serve_out_tokens_per_s.stream`` and that
metric's wider bound."""

from benchmark.lib import stream_phases

LAYER = "Serve ingress, router, replica"
UNIT = "us"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "serve_out_tokens_per_s.stream"


def read(rec):
    return stream_phases.us_per_item(rec, "stream_produce_s",
                                     "stream_items_timed_produce")
