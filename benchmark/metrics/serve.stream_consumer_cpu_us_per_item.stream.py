"""CPU time of the consuming threads (``stream_consumer_cpu_s``) per item handed to
them (``stream_items_consumed``). It holds whatever else the reading thread does
between two items: in the cells the benchmark client's stamp and append, in
Serve's HTTP proxy the SSE write.

The ``.stream`` twin of ``serve.stream_consumer_cpu_us_per_item.decode``: the same
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
    return stream_phases.us_per_item(rec, "stream_consumer_cpu_s",
                                     "stream_items_consumed")
