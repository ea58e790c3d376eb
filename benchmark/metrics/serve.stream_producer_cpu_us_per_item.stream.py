"""CPU time of the replica threads (``stream_producer_cpu_s``: each thread's own
``thread_time`` since it took its stream up, published at sampled items) per
item they reported (``stream_items_reported``): what one streamed item costs
the producing side in Python, waiting left out.

The ``.stream`` twin of ``serve.stream_producer_cpu_us_per_item.decode``: the same
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
    return stream_phases.us_per_item(rec, "stream_producer_cpu_s",
                                     "stream_items_reported")
