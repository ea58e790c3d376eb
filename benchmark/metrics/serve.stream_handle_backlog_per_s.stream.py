"""Growth of what waits BEFORE the clients, reported to the handle and not yet
asked for (``stream_items_reported`` less ``stream_items_consumed`` over the
window's seconds): above 0 the consuming side (``next`` and ``get`` on the
client's thread) is the stream path's ceiling.

The ``.stream`` twin of ``serve.stream_handle_backlog_per_s.decode``: the same
reading in the cell whose clients' rate the Serve stream path sets
(``batch_decode``), where it moves ``serve_out_tokens_per_s.stream`` and that
metric's wider bound."""

from benchmark.lib import stream_phases

LAYER = "Serve ingress, router, replica"
UNIT = "items/s"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "serve_out_tokens_per_s.stream"


def read(rec):
    return stream_phases.backlog_per_s(rec, "stream_items_reported",
                                       "stream_items_consumed")
