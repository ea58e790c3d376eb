"""Growth of what waits BEFORE the replica threads, in the requests' queues
(``stream_puts`` less ``stream_takes`` over the window's seconds): above 0 the
producing side of the stream path (take, chunk, store, report) is its ceiling.

The ``.stream`` twin of ``serve.stream_replica_backlog_per_s.decode``: the same
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
    return stream_phases.backlog_per_s(rec, "stream_puts", "stream_takes")
