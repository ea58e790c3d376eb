"""Growth of what waits BEFORE the replica threads, in the requests' queues
(``stream_puts`` less ``stream_takes`` over the window's seconds): above 0 the
producing side of the stream path (take, chunk, store, report) is its ceiling."""

from benchmark.lib import stream_phases

LAYER = "Serve ingress, router, replica"
UNIT = "items/s"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "serve_out_tokens_per_s"


def read(rec):
    return stream_phases.backlog_per_s(rec, "stream_puts", "stream_takes")
