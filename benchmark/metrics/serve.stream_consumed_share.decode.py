"""Items handed to the clients (``stream_items_consumed``: every ref a consumer's
``next`` was given) over items the engine put on its requests' streams
(``stream_puts``: tokens and end markers) in the window: 100 where the clients
keep up with the engine, under it where the Serve stream path sets their rate
and a backlog grows behind them (the mark of a stream-bound cell)."""

from benchmark.lib import stream_phases

LAYER = "Serve ingress, router, replica"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "serve_out_tokens_per_s"


def read(rec):
    return stream_phases.consumed_share(rec)
