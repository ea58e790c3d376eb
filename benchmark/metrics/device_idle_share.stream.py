"""1 - union of device-op intervals over the traced window.

The ``.stream`` twin of ``device_idle_share.decode``: the same reading
in the cell whose clients' rate the Serve stream path sets
(``batch_decode``), where it moves ``serve_out_tokens_per_s.stream`` and
that metric's wider bound."""

from benchmark.lib import readers

LAYER = "Device"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "serve_out_tokens_per_s.stream"


def read(rec):
    return readers.device_idle_share(rec)
