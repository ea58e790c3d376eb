"""``memory_stats()["peak_bytes_in_use"]`` on the fullest chip.

The ``.stream`` twin of ``peak_hbm_gib.decode``: the same reading in the
cell whose clients' rate the Serve stream path sets (``batch_decode``),
where it moves ``serve_out_tokens_per_s.stream`` and that metric's wider
bound."""

from benchmark.lib import readers

LAYER = "Device"
UNIT = "GiB"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "serve_out_tokens_per_s.stream"


def read(rec):
    return readers.peak_hbm_gib(rec)
