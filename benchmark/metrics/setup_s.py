"""Process start to the start of the measured window: imports, backend,
runtime, weights from the seed, compile-cache reads (or compiles, on a
checkout's first run), warm-up, correctness check."""

LAYER = "end to end"
UNIT = "s"
BETTER = "lower"
SOURCE = "host_clock"
MOVES = None


def read(rec):
    return rec.get("setup_s")
