"""From the backend being up (``import jax`` and ``jax.devices()`` have
returned) to the start of the measured window: the program's imports,
runtime, weights from the seed, compile-cache reads (or compiles, on a
checkout's first run), warm-up, correctness check. The machine's own
start of its TPU runtime before that (9-17 s, once 27, from one run to
the next on one machine) is no work of the program or the benchmark and
is printed apart, ``setup_phases``: ``import_jax``, ``backend_start``."""

LAYER = "end to end"
UNIT = "s"
BETTER = "lower"
SOURCE = "host_clock"
MOVES = None


def read(rec):
    return rec.get("setup_s")
