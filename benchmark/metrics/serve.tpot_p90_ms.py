"""90th percentile over requests of the mean gap between output tokens."""

from benchmark.lib import readers

LAYER = "Serve ingress, router, replica"
UNIT = "ms"
BETTER = "lower"
SOURCE = "host_clock"
MOVES = "tpot_p50_ms"


def read(rec):
    return readers.tpot_percentile_ms(rec, 90)
