"""1 - union of device-op intervals over the traced window."""

from benchmark.lib import readers

LAYER = "Device"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "tpot_p50_ms"


def read(rec):
    return readers.device_idle_share(rec)
