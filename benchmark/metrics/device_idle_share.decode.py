"""1 - union of device-op intervals over the traced window."""

from benchmark.lib import readers

LAYER = "Device"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "serve_out_tokens_per_s"


def read(rec):
    return readers.device_idle_share(rec)
