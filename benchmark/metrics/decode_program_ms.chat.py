"""Device time of the decode program per call (``XLA Modules`` events of
``jit_decode_step_paged``) in the traced part of the window."""

from benchmark.lib import readers

PROGRAM = "decode_step_paged"

LAYER = "Model step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "tpot_p50_ms"


def read(rec):
    return readers.program_ms_per_call(rec, PROGRAM)
