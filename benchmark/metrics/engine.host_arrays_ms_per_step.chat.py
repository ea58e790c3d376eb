"""Phase ``engine.host_arrays`` (the decode step's three numpy arrays, five
host-to-device arrays and the key split)
per decode step: ``t_host_arrays_s`` / ``decode_steps``."""

from benchmark.lib import engine_phases

LAYER = "Engine scheduler"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "tpot_p50_ms"


def read(rec):
    return engine_phases.ms_per_step(rec, "t_host_arrays_s")
