"""Phase ``engine.prefill`` (an admitted group's host arrays, prefill, insert
and sample programs and the read-back) per decode step: ``t_prefill_s`` /
``decode_steps``, what admission adds to every running request's token gap."""

from benchmark.lib import engine_phases

LAYER = "Engine scheduler"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "tpot_p50_ms"


def read(rec):
    return engine_phases.ms_per_step(rec, "t_prefill_s")
