"""Token positions prefill computed that held no token: 1 -
``prefill_tokens`` / ``prefill_padded_tokens`` (rows x bucket of every prefill
program run in the window)."""

from benchmark.lib import engine_phases

LAYER = "Engine scheduler"
UNIT = "%"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "tpot_p50_ms"


def read(rec):
    return engine_phases.prefill_padding_share(rec)
