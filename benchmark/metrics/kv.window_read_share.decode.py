"""Rows the decode steps' sliding-window layers read over the rows they
would read as full layers: blocks of the slots' SLIDING-kind tables a
step's attention reads (``decode_kv_blocks_live_window``: from the block
that holds a slot's first visible position to its tail block) over those
of the FULL-kind tables (``decode_kv_blocks_live``), as deltas over the
window. ~11-13 % at a 1024-token window and 8.2k-9.4k tokens a slot; 100
means the window is not applied in the timed program. A program with no
such counter, or a model with one kind of layer (the counter stays 0),
reads nothing."""

from benchmark.lib import engine_phases

LAYER = "Engine scheduler"
UNIT = "%"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "serve_out_tokens_per_s"


def read(rec):
    window = engine_phases.delta(rec, "decode_kv_blocks_live_window")
    full = engine_phases.delta(rec, "decode_kv_blocks_live")
    if not window or not full or full <= 0:
        return None
    return 100.0 * window / full
