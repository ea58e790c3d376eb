"""Rows the window's decode steps' attention READ over the rows their
slots held: ``d decode_kv_rows_selected / (d decode_kv_blocks_live x
block_size)`` (both counted on the host at each dispatch: a live slot's
``min(length, index_topk)`` and its live blocks). ~11 % where 2,048 of
~18.5k rows are selected; 100 % if the selection is bypassed (every row
read) and while every slot is shorter than ``index_topk``. A program with
no such counter, or no selection (the counter stays 0), reads nothing."""

from benchmark.lib import engine_phases

LAYER = "Engine scheduler"
UNIT = "%"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "serve_out_tokens_per_s"


def read(rec):
    selected = engine_phases.delta(rec, "decode_kv_rows_selected")
    blocks = engine_phases.delta(rec, "decode_kv_blocks_live")
    if not selected or not blocks or blocks <= 0:
        return None
    return 100.0 * selected / (
        blocks * rec["traffic"]["engine"]["block_size"])
