"""Of the K/V blocks the decode steps' EVA attention read, the share that
held chunk SUMMARIES: blocks of the slots' summary tables a step reads
(``decode_kv_blocks_live_summary``: the summaries of every window before
the slot's own) over the blocks of both parts (``decode_kv_blocks_live``:
those, and the exact blocks of the slot's window so far), as deltas over
the window. ~50 % at 8-9 windows behind a slot (1,024-1,280 summary rows
beside 1-2,048 exact ones); 0 means no summary was read. A program with
no such counter, or a model that keeps no summaries (the counter stays
0), reads nothing."""

from benchmark.lib import engine_phases

LAYER = "Engine scheduler"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "serve_out_tokens_per_s"


def read(rec):
    summary = engine_phases.delta(rec, "decode_kv_blocks_live_summary")
    live = engine_phases.delta(rec, "decode_kv_blocks_live")
    if not summary or not live or live <= 0:
        return None
    return 100.0 * summary / live
