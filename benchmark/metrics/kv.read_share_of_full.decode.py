"""K/V blocks the decode steps' EVA attention read over the blocks ONE ROW
A POSITION would read at the same offsets: ``decode_kv_blocks_live`` (the
exact blocks of each slot's window so far and the summary blocks of
everything before it) over ``decode_kv_blocks_full_equivalent``
(``ceil((offset + 1) / block)`` a slot), as deltas over the window. ~12 %
at 16.4k-20.5k positions a slot (a window of 2,048, one summary per 16
positions); 100 means EVA is not applied in the timed program. A program
with no such counter, or a model of another kind (the counter stays 0),
reads nothing."""

from benchmark.lib import engine_phases

LAYER = "Engine scheduler"
UNIT = "%"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "serve_out_tokens_per_s"


def read(rec):
    live = engine_phases.delta(rec, "decode_kv_blocks_live")
    full = engine_phases.delta(rec, "decode_kv_blocks_full_equivalent")
    if not live or not full or full <= 0:
        return None
    return 100.0 * live / full
