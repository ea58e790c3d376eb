"""Of the rows the window's decode steps computed for a request, the share
READ AND NOT BOOKED (``d decode_rows_dropped / (d tokens_generated + d
decode_rows_dropped)``, the engine's own counts, read with its ``stats``
at both edges): the step ahead was dispatched for the slots as they
stood, and the request of such a row ended in the step before (its
length, a stop token). One row a request that ends under a step ahead:
about 1 / (its tokens + 1). What speculation a row costs: the row rode a
program that ran anyway for the other slots, so it is device time only
where it was the step's last live row. A program without the counter
reads nothing."""

from benchmark.lib import engine_phases

LAYER = "Engine scheduler"
UNIT = "%"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "tpot_p50_ms"


def read(rec):
    dropped = engine_phases.delta(rec, "decode_rows_dropped")
    booked = engine_phases.delta(rec, "tokens_generated")
    if dropped is None or booked is None or dropped + booked <= 0:
        return None
    return 100.0 * dropped / (dropped + booked)
