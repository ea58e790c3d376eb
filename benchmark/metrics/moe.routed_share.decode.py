"""Rows the expert FFN processed for live slots in the window's decode
steps (``moe_assignments``, summed on the device by the decode program)
over what a dropless FFN must process (``moe_assignments_expected``: live
slots x experts per token x layers, counted on the host). Must read 100:
under it, some chosen expert was not computed."""

from benchmark.lib import engine_phases

LAYER = "Model step"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "serve_out_tokens_per_s"


def read(rec):
    done = engine_phases.delta(rec, "moe_assignments")
    due = engine_phases.delta(rec, "moe_assignments_expected")
    if done is None or not due or due <= 0:
        return None
    return 100.0 * done / due
