"""The Mamba-1 state update's share of its roofline in a decode step. The
update reads and writes every slot's ``S`` of every Mamba layer (8 B a
number: the decay is made in the kernel from ``dt`` and the layer's
``A``, which is read once a layer) and does ~6 operations and one
exponential a number: the least time of ALL the layers' updates of a
step is the larger of their bytes over 819 GB/s and their operations over
197 TFLOP/s (``costs.ssm_state_update_least_s``: the bytes, by two orders
of magnitude), over the kernel's device time A STEP: the self time of
every ``ssm1_state_update*`` operation of the decode program in the trace
(the Mosaic calls) over the program's executions.

EVERY such operation is summed, from the trace itself
(``lib/scoped_ops.py``), not the ten that ``breakdown.device_ops`` lists:
the model scans runs of like layers, so the 26 calls a step are three
operation names (7, 13 and 6 executions a step), of which any number may
be among the ten; ``ssm.state_update_roofline.decode`` divides by the
listed calls and would read a fraction of the truth here. The reading is
the same for one scanned operation, 26 unrolled ones or anything
between. Reads nothing where the run is untraced, the program has no
such kernel or the costs know no ``ssm_state_update_least_s``."""

from benchmark.lib import scoped_ops

NEEDLE = "ssm1_state_update"

LAYER = "Kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "serve_out_tokens_per_s"


def read(rec):
    costs = rec.get("costs")
    if (not rec.get("trace") or not rec.get("peaks")
            or not hasattr(costs, "ssm_state_update_least_s")):
        return None
    per_step_s = scoped_ops.named_seconds_per_call(rec, NEEDLE)
    if not per_step_s:
        return None
    slots = rec["traffic"]["engine"]["max_slots"]
    least_s = costs.ssm_state_update_least_s(rec["config"], rec["peaks"],
                                             slots)
    return 100.0 * least_s / per_step_s
