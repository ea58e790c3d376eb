"""Of the rows the window's decode steps' routers assigned
(``moe_assignments``: live slots x experts per token x expert layers,
summed on the device), the share that went to experts THIS chip holds
(``moe_assignments_held``: the same device-side counts over the held
range), which are the rows its expert block computed. 6.25 % by uniform
routing at 16 of 256; it moves only if the share or the group limit is
wrong (a router that favoured the held groups would read higher). A
program with no such counter reads nothing."""

from benchmark.lib import engine_phases

LAYER = "Model step"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "serve_out_tokens_per_s"


def read(rec):
    held = engine_phases.delta(rec, "moe_assignments_held")
    routed = engine_phases.delta(rec, "moe_assignments")
    if held is None or not routed or routed <= 0:
        return None
    return 100.0 * held / routed
