"""Requests preempted (recompute) inside the window: ``stats["preemptions"]``."""

LAYER = "Engine scheduler"
UNIT = "count"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "tpot_p50_ms"


def read(rec):
    a, b = rec.get("engine_before"), rec.get("engine_after")
    if not a or not b:
        return None
    return float(b["preemptions"] - a["preemptions"])
