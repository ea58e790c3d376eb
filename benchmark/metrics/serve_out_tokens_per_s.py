"""Tokens streamed to the clients per second by a full batch of long
generations: each client's rate over the WHOLE inter-token intervals it
saw inside the window, summed over the clients — all the work and all
the time of the window, less at most one step at each edge."""

from benchmark.lib.records import whole_interval_rate

LAYER = "end to end"
UNIT = "tokens/s"
BETTER = "higher"
SOURCE = "host_clock"
MOVES = None


def read(rec):
    total = 0.0
    for stamps in rec.get("stamps", []):
        rate, _, _ = whole_interval_rate(stamps, rec["t_open"], rec["t_close"])
        total += rate or 0.0
    return total or None
