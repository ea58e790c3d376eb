"""Collective time during which no compute runs on that chip, over the
traced window, averaged over the chips."""

LAYER = "Collectives"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "train_tokens_per_s_chip"


def read(rec):
    tr = rec.get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * tr["collective_exposed_s"] / tr["window_s"]
