"""Time the loop waited for its next batch over the window."""

LAYER = "Train step"
UNIT = "%"
BETTER = "lower"
SOURCE = "host_clock"
MOVES = "train_tokens_per_s_chip"


def read(rec):
    if "input_wait_s" not in rec:
        return None
    return 100.0 * rec["input_wait_s"] / (rec["t_close"] - rec["t_open"])
