"""``memory_stats()["peak_bytes_in_use"]`` on the fullest chip."""

from benchmark.lib import readers

LAYER = "Device"
UNIT = "GiB"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "train_tokens_per_s_chip"


def read(rec):
    return readers.peak_hbm_gib(rec)
