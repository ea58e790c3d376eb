"""Model FLOPs per token (forward + backward, no recomputation) x tokens
per second per chip over the chip's bf16 peak. Taken from the MEDIAN
step so that the traced run's start/stop stalls do not enter."""

from benchmark.lib import readers

LAYER = "Kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "host_clock"
MOVES = "train_tokens_per_s_chip"


def read(rec):
    s = readers.median_step_s(rec)
    if s is None or not rec.get("peaks"):
        return None
    flops = rec["costs"].train_flops_per_token(rec["config"], rec["seq_len"])
    rate = rec["tokens_per_step"] / rec["chips"] / s
    return 100.0 * flops * rate / rec["peaks"]["bf16_flops_per_s"]
