"""The gated short convolutions' share of their roofline in a decode step.
ALL the conv mixers of a step read their weights once (``in_proj`` d x 3d,
``out_proj`` d x d and the filter's taps, bf16) and every slot's state,
``conv_L_cache - 1`` rows of d numbers, which they write back shifted:
``costs.shortconv_mixer_bytes`` over 819 GB/s (v5e), over the device time
A STEP of every operation of the decode program traced under a
``shortconv_*`` scope (``shortconv.mixer_share_of_step.decode``'s
numerator, by ``lib/scoped_ops.py``: all of the program's operations,
scanned or unrolled, listed or not). The operations are two thin matmuls
and an elementwise filter of 32 rows: bytes, not FLOPs, bound them.

Reads nothing where the run is untraced, the program has no such scope or
the costs know no ``shortconv_mixer_bytes``."""

from benchmark.lib import scoped_ops

NEEDLE = "shortconv_"

LAYER = "Kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "serve_out_tokens_per_s"


def read(rec):
    costs = rec.get("costs")
    if (not rec.get("trace") or not rec.get("peaks")
            or not hasattr(costs, "shortconv_mixer_bytes")):
        return None
    per_step_s = scoped_ops.scoped_seconds_per_call(rec, NEEDLE)
    if not per_step_s:
        return None
    slots = rec["traffic"]["engine"]["max_slots"]
    least_s = (costs.shortconv_mixer_bytes(rec["config"], slots)
               / rec["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / per_step_s
