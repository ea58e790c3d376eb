"""Least time one decode step could take over the time the decode program
took. MEMORY-bound: bytes of every matmul weight (bf16, the served
dtype) plus the K/V of every live cached token, over 819 GB/s (v5e)."""

from benchmark.lib import readers

PROGRAM = "decode_step_paged"

LAYER = "Kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "serve_out_tokens_per_s"


def read(rec):
    ms = readers.program_ms_per_call(rec, PROGRAM)
    if ms is None or not rec.get("peaks"):
        return None
    least_s = rec["costs"].decode_step_bytes(
        rec["config"], rec["live_tokens_total"]) / rec["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (ms / 1e3)
