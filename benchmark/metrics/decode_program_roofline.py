"""Least time one decode step could take over the time the decode program
took. MEMORY-bound: bytes of every matmul weight (bf16, the served
dtype) plus the K/V of every live cached token, over 819 GB/s (v5e).

Numerator and denominator are of the SAME span, the traced one, and the
live tokens are the ENGINE's (its clients lag it): the driver reads the
engine's ``stats`` just after the tracer starts and just before it
stops (``rec["engine_trace_edges"]``), and the tokens a step's attention
read are ``d decode_kv_blocks_live x block_size / d decode_steps``, all
slots together. Block-granular, as the paged kernel reads: up to
``block_size - 1`` tokens a slot over the cached ones (16 of ~800 on
average at the cells' lengths: under 2 % of the K/V term, 0.3 % of the
bytes). An untraced run, a driver that takes no such snapshots or a
span in which no decode step ran reads nothing."""

from benchmark.lib import readers

PROGRAM = "decode_step_paged"

LAYER = "Kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "serve_out_tokens_per_s"


def live_tokens_per_step(rec):
    """Tokens whose K/V one decode step of the traced span read, summed
    over the slots; ``None`` where the span has no two snapshots, no
    counter or no step."""
    edges = rec.get("engine_trace_edges") or []
    if len(edges) != 2 or "decode_kv_blocks_live" not in edges[0]:
        return None
    steps = edges[1]["decode_steps"] - edges[0]["decode_steps"]
    blocks = (edges[1]["decode_kv_blocks_live"]
              - edges[0]["decode_kv_blocks_live"])
    if steps <= 0:
        return None
    return blocks * rec["traffic"]["engine"]["block_size"] / steps


def read(rec):
    ms = readers.program_ms_per_call(rec, PROGRAM)
    live = live_tokens_per_step(rec)
    if ms is None or live is None or not rec.get("peaks"):
        return None
    least_s = rec["costs"].decode_step_bytes(
        rec["config"], live) / rec["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (ms / 1e3)
