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
span in which no decode step ran reads nothing.

The ``.stream`` twin of ``decode_program_roofline``: the same reading in
the cell whose clients' rate the Serve stream path sets
(``batch_decode``), where it moves ``serve_out_tokens_per_s.stream`` and
that metric's wider bound."""

from benchmark.lib import readers

PROGRAM = "decode_step_paged"

LAYER = "Kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "serve_out_tokens_per_s.stream"


def read(rec):
    return readers.decode_program_roofline(rec, PROGRAM)
