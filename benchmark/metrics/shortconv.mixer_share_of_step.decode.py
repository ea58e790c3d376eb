"""The gated short convolutions' share of a decode step's device time: the
self time of every operation of the decode program traced under a
``shortconv_*`` scope (``shortconv_in_proj``, ``shortconv_gate_conv``,
``shortconv_out_proj``: the conv mixers, not their layers' FFNs, norms or
residuals) over the program's device time, from the trace. By the costs
the eight mixers are 0.27 GB of a 10.9 GB step (2.5 %); a share well above
that says their 24 small operations between the expert calls run under
their bytes' rate.

The trace's operations carry no scope; ``lib/scoped_ops.py`` takes each
operation's scope from the compiled program and sums over ALL of the
program's operations, so the reading does not depend on how many layer
bodies the program holds nor on which operations are among the ten
listed. A fusion counts under the scope of its root. Reads nothing where
the run is untraced or the program has no such scope."""

from benchmark.lib import readers, scoped_ops

NEEDLE = "shortconv_"
PROGRAM = "decode_step_paged"

LAYER = "Model step"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "serve_out_tokens_per_s"


def read(rec):
    ms = readers.program_ms_per_call(rec, PROGRAM)
    if not ms:
        return None
    mixers_s = scoped_ops.scoped_seconds_per_call(rec, NEEDLE)
    return None if mixers_s is None else 100.0 * mixers_s / (ms / 1e3)
