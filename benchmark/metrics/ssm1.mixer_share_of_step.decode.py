"""The Mamba-1 mixers' share of a decode step's device time: the self
time of every operation of the decode program traced under an ``ssm1_*``
scope (``ssm1_in_proj``, ``ssm1_conv``, ``ssm1_x_proj``,
``ssm1_state_update``, ``ssm1_gate_out_proj``: the 26 mixers, not their
layers' SwiGLUs, norms or residuals) over the program's device time, from
the trace. By the costs the mixers are 2.74 GB of a 7.28 GB step (38 %);
a share well above that says their many small operations run under their
bytes' rate.

The trace's operations carry no scope; ``lib/scoped_ops.py`` takes each
operation's scope from the compiled program and sums over ALL of the
program's operations, so the reading does not depend on how many layer
bodies the program holds nor on which operations are among the ten
listed. A fusion counts under the scope of its root: where the compiler
fuses an ``ssm1_*`` operation into a neighbour's (the norm before
``ssm1_in_proj``), the few microseconds go with the root. Reads nothing
where the run is untraced or the program has no such scope."""

from benchmark.lib import readers, scoped_ops

NEEDLE = "ssm1_"
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
