"""Tokens streamed to the clients per second by a full batch of long
generations: each client's rate over the WHOLE inter-token intervals it
saw inside the window, summed over the clients — all the work and all
the time of the window, less at most one step at each edge.

``serve_out_tokens_per_s`` in ``batch_decode``, a metric of its own
since PR 36: there the 32 clients receive fewer tokens than the engine
generates (the Serve stream path on the host is the ceiling, not the
decode step), its runs spread by 1.5-2.6 % where the other closed cells'
spread by 0.3-0.6 %, and one bound cannot be right for both."""

from benchmark.lib import readers

LAYER = "end to end"
UNIT = "tokens/s"
BETTER = "higher"
SOURCE = "host_clock"
MOVES = None


def read(rec):
    return readers.streamed_tokens_per_s(rec)
