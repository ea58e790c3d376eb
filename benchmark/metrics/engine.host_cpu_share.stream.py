"""The engine thread's own CPU time (``cpu_host_s``, ``time.thread_time``) over
the wall time of the four host phases: near 100 the loop's Python fills the
gap between decode programs, well under it the thread waited for the GIL.

The ``.stream`` twin of ``engine.host_cpu_share.decode``: the same
reading in the cell whose clients' rate the Serve stream path sets
(``batch_decode``), where it moves ``serve_out_tokens_per_s.stream`` and
that metric's wider bound."""

from benchmark.lib import engine_phases

LAYER = "Engine scheduler"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "serve_out_tokens_per_s.stream"


def read(rec):
    return engine_phases.host_cpu_share(rec)
