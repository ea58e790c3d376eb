"""CPU seconds of the engine thread, the replica threads and the consuming
threads (``engine_thread_cpu_s`` + ``stream_producer_cpu_s`` +
``stream_consumer_cpu_s``) over the window's wall seconds: how busy the threads
the program accounts for kept the host. Well under 100 they waited (for the
device, for tokens); near or over 100 the interpreter lock is contended (a
thread's CPU clock also runs in native code that let the lock go and in the
kernel: over 100 is no contradiction).

The ``.stream`` twin of ``serve.python_cpu_share.decode``: the same
reading in the cell whose clients' rate the Serve stream path sets
(``batch_decode``), where it moves ``serve_out_tokens_per_s.stream`` and that
metric's wider bound."""

from benchmark.lib import stream_phases

LAYER = "Serve ingress, router, replica"
UNIT = "%"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "serve_out_tokens_per_s.stream"


def read(rec):
    return stream_phases.python_cpu_share(rec)
