"""Seconds the device stood KNOWN to be starved by the host over the seconds of the
traced span, %: ``t_device_starved_s`` (from the host knowing the device's queue
empty, by a blocking read of the newest program or a readiness probe, to the end of
its next enqueue) between the two ``stats`` snapshots taken inside the span
(``counts.engine_trace_edges``) over the seconds between them by their own clock
(``t_now_s``). To be read against ``device_idle_share.*`` of the SAME span: the
counter is a lower bound of the trace's idle share, and the difference is the lag
between the device's finish and the host seeing it, times the steps a second. (Over
the whole window: ``python3 -m benchmark.lib.device_account`` on an UNTRACED line; a
traced run's window holds the seconds in which ``Tracer.stop()`` reduces the trace
beside the engine.)

The ``.stream`` twin of ``engine.device_starved_share_in_trace.decode``: the same reading in the cell whose
clients' rate the Serve stream path sets (``batch_decode``), where it moves
``serve_out_tokens_per_s.stream`` and that metric's wider bound."""

from benchmark.lib import device_account

LAYER = "Engine scheduler"
UNIT = "%"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "serve_out_tokens_per_s.stream"


def read(rec):
    return device_account.read("engine.device_starved_share_in_trace", rec)
