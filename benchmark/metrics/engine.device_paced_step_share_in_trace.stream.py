"""Share of the traced span's decode steps at which the device set the pace with the
host standing by, %: ``decode_steps_device_paced`` / ``decode_steps`` between the two
``stats`` snapshots of ``counts.engine_trace_edges`` (steps that ran back to back with
the step before them while the host stood waiting for each to end). A LOWER bound of
the device-paced steps: near 100 the span is device-bound with slack on the host; what
is missing is steps the host came late to its read-back for, which starved the device
only where ``engine.device_starved_share_in_trace.*`` says so. Also how many steps
``engine.device_paced_step_ms_in_trace.*`` averages: under 50 that mean leans towards
the longer steps and need not match ``decode_program_ms.*``.

The ``.stream`` twin of ``engine.device_paced_step_share_in_trace.decode``: the same reading in the cell whose
clients' rate the Serve stream path sets (``batch_decode``), where it moves
``serve_out_tokens_per_s.stream`` and that metric's wider bound."""

from benchmark.lib import device_account

LAYER = "Engine scheduler"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "serve_out_tokens_per_s.stream"


def read(rec):
    return device_account.read("engine.device_paced_step_share_in_trace", rec)
